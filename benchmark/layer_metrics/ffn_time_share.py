"""Device time of the ops whose ``jax.named_scope`` is ``ffn`` (a block's
second layer norm, its two matmuls and the GELU, and their gradients) /
device busy time, from the trace: every busy instant of the window goes
to the scope of the innermost op running
(``program_spans.scope_seconds``)."""

import program_spans


def read(run):
    return program_spans.run_scope_share("ffn")
