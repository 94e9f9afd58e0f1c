"""Device time of the ops whose ``jax.named_scope`` is ``attn`` (a block's
first layer norm, the q/k/v/o projections, rope, the flash kernels, and
the gradients of all of them) / device busy time, from the trace: every
busy instant of the window goes to the scope of the innermost op running
(``program_spans.scope_seconds``)."""

import program_spans


def read(run):
    return program_spans.run_scope_share("attn")
