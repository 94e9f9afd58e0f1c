"""Device time of the ops whose ``jax.named_scope`` is ``embed`` or
``logits_loss`` (the embedding gather, the final layer norm, the logits
matmul, the cross-entropy, and their gradients) / device busy time, from
the trace: every busy instant of the window goes to the scope of the
innermost op running (``program_spans.scope_seconds``)."""

import program_spans


def read(run):
    return program_spans.run_scope_share("embed", "logits_loss")
