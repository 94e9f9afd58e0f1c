"""Device time of the ops whose ``jax.named_scope`` is ``optimizer``
(``opt.update`` and ``optax.apply_updates``) / device busy time, from the
trace: every busy instant of the window goes to the scope of the
innermost op running (``program_spans.scope_seconds``). A fused op has
the scope of the instruction the compiler names it by: on the v5e a
weight-gradient matmul fused with its AdamW update counts under the
matmul's scope, not here (PERF.md, section 5)."""

import program_spans


def read(run):
    return program_spans.run_scope_share("optimizer")
