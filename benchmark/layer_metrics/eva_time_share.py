"""Device time of the ops inside the program's ``eva`` scope (pooling, the
window's and the summaries' attention kernels, the merge, and their
gradients: ``eva_scope.py``) / device busy time, from the trace. A part of
``attn_time_share``."""

import eva_scope


def read(run):
    stages = eva_scope.of_run()
    busy = run["trace"].get("busy_s")
    if not stages or not busy:
        return None
    return 100.0 * sum(stages.values()) / busy
