"""Device time of the Pallas attention kernels (forward with residuals,
backward dq, backward dk/dv: the only Pallas calls in the step, see
``trace_reduce.pallas_ops``) / device busy time, from the trace."""

import trace_reduce


def read(run):
    busy = run["trace"].get("busy_s")
    seconds = sum(v["s"] for v in trace_reduce.pallas_ops(run["trace"]).values())
    if not busy or not seconds:
        return None
    return 100.0 * seconds / busy
