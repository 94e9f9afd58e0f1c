"""Device time of the all-reduce operation / rank 0's call time, both over
the traced calls at ``judged_large``. Its complement is the share of a
call the driver spends on the host: reading payloads back, placing them
again, launching, reading the result back."""

import trace_reduce


def read(run):
    rec = run["record"]
    calls = rec.get("call_s", {}).get(str(rec.get("judged_large")))
    ops = trace_reduce.collective_ops(run["trace"])
    seconds = sum(v["s"] for v in ops.values())
    if not calls or not seconds:
        return None
    return 100.0 * seconds / sum(calls)
