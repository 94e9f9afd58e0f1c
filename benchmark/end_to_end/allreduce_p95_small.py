"""95th percentile (nearest rank) of rank 0's call times at the traffic's
``judged_small`` size, in microseconds, over every call of the window."""

import math


def read(run):
    rec = run["record"]
    samples = rec.get("call_s", {}).get(str(rec.get("judged_small")))
    if not samples:
        return None
    ordered = sorted(samples)
    return ordered[math.ceil(0.95 * len(ordered)) - 1] * 1e6
