"""The compiled all-reduce against the interconnect: bus bytes of one call
at ``judged_large`` (bytes x 2(n-1)/n) / the median device duration of the
all-reduce operation / the chip's published interconnect rate
(``peaks.json``: 1,600 Gbit/s a chip = 200 GB/s, all links together; a 2x2
mesh uses a part of them, so this is a share of what the chip could ever
move, and the interconnect, not compute or HBM, is what bounds it)."""

import statistics

import trace_reduce


def read(run):
    rec, peaks = run["record"], run["peaks"]
    ops = trace_reduce.collective_ops(run["trace"])
    if not ops or not peaks or "ici_gbytes_per_s" not in peaks:
        return None
    # One instruction does the whole reduction of a call; where the
    # compiler splits it, the call's device time is the sum over names.
    per_call = sum(statistics.median(v["durations"]) for v in ops.values())
    n = rec["ranks"]
    bus = rec["judged_large"] * 2 * (n - 1) / n
    return 100.0 * bus / per_call / (peaks["ici_gbytes_per_s"] * 1e9)
