"""Bus bandwidth at the traffic's ``judged_large`` size, in GB/s: bytes x
2(n-1)/n (the NCCL-tests convention: what a ring must move over each
link) x rank 0's calls of the window / the sum of their times. That is
the size over OSU's average latency: every call and all of its time
count, so a stalled call shows. The median is in ``notes.median_us``."""


def read(run):
    rec = run["record"]
    size = rec.get("judged_large")
    samples = rec.get("call_s", {}).get(str(size))
    if not samples:
        return None
    n = rec["ranks"]
    return size * 2 * (n - 1) / n * len(samples) / sum(samples) / 1e9
