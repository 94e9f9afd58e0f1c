"""Median time a step waited for its batch: the benchmark's own host span
around ``next(loader)``, in milliseconds."""

import statistics


def read(run):
    waits = run["record"].get("loader_wait_s")
    return 1e3 * statistics.median(waits) if waits else None
