"""Seconds of set-up spent tracing jitted functions and lowering them to
MLIR: the union of the program's ``trace`` and ``lower`` records that began
before the window opened (``setup_spans.py``). What a warm compile cache
does not spare."""

import setup_spans


def read(run):
    records = setup_spans.of_run(run)
    if records is None:
        return None
    return setup_spans.union_s(records, ("trace", "lower"))
