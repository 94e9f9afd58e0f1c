"""Backend compiles of the train step's program (``jit(step)``) before the
window opened (``setup_spans.py``): 1 is the floor, 2 is a step that saw
its state under two layouts."""

import setup_spans


def read(run):
    records = setup_spans.of_run(run)
    return None if records is None else setup_spans.step_compiles(records)
