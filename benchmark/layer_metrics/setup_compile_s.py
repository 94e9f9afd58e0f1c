"""Seconds of set-up inside a backend compile, a read of the persistent
cache included: the union of the program's ``compile`` records that began
before the window opened (``setup_spans.py``). The first of the four
set-up readers, so it prints the set-up table."""

import setup_spans


def read(run):
    records = setup_spans.of_run(run)
    if records is None:
        return None
    for line in setup_spans.table_lines(records):
        print(line, flush=True)
    return setup_spans.union_s(records, ("compile",))
