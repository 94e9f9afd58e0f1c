"""Percent of set-up's compile requests to the persistent cache that it
served (``setup_spans.py``): near 100 on a warm machine, 0 on the first run
of a checkout; nothing where no request went to the cache."""

import setup_spans


def read(run):
    records = setup_spans.of_run(run)
    return None if records is None else setup_spans.hit_share(records)
