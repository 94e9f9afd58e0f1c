"""What the loader's producer thread needs for one batch: the median
duration of the program's ``data.batch`` span (``source(step)`` and the
placement on the mesh), in milliseconds. The loader holds the step back
when this nears the step time, whatever ``loader_wait_ms`` reads."""

import program_spans


def read(run):
    got = program_spans.of_run()
    if got is None:
        return None
    return program_spans.median_ms(got["rows"], "data.batch")
