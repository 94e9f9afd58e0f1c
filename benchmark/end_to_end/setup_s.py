"""Seconds from process start to the first measured operation: imports,
building and placing the state, the correctness programs, warm-up and,
in a run that compiles, compilation."""


def read(run):
    return run["record"].get("setup_s")
