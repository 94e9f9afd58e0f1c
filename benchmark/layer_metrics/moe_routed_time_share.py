"""Device time of the routed experts — scopes ``moe.route`` (scores and
top-k) and ``moe.routed`` (the sort, the dispatch buffer, the expert
products, the combine, and their gradients: ``nemotron_scope.py``) /
device busy time, from the trace. A part of ``ffn_time_share``; the shared
expert (``moe.shared``) is not in it."""

import nemotron_scope


def read(run):
    route = nemotron_scope.seconds_in("moe.route")
    routed = nemotron_scope.seconds_in("moe.routed")
    busy = run["trace"].get("busy_s")
    if routed is None or not busy:
        return None
    return 100.0 * ((route or 0.0) + routed) / busy
