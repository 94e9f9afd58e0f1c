"""Device time of the ops inside the program's ``ssm`` scope (the Mamba-2
mixer: projections, conv, scan, gated norm, and their gradients:
``nemotron_scope.py``) / device busy time, from the trace. A part of
``attn_time_share``, under which the mixers stand."""

import nemotron_scope


def read(run):
    seconds = nemotron_scope.seconds_in("ssm")
    busy = run["trace"].get("busy_s")
    if seconds is None or not busy:
        return None
    return 100.0 * seconds / busy
