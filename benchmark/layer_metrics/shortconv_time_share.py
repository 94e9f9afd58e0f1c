"""Device time of the ops inside the program's ``shortconv`` scope (the
gated short-conv mixer: in-projection, gates and taps, out-projection,
and their gradients: ``lfm2_scope.py``) / device busy time, from the
trace. A part of ``attn_time_share``, under which the mixers stand."""

import lfm2_scope


def read(run):
    seconds = lfm2_scope.seconds_in("shortconv")
    busy = run["trace"].get("busy_s")
    if seconds is None or not busy:
        return None
    return 100.0 * seconds / busy
