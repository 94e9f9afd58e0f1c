"""Share of the traced window in which no operation ran on the device:
1 - union of the device-op intervals / window, mean over the chips used."""


def read(run):
    share = run["trace"].get("idle_share")
    return None if share is None else 100.0 * share
