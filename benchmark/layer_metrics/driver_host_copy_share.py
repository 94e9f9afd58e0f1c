"""Share of rank 0's traced calls at ``judged_large`` that the xla driver
spends copying: the program's ``xla.coll.host_read`` (payloads device ->
host), ``xla.coll.device_put`` (host -> device) and ``xla.coll.read_back``
(wait for the device, results device -> host) spans of this collective,
summed inside the window, / the sum of the call times. 0.0 where the
driver has its ``xla.coll.leader`` spans and no copy is left."""

import program_spans


def read(run):
    found = program_spans.traced_calls(run)
    return None if found is None else program_spans.host_copy_share(*found)
