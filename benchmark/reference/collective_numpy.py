"""Plain reference for the collectives: numpy over the ranks' payloads,
in rank order, on the host. ``payloads[r]`` is what rank ``r`` passed in;
the return value is what every rank must get back, as one array. One
function per collective a cell calls, named as ``mpi_tpu`` names it."""

from __future__ import annotations

import numpy as np


def allreduce(payloads, op="sum"):
    if op != "sum":
        raise ValueError(f"no reference for allreduce op {op!r} yet")
    total = np.array(payloads[0], copy=True)
    for p in payloads[1:]:
        total = total + p
    return total
