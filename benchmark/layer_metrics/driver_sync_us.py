"""What a call costs outside the leader's work: median over the traced
calls of rank 0's call time minus the ``xla.coll.leader`` span of the same
call (the k-th call with the k-th span), in microseconds: arrival skew,
the two barrier wake-ups of ``_CollectiveSession``, the facade, and what
the kind does inside its timed call round ``mpi.<op>``. It is read in the
calls at ``judged_large`` because the traced window holds no others, and
on the v5e it is not the same at every size: 17.2 ms there, 2.1 ms in
traced 4 B calls (PERF.md, section 5)."""

import program_spans


def read(run):
    found = program_spans.traced_calls(run)
    return None if found is None else program_spans.sync_us(*found)
