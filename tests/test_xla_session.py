"""The xla driver's collective session (ISSUE 30): one rendezvous a
collective. The rank that completes the count leads on the thread it is
on, every other rank sleeps once behind its own gate, and there is no
second rendezvous before the session is used again.

Four rank threads on the CPU backend's virtual devices. Every case runs
under a time limit of its own (``_spmd``), so a lost wake-up fails the
case instead of hanging the suite.
"""

import random
import sys
import threading
import time

import numpy as np
import pytest

import mpi_tpu
from mpi_tpu.backends.xla import XlaNetwork, run_spmd
from mpi_tpu.comm import comm_world
from mpi_tpu.utils import trace

RANKS = 4
LIMIT = 120.0  # seconds a case may take before it counts as hung


@pytest.fixture
def traced():
    was = trace.enabled()
    trace.clear()
    trace.enable()
    yield trace
    if not was:
        trace.disable()
    trace.clear()


def _spmd(fn, net, limit=LIMIT):
    """``run_spmd(fn, net=net)`` under a time limit: its results, or its
    error, or a failure if a rank thread never came back."""
    box = {}

    def drive():
        try:
            box["out"] = run_spmd(fn, net=net, register_facade=False)
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            box["error"] = exc

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    t.join(timeout=limit)
    assert not t.is_alive(), f"rank threads still running after {limit} s"
    if "error" in box:
        raise box["error"]
    return box["out"]


def _sessions(net):
    return [e._coll for e in (net._world_coll, *net._group_colls.values())]


def _until_asleep(net, count, limit=LIMIT / 2):
    """Wait until ``count`` ranks sleep in the net's sessions."""
    deadline = time.monotonic() + limit
    while sum(sum(s._asleep) for s in _sessions(net)) < count:
        assert time.monotonic() < deadline, "the other ranks never slept"
        time.sleep(0.001)


# -- (a) the last arriver leads ---------------------------------------------

@pytest.mark.parametrize("late", range(RANKS))
def test_last_arriver_leads_on_its_own_thread(late, traced):
    net = XlaNetwork(n=RANKS)

    def main():
        me = net.rank()
        if me == late:
            _until_asleep(net, RANKS - 1)
        total = net.allreduce(np.float32([me]))
        return threading.current_thread().name, float(total[0])

    out = _spmd(main, net)
    assert [o[1] for o in out] == [float(sum(range(RANKS)))] * RANKS
    leader, = [e for e in traced.events() if e["name"] == "xla.coll.leader"]
    assert leader["op"] == "allreduce"
    assert leader["thread"] == out[late][0]
    # The leader never waited; each of the others did, once.
    waits = [e["thread"] for e in traced.events()
             if e["name"] == "xla.coll.release_wait"]
    assert sorted(waits) == sorted(o[0] for r, o in enumerate(out)
                                   if r != late)


# -- (b) n - 1 sleeps a collective ------------------------------------------

@pytest.mark.parametrize("rounds", [1, 7])
def test_sleeps_counter_reads_three_a_collective(rounds, traced):
    net = XlaNetwork(n=RANKS)

    def main():
        for g in range(rounds):
            net.allreduce(np.int32([g]))

    _spmd(main, net)
    assert traced.counters().get("xla.coll.sleeps") == (RANKS - 1) * rounds


def test_sleeps_counter_stays_silent_with_tracing_off():
    was = trace.enabled()
    trace.disable()
    trace.clear()
    try:
        net = XlaNetwork(n=RANKS)
        _spmd(lambda: net.allreduce(np.int32([1])), net)
        assert "xla.coll.sleeps" not in trace.counters()
    finally:
        if was:
            trace.enable()


# -- (c) reuse with no second rendezvous ------------------------------------

def _round(net, kind, g, me):
    """Collective ``kind`` of round ``g``; whether the result is round
    ``g``'s own."""
    if kind == "allreduce":
        got = net.allreduce(np.int64([g * (me + 1)]))
        return int(got[0]) == g * sum(range(1, RANKS + 1))
    if kind == "bcast":
        root = g % RANKS
        return net.bcast(("round", g) if me == root else None,
                         root=root) == ("round", g)
    if kind == "allgather":
        return net.allgather((g, me)) == [(g, r) for r in range(RANKS)]
    return net.barrier() is None


@pytest.mark.parametrize("seed", [30, 2718281828])
def test_back_to_back_collectives_each_rank_gets_its_own_round(seed):
    rounds = 1000
    kinds = ("allreduce", "bcast", "barrier", "allgather")
    net = XlaNetwork(n=RANKS)

    def main():
        me = net.rank()
        rng = random.Random(seed * RANKS + me)
        wrong = []
        for g in range(rounds):
            if rng.random() < 0.25:
                time.sleep(rng.random() * 2e-4)
            kind = kinds[g % len(kinds)]
            if not _round(net, kind, g, me):
                wrong.append((g, kind))
        return wrong

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        assert _spmd(main, net) == [[]] * RANKS
    finally:
        sys.setswitchinterval(was)
    assert all(s._count == 0 and not any(s._asleep) for s in _sessions(net))


# -- (d) abort ----------------------------------------------------------------

@pytest.mark.parametrize("engine", ["world", "group"])
def test_abort_fails_the_sleeping_ranks_and_those_that_enter_later(engine):
    net = XlaNetwork(n=RANKS)
    aborted = threading.Event()

    def main():
        world = comm_world(net)
        comm = world if engine == "world" \
            else world.split(color=world.rank() % 2)
        # The last rank of each communicator stays out until the abort;
        # the others go to sleep in the session.
        late = comm.rank() == comm.size() - 1
        if world.rank() == RANKS - 1:
            _until_asleep(net, RANKS - (1 if engine == "world" else 2))
            net.abort_collectives()
            aborted.set()
        elif late:
            assert aborted.wait(LIMIT / 2)
        with pytest.raises(mpi_tpu.MpiError, match="collective aborted"):
            comm.allreduce(np.float32([1.0]))
        # Broken for good, as an aborted barrier is.
        with pytest.raises(mpi_tpu.MpiError, match="collective aborted"):
            comm.barrier()
        return late

    assert sum(_spmd(main, net)) == (1 if engine == "world" else 2)


def test_failed_rank_is_reported_and_not_the_aborted_collectives():
    """``run_spmd`` aborts the sessions when a rank dies; the ranks that
    slept in a collective are collateral, the dead rank's error is
    raised."""
    net = XlaNetwork(n=RANKS)

    def main():
        if net.rank() == 2:
            _until_asleep(net, RANKS - 1)
            raise RuntimeError("boom on 2")
        net.barrier()

    with pytest.raises(RuntimeError, match="boom on 2"):
        _spmd(main, net)


# -- (e) a leader's exception --------------------------------------------------

@pytest.mark.parametrize("late", [0, 3])
def test_leaders_exception_reaches_every_rank_and_the_session_goes_on(late):
    net = XlaNetwork(n=RANKS)

    def main():
        me = net.rank()
        if me == late:
            _until_asleep(net, RANKS - 1)
        # Rank 1's payload has another shape: the leader refuses it.
        with pytest.raises(mpi_tpu.MpiError,
                           match="collective failed on leader.*mismatch"):
            net.allreduce(np.zeros(3 if me == 1 else 2, np.float32))
        return float(net.allreduce(np.float32([me]))[0])

    assert _spmd(main, net) == [float(sum(range(RANKS)))] * RANKS
