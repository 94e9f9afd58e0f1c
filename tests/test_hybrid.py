"""Hybrid driver: 2 in-process "hosts" x 2 local ranks = 4 global ranks.

Each host is a thread running ``run_spmd_hybrid`` (which itself spawns the
local rank threads); hosts talk TCP over loopback, locals over the xla
driver's in-process rendezvous — the same composition a real
multi-host x multi-chip deployment uses, shrunk onto one machine
(SURVEY.md §4's "multi-node-without-a-cluster" story, upgraded).
"""

import threading

import numpy as np
import pytest


HOSTS = 2
LOCAL = 2
WORLD = HOSTS * LOCAL


def run_world(fn_for, local=LOCAL, hosts=HOSTS, timeout=60.0):
    """Shared harness (conftest.run_hybrid_world) with this module's
    default 2x2 world."""
    from conftest import run_hybrid_world

    return run_hybrid_world(fn_for, hosts=hosts, local=local,
                            timeout=timeout)



def test_rank_size_topology():
    def fn_for(net):
        def fn():
            net.init()
            out = (net.rank(), net.size())
            net.finalize()
            return out
        return fn

    got = run_world(fn_for)
    assert got == [(g, WORLD) for g in range(WORLD)]


def test_p2p_ring_crosses_hosts():
    def fn_for(net):
        def fn():
            net.init()
            me, n = net.rank(), net.size()
            payload = np.arange(5, dtype=np.float32) + me
            # ring: send to (me+1)%n (crosses the host boundary at 1->2
            # and 3->0), receive from (me-1)%n, concurrently
            got = {}

            def recv():
                got["v"] = net.receive(source=(me - 1) % n, tag=7)

            t = threading.Thread(target=recv, daemon=True)
            t.start()
            net.send(payload, (me + 1) % n, 7)
            t.join(timeout=30)
            assert not t.is_alive()
            net.finalize()
            return got["v"]
        return fn

    got = run_world(fn_for)
    for g in range(WORLD):
        np.testing.assert_array_equal(
            got[g], np.arange(5, dtype=np.float32) + (g - 1) % WORLD)


def test_allreduce_hierarchical_sum():
    def fn_for(net):
        def fn():
            net.init()
            me = net.rank()
            out = net.allreduce(np.full((3,), float(me + 1), np.float64))
            net.finalize()
            return out
        return fn

    got = run_world(fn_for)
    want = np.full((3,), float(sum(range(1, WORLD + 1))), np.float64)
    for v in got:
        np.testing.assert_array_equal(v, want)


def test_allreduce_device_payload_gives_host_result():
    """The inner xla driver returns a device payload's result on the
    device; the hybrid driver's contract stays host-side: numpy out,
    the same bits as for the numpy payload."""
    import jax

    def fn_for(net):
        def fn():
            net.init()
            x = np.arange(6, dtype=np.float32) * 0.1 + net.rank()
            on_device = jax.device_put(x, net._inner.device())
            out = (net.allreduce(on_device), net.allreduce(x),
                   net.reduce_scatter(jax.device_put(
                       np.arange(8, dtype=np.float32) + net.rank(),
                       net._inner.device())))
            net.finalize()
            return out
        return fn

    got = run_world(fn_for)
    for me, (from_device, from_host, block) in enumerate(got):
        assert type(from_device) is np.ndarray and type(block) is np.ndarray
        np.testing.assert_array_equal(from_device, from_host)
        np.testing.assert_array_equal(
            block, (np.arange(8, dtype=np.float32) * WORLD
                    + sum(range(WORLD)))[2 * me:2 * me + 2])


@pytest.mark.parametrize("root", [0, 3])
def test_bcast_from_either_host(root):
    def fn_for(net):
        def fn():
            net.init()
            data = {"msg": "hello", "rank": net.rank()} \
                if net.rank() == root else None
            out = net.bcast(data, root=root)
            net.finalize()
            return out
        return fn

    got = run_world(fn_for)
    assert got == [{"msg": "hello", "rank": root}] * WORLD


def test_allgather_and_gather():
    def fn_for(net):
        def fn():
            net.init()
            ag = net.allgather(net.rank() * 10)
            g = net.gather(net.rank() * 10, root=2)
            net.finalize()
            return ag, g
        return fn

    got = run_world(fn_for)
    want = [g * 10 for g in range(WORLD)]
    for rank, (ag, g) in enumerate(got):
        assert ag == want
        assert g == (want if rank == 2 else None)


@pytest.mark.parametrize("root", [0, 2])
def test_scatter(root):
    def fn_for(net):
        def fn():
            net.init()
            items = [f"item-{i}" for i in range(WORLD)] \
                if net.rank() == root else None
            out = net.scatter(items, root=root)
            net.finalize()
            return out
        return fn

    got = run_world(fn_for)
    assert got == [f"item-{g}" for g in range(WORLD)]


def test_alltoall():
    def fn_for(net):
        def fn():
            net.init()
            me = net.rank()
            out = net.alltoall([(me, dst) for dst in range(WORLD)])
            net.finalize()
            return out
        return fn

    got = run_world(fn_for)
    for dst in range(WORLD):
        assert got[dst] == [(src, dst) for src in range(WORLD)]


def test_barrier_and_reduce():
    def fn_for(net):
        def fn():
            net.init()
            net.barrier()
            r = net.reduce(float(net.rank()), root=1, op="max")
            net.finalize()
            return r
        return fn

    got = run_world(fn_for)
    assert got[1] == float(WORLD - 1)
    assert all(v is None for i, v in enumerate(got) if i != 1)


@pytest.mark.integration
def test_rank_failure_aborts_collective_not_hangs():
    """A rank that dies while siblings sit in a native collective must
    break their barrier (the abort path through
    XlaNetwork.abort_collectives), not leave them hanging."""
    def fn_for(net):
        def main():
            net.init()
            r = net.rank()
            if r == 1:
                raise RuntimeError("boom on rank 1")
            net.allreduce(np.float32([1.0]))
            net.finalize()
        return main

    from mpi_tpu.api import MpiError

    with pytest.raises((RuntimeError, MpiError)):
        run_world(fn_for, timeout=30.0)


def test_split_type_host_groups_local_ranks():
    """split_type('host') over the hybrid world yields one communicator
    per host, containing exactly that host's local ranks."""
    from mpi_tpu.comm import comm_world

    def fn_for(net):
        def main():
            net.init()
            node = comm_world(net).split_type("host")
            total = node.allreduce(np.float32(net.rank()))
            res = (node.members, node.rank(), float(total))
            net.finalize()
            return res
        return main

    out = run_world(fn_for)
    assert out[0][0] == (0, 1) and out[2][0] == (2, 3)
    assert [o[1] for o in out] == [0, 1, 0, 1]
    assert [o[2] for o in out] == [1.0, 1.0, 5.0, 5.0]


def test_cross_host_group_collectives_hierarchical():
    """A communicator spanning both hosts runs the full collective suite
    through the hierarchical group engine (local xla sub-engine + TCP
    leader leg), including with a key-permuted (host-interleaved) rank
    order."""
    from mpi_tpu.comm import comm_world

    def fn_for(net):
        def main():
            net.init()
            w = comm_world(net)
            r = w.rank()
            # Even world ranks, one per host pair: members (0, 2) /
            # odd: (1, 3) — both span hosts. key=-r reverses the order.
            sub = w.split(color=r % 2, key=-r)
            res = {
                "members": sub.members,
                "rank": sub.rank(),
                "sum": float(sub.allreduce(np.float32(r))),
                "bcast": sub.bcast(f"root={r}" if sub.rank() == 0
                                   else None),
                "ag": sub.allgather(int(r)),
                "scattered": sub.scatter(
                    [f"p{i}" for i in range(sub.size())]
                    if sub.rank() == 0 else None),
                "a2a": sub.alltoall([(r, j) for j in range(sub.size())]),
                "rs": sub.reduce_scatter(
                    np.arange(4, dtype=np.float32) + r).tolist(),
                "scan": float(sub.scan(np.float32(1.0))),
            }
            sub.barrier()
            net.finalize()
            return res

        return main

    out = run_world(fn_for)
    for r in range(4):
        res = out[r]
        members = (2, 0) if r % 2 == 0 else (3, 1)  # key=-r reverses
        g = members.index(r)
        n = 2
        assert res["members"] == members
        assert res["rank"] == g
        assert res["sum"] == float(sum(members))
        assert res["bcast"] == f"root={members[0]}"
        assert res["ag"] == list(members)
        assert res["scattered"] == f"p{g}"
        assert res["a2a"] == [(m, g) for m in members]
        expect_rs = (np.arange(4, dtype=np.float32) * n
                     + sum(members))[g * 2:(g + 1) * 2]
        assert res["rs"] == expect_rs.tolist()
        assert res["scan"] == float(g + 1)
    # Engines were actually built on each host (not the generic path).
    # (run_world constructs nets internally; presence is asserted via
    # the cross-host results above matching the hierarchical layout.)


def test_callable_op_rank_order_across_hosts():
    """Non-commutative callable op (matmul) on a host-INTERLEAVED group:
    the hierarchical local-then-host fold would reorder operands, so the
    engine must fall back to the group-rank-ordered tree."""
    from mpi_tpu.comm import comm_world

    mats = [np.array([[1.0, float(r + 1)], [0.0, 1.0]]) for r in range(4)]

    def fn_for(net):
        def main():
            net.init()
            w = comm_world(net)
            r = w.rank()
            # key=-r reverses group order: members (2, 0) / (3, 1) —
            # interleaving hosts relative to rank order.
            sub = w.split(color=r % 2, key=-r)
            out = sub.allreduce(mats[r], op=lambda a, b: a @ b)
            wout = net.allreduce(mats[r], op=lambda a, b: a @ b)
            net.finalize()
            return np.asarray(out), np.asarray(wout)

        return main

    out = run_world(fn_for)
    world_expect = mats[0] @ mats[1] @ mats[2] @ mats[3]
    for r in range(4):
        members = (2, 0) if r % 2 == 0 else (3, 1)
        expect = mats[members[0]] @ mats[members[1]]
        np.testing.assert_array_equal(out[r][0], expect)
        np.testing.assert_array_equal(out[r][1], world_expect)


def test_neighbor_collectives_cross_host_via_allgather():
    """A Cartesian grid spanning both hosts: neighborhood collectives
    must route through the hierarchical group allgather (pairwise comm
    sendrecv cannot cross hosts on the hybrid driver and would hang)."""
    from mpi_tpu.comm import comm_world

    def fn_for(net):
        def main():
            net.init()
            w = comm_world(net)
            cart = mpi_tpu_cart(w)
            halo = cart.neighbor_allgather(cart.rank())
            a2a = cart.neighbor_alltoall(
                [("m", cart.rank()), ("p", cart.rank())])
            net.finalize()
            return halo, a2a

        return main

    import mpi_tpu

    def mpi_tpu_cart(w):
        return mpi_tpu.cart_create(w, (4,), periods=(True,))

    out = run_world(fn_for, timeout=30.0)
    for r in range(4):
        halo, a2a = out[r]
        assert halo == [(r - 1) % 4, (r + 1) % 4]
        assert tuple(a2a[0]) == ("p", (r - 1) % 4)
        assert tuple(a2a[1]) == ("m", (r + 1) % 4)


def test_cross_host_group_p2p_raises_clearly():
    from mpi_tpu.comm import comm_world

    def fn_for(net):
        def main():
            net.init()
            w = comm_world(net)
            r = w.rank()
            sub = w.split(color=r % 2)  # spans hosts: (0,2) / (1,3)
            err = None
            if sub.rank() == 0:
                try:
                    sub.send(b"x", 1, 5)  # cross-host group p2p
                except MpiError as exc:
                    err = str(exc)
            net.finalize()
            return err

        return main

    from mpi_tpu.api import MpiError

    out = run_world(fn_for)
    assert "not supported by the hybrid driver" in (out[0] or "")


def test_hybrid_end_to_end_via_mpirun(tmp_path):
    """2 OS processes (hosts) x 2 local ranks = 4 global ranks, launched
    with the reference flag ABI plus --mpi-backend hybrid."""
    import subprocess
    import sys
    from pathlib import Path

    from conftest import _free_port_block

    repo = Path(__file__).resolve().parent.parent
    prog = tmp_path / "hybrid_prog.py"
    # Per-rank result files: concurrent rank threads share one stdout pipe,
    # so line-level assertions on it are racy.
    prog.write_text(
        "import sys; sys.path.insert(0, %r)\n"
        "from mpi_tpu.utils.platform import force_platform\n"
        "force_platform('cpu', 2)\n"
        "import numpy as np\n"
        "import mpi_tpu\n"
        "def main():\n"
        "    mpi_tpu.init()\n"
        "    r, n = mpi_tpu.rank(), mpi_tpu.size()\n"
        "    total = mpi_tpu.allreduce(np.array([float(r)], np.float32))\n"
        "    open(%r + f'/rank{r}.txt', 'w').write(\n"
        "        f'rank {r} of {n} sum {float(total[0])}')\n"
        "    mpi_tpu.finalize()\n"
        "mpi_tpu.run_main(main)\n" % (str(repo), str(tmp_path)))
    port = _free_port_block(2)
    res = subprocess.run(
        [sys.executable, "-m", "mpi_tpu.launch.mpirun",
         "--port-base", str(port), "--timeout", "30",
         "2", str(prog), "--mpi-backend", "hybrid", "--mpi-ranks", "2"],
        cwd=repo, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    got = sorted((tmp_path / f"rank{g}.txt").read_text() for g in range(4))
    assert got == [f"rank {g} of 4 sum 6.0" for g in range(4)]
