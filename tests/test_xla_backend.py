"""XLA driver tests: thread-per-rank SPMD over the 8-device CPU mesh,
including the north-star bitwise TCP-vs-XLA allreduce parity
(BASELINE.json: "bitwise-identical results to the TCP backend")."""

import threading

import numpy as np
import pytest

import mpi_tpu
from mpi_tpu import api
from mpi_tpu.backends.xla import XlaNetwork, run_spmd

from conftest import run_on_ranks, tcp_cluster

N = 8


@pytest.fixture(autouse=True)
def fresh_registry():
    api._reset_for_testing()
    yield
    api._reset_for_testing()


def spmd(fn, n=N, **kw):
    return run_spmd(fn, n=n, **kw)


class TestLifecycle:
    def test_rank_size_device_binding(self):
        def main():
            mpi_tpu.init()
            r, s = mpi_tpu.rank(), mpi_tpu.size()
            dev = mpi_tpu.registered().device()
            mpi_tpu.finalize()
            return (r, s, dev.id)

        out = spmd(main)
        assert [o[0] for o in out] == list(range(N))
        assert all(o[1] == N for o in out)
        assert len({o[2] for o in out}) == N  # distinct devices

    def test_unbound_thread_rejected(self):
        net = XlaNetwork(n=4)
        with pytest.raises(mpi_tpu.MpiError, match="no rank binding"):
            net.rank()

    def test_too_many_ranks(self):
        with pytest.raises(mpi_tpu.MpiError, match="need"):
            XlaNetwork(n=99)

    def test_rank_error_propagates(self):
        def main():
            mpi_tpu.init()
            if mpi_tpu.rank() == 3:
                raise RuntimeError("boom on 3")
            mpi_tpu.barrier()

        with pytest.raises((RuntimeError, mpi_tpu.MpiError)):
            spmd(main)


class TestPointToPoint:
    def test_ring_exchange(self):
        def main():
            mpi_tpu.init()
            r, n = mpi_tpu.rank(), mpi_tpu.size()
            right, left = (r + 1) % n, (r - 1) % n
            got = mpi_tpu.sendrecv(np.full(4, r, np.float32), dest=right,
                                   source=left, tag=7)
            mpi_tpu.finalize()
            return got

        out = spmd(main)
        for r in range(N):
            np.testing.assert_array_equal(
                out[r], np.full(4, (r - 1) % N, np.float32))

    def test_jax_array_payload_lands_on_dest_device(self):
        import jax

        def main():
            mpi_tpu.init()
            net = mpi_tpu.registered()
            r = mpi_tpu.rank()
            if r == 0:
                x = jax.device_put(jax.numpy.arange(8.0), net.device(0))
                mpi_tpu.send(x, dest=5, tag=1)
                return None
            if r == 5:
                got = mpi_tpu.receive(0, tag=1)
                return (np.asarray(got), list(got.devices())[0].id,
                        net.device(5).id)
            return None

        out = spmd(main)
        arr, dev_id, expect_dev = out[5]
        np.testing.assert_array_equal(arr, np.arange(8.0))
        assert dev_id == expect_dev  # moved to receiver's device

    def test_self_send(self):
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            t = threading.Thread(
                target=mpi_tpu.send, args=(f"me{r}", r, 3), daemon=True)
            t.start()
            got = mpi_tpu.receive(r, tag=3)
            t.join(timeout=5)
            return got

        out = spmd(main)
        assert out == [f"me{r}" for r in range(N)]

    def test_value_semantics_no_aliasing(self):
        # gob round-trip semantics: receiver must not alias sender memory.
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            if r == 0:
                payload = np.zeros(4)
                mpi_tpu.send(payload, dest=1, tag=2)
                payload[:] = 999  # mutate after send returns
                mpi_tpu.barrier()
                return None
            if r == 1:
                got = mpi_tpu.receive(0, tag=2)
                mpi_tpu.barrier()
                return got.copy()
            mpi_tpu.barrier()
            return None

        out = spmd(main)
        np.testing.assert_array_equal(out[1], np.zeros(4))

    def test_tag_misuse_detected(self):
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            hit = None
            if r == 0:
                t = threading.Thread(target=mpi_tpu.send,
                                     args=(b"a", 1, 9), daemon=True)
                t.start()
                import time

                time.sleep(0.2)
                try:
                    mpi_tpu.send(b"b", 1, 9)
                except mpi_tpu.TagError as exc:
                    hit = exc
                mpi_tpu.send(b"go", 1, 99)
                t.join(timeout=5)
            elif r == 1:
                assert mpi_tpu.receive(0, 99) == b"go"
                assert mpi_tpu.receive(0, 9) == b"a"
            return hit is not None

        out = spmd(main)
        assert out[0] is True


class TestCollectives:
    def test_allreduce_array(self):
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            return mpi_tpu.allreduce(np.full((2, 2), float(r + 1), np.float32))

        out = spmd(main)
        expect = np.full((2, 2), sum(range(1, N + 1)), np.float32)
        for o in out:
            np.testing.assert_array_equal(o, expect)

    def test_allreduce_scalar_and_ops(self):
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            return (float(mpi_tpu.allreduce(float(r))),
                    float(mpi_tpu.allreduce(float(r), op="max")),
                    float(mpi_tpu.allreduce(float(r + 1), op="prod")))

        out = spmd(main)
        import math

        for o in out:
            assert o[0] == sum(range(N))
            assert o[1] == N - 1
            assert o[2] == math.factorial(N)

    def test_bcast_gather_scatter_alltoall(self):
        def main():
            mpi_tpu.init()
            r, n = mpi_tpu.rank(), mpi_tpu.size()
            b = mpi_tpu.bcast({"cfg": 42} if r == 2 else None, root=2)
            g = mpi_tpu.gather(f"g{r}", root=1)
            s = mpi_tpu.scatter([f"s->{i}" for i in range(n)]
                                if r == 0 else None, root=0)
            a2a = mpi_tpu.alltoall([f"{r}->{d}" for d in range(n)])
            ag = mpi_tpu.allgather(r * 2)
            return b, g, s, a2a, ag

        out = spmd(main)
        for r, (b, g, s, a2a, ag) in enumerate(out):
            assert b == {"cfg": 42}
            assert (g == [f"g{i}" for i in range(N)]) if r == 1 else g is None
            assert s == f"s->{r}"
            assert a2a == [f"{src}->{r}" for src in range(N)]
            assert ag == [i * 2 for i in range(N)]

    def test_scan_exscan(self):
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            inc = mpi_tpu.scan(np.float32(r + 1))
            exc = mpi_tpu.exscan(np.float32(r + 1))
            mx = mpi_tpu.scan(np.float32(r), op="max")
            return float(inc), None if exc is None else float(exc), float(mx)

        out = spmd(main)
        for r, (inc, exc, mx) in enumerate(out):
            assert inc == sum(range(1, r + 2))
            assert (exc is None) if r == 0 else exc == sum(range(1, r + 1))
            assert mx == r

    def test_array_scan_compiled_and_bitwise_vs_generic(self):
        """Array payloads scan as ONE compiled program (prefix_reduce)
        whose left-fold order is bitwise-identical to the generic
        driver's host fold."""
        from mpi_tpu.collectives_generic import _prefix_fold

        rng = np.random.default_rng(11)
        payloads = [rng.standard_normal(17).astype(np.float32)
                    for _ in range(N)]

        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            inc = mpi_tpu.scan(payloads[r])
            exc = mpi_tpu.exscan(payloads[r])
            mpi_tpu.finalize()
            return np.asarray(inc), None if exc is None else np.asarray(exc)

        net = XlaNetwork(n=N)
        out = run_spmd(main, net=net)
        assert ("prefix", "sum", False) in net._world_coll._jit_cache
        assert ("prefix", "sum", True) in net._world_coll._jit_cache
        for r in range(N):
            want = _prefix_fold(payloads, r + 1, "sum")
            assert out[r][0].tobytes() == want.tobytes()  # bitwise
            if r == 0:
                assert out[r][1] is None
            else:
                wexc = _prefix_fold(payloads, r, "sum")
                assert out[r][1].tobytes() == wexc.tobytes()

    def test_bool_exscan_minmax_takes_host_path(self):
        """bool/complex payloads fold on the host (jnp rejects them in
        ways numpy doesn't; exclusive min/max also lack an identity) —
        inclusive scan included, and scalars keep their native type."""
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            exc = mpi_tpu.exscan(np.array([r % 2 == 0, True]), op="min")
            inc = mpi_tpu.scan(np.array([r % 2 == 0, True]), op="min")
            scalar = mpi_tpu.scan(1.5)
            mpi_tpu.finalize()
            return (None if exc is None else np.asarray(exc).tolist(),
                    np.asarray(inc).tolist(), scalar)

        out = spmd(main)
        assert out[0][0] is None
        assert isinstance(out[0][2], float)  # rank 0 keeps its payload
        for r in range(N):
            exc, inc, scalar = out[r]
            if r >= 1:
                assert exc == [r < 2, True]
            assert inc == [r < 1, True]
            assert float(scalar) == 1.5 * (r + 1)

    def test_reduce_root_only(self):
        def main():
            mpi_tpu.init()
            return mpi_tpu.reduce(np.float32(1.0), root=4)

        out = spmd(main)
        for r, o in enumerate(out):
            if r == 4:
                assert float(o) == N
            else:
                assert o is None

    def test_mixed_payload_shape_error(self):
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            shape = (3,) if r != 5 else (4,)
            try:
                mpi_tpu.allreduce(np.ones(shape, np.float32))
                return None
            except mpi_tpu.MpiError as exc:
                return str(exc)

        out = spmd(main)
        assert all(o is not None and "mismatch" in o for o in out)


@pytest.mark.parametrize("nranks", [2, 3, 5, 8])
class TestBitwiseParity:
    """North star: xla deterministic allreduce == TCP tree, bit for bit."""

    def test_allreduce_float32(self, nranks):
        rng = np.random.default_rng(11)
        contribs = [rng.standard_normal(513).astype(np.float32)
                    for _ in range(nranks)]

        # TCP oracle.
        from mpi_tpu import collectives_generic as gen

        with tcp_cluster(nranks) as nets:
            tcp_out = run_on_ranks(
                nets, lambda net, r: gen.allreduce(net, contribs[r]))

        # XLA driver, deterministic tree.
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            return mpi_tpu.registered().allreduce(contribs[r],
                                                  deterministic=True)

        xla_out = run_spmd(main, n=nranks)

        for r in range(nranks):
            assert np.asarray(xla_out[r]).tobytes() == \
                np.asarray(tcp_out[r]).tobytes(), \
                f"rank {r}: xla and tcp allreduce differ bitwise"

    def test_allreduce_float64(self, nranks):
        rng = np.random.default_rng(13)
        contribs = [rng.standard_normal(64) for _ in range(nranks)]

        from mpi_tpu import collectives_generic as gen

        with tcp_cluster(nranks) as nets:
            tcp_out = run_on_ranks(
                nets, lambda net, r: gen.allreduce(net, contribs[r]))

        def main():
            mpi_tpu.init()
            return mpi_tpu.registered().allreduce(
                contribs[mpi_tpu.rank()], deterministic=True)

        xla_out = run_spmd(main, n=nranks)
        for r in range(nranks):
            assert np.asarray(xla_out[r]).tobytes() == \
                np.asarray(tcp_out[r]).tobytes()


class TestRerunability:
    def test_run_spmd_twice_same_process(self):
        def main():
            mpi_tpu.init()
            return mpi_tpu.rank()

        assert spmd(main, n=2) == [0, 1]
        assert spmd(main, n=2) == [0, 1]  # facade released between runs

    def test_allreduce_list_payload_matches_generic(self):
        def main():
            mpi_tpu.init()
            return mpi_tpu.allreduce([1.0, 2.0])

        out = spmd(main, n=4)
        for o in out:
            np.testing.assert_array_equal(np.asarray(o), [4.0, 8.0])

    def test_allreduce_string_payload_raises_everywhere(self):
        def main():
            mpi_tpu.init()
            try:
                mpi_tpu.allreduce("nope")
                return None
            except mpi_tpu.MpiError as exc:
                return str(exc)

        out = spmd(main, n=2)
        assert all(o and "numeric" in o for o in out)


class TestOversubscription:
    """Reference parity: N ranks on fewer devices (gompirun spawns N
    processes regardless of core count, gompirun.go:46-51)."""

    def test_ranks_exceed_devices(self):
        N = 12  # > 8 virtual devices

        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            total = mpi_tpu.allreduce(float(r))
            mpi_tpu.finalize()
            return total

        net = XlaNetwork(n=N, oversubscribe=True)
        out = run_spmd(main, net=net)
        assert out == [float(sum(range(N)))] * N

    def test_oversubscribed_p2p_roundtrip(self):
        def main():
            mpi_tpu.init()
            if mpi_tpu.rank() == 0:
                mpi_tpu.send(b"ping", 1, 7)
                assert mpi_tpu.receive(source=1, tag=8) == b"pong"
            else:
                assert mpi_tpu.receive(source=0, tag=7) == b"ping"
                mpi_tpu.send(b"pong", 0, 8)
            mpi_tpu.finalize()

        run_spmd(main, net=XlaNetwork(n=2, oversubscribe=True))

    def test_oversubscribed_matches_tcp_tree_order(self):
        """Oversubscribed host-tree allreduce is bitwise equal to the TCP
        driver's wire allreduce — the true oracle, not a copied loop."""
        import numpy as np
        from mpi_tpu import collectives_generic as cg

        vals = [np.float32([1e8, 1.5, -3.25]) * (i + 1) for i in range(12)]
        with tcp_cluster(12) as nets:
            tcp_out = run_on_ranks(
                nets, lambda net, r: cg.allreduce(net, vals[r]))
        expect = np.asarray(tcp_out[0])
        for o in tcp_out:
            np.testing.assert_array_equal(np.asarray(o), expect)

        def main():
            mpi_tpu.init()
            out = mpi_tpu.allreduce(vals[mpi_tpu.rank()])
            mpi_tpu.finalize()
            return out

        outs = run_spmd(main, net=XlaNetwork(n=12, oversubscribe=True))
        for o in outs:
            np.testing.assert_array_equal(np.asarray(o), expect)


def test_oversubscribed_validation_matches_mesh_path():
    """Payload mismatch raises the same clear error whether or not ranks
    oversubscribe — behavior must not depend on the rank/device ratio."""
    import numpy as np

    api._reset_for_testing()

    def main():
        mpi_tpu.init()
        r = mpi_tpu.rank()
        data = np.float32([1, 2]) if r == 0 else np.float32([1, 2, 3])
        try:
            mpi_tpu.allreduce(data)
        finally:
            mpi_tpu.finalize()

    with pytest.raises(mpi_tpu.MpiError, match="payload mismatch"):
        run_spmd(main, net=XlaNetwork(n=12, oversubscribe=True))
    api._reset_for_testing()


class TestCompiledAllgather:
    """Uniform array payloads take the single compiled XLA all_gather."""

    def test_array_allgather_values(self):
        def main():
            mpi_tpu.init()
            me = mpi_tpu.rank()
            got = mpi_tpu.allgather(
                np.full((2, 3), float(me), np.float32))
            mpi_tpu.finalize()
            return got

        results = spmd(main, n=4)
        for per_rank in results:
            assert len(per_rank) == 4
            for r, arr in enumerate(per_rank):
                np.testing.assert_array_equal(
                    np.asarray(arr), np.full((2, 3), float(r), np.float32))

    def test_mixed_payloads_fall_back(self):
        def main():
            mpi_tpu.init()
            me = mpi_tpu.rank()
            payload = {"rank": me} if me % 2 else np.zeros(2, np.float32)
            got = mpi_tpu.allgather(payload)
            mpi_tpu.finalize()
            return got

        results = spmd(main, n=4)
        for per_rank in results:
            assert per_rank[1] == {"rank": 1}
            np.testing.assert_array_equal(per_rank[0],
                                          np.zeros(2, np.float32))

    def test_scalar_payloads_keep_types(self):
        def main():
            mpi_tpu.init()
            got = mpi_tpu.allgather(mpi_tpu.rank() * 10)
            mpi_tpu.finalize()
            return got

        results = spmd(main, n=4)
        for per_rank in results:
            assert per_rank == [0, 10, 20, 30]
            assert all(isinstance(v, int) for v in per_rank)


class TestCompiledCollectivePaths:
    """VERDICT round-1 item 3: bcast / scatter / gather / alltoall /
    reduce_scatter run as single compiled XLA programs for uniform array
    payloads (the object fallback keeps working), and results agree with
    the generic oracle."""

    def _run(self, fn, net=None):
        net = net or XlaNetwork(n=N)
        out = run_spmd(fn, net=net)
        return out, net

    def test_bcast_array_compiled(self):
        data = np.arange(24, dtype=np.float32).reshape(4, 6)

        def main():
            mpi_tpu.init()
            payload = data + 1 if mpi_tpu.rank() == 2 else None
            got = mpi_tpu.bcast(payload, root=2)
            mpi_tpu.finalize()
            return np.asarray(got)

        out, net = self._run(main)
        for o in out:
            np.testing.assert_array_equal(o, data + 1)
        assert ("bcast", "", False, 2) in net._world_coll._jit_cache

    def test_scatter_array_compiled(self):
        def main():
            mpi_tpu.init()
            items = None
            if mpi_tpu.rank() == 0:
                items = [np.full((3,), float(i), np.float32)
                         for i in range(N)]
            got = mpi_tpu.scatter(items, root=0)
            mpi_tpu.finalize()
            return np.asarray(got)

        out, _ = self._run(main)
        for i, o in enumerate(out):
            np.testing.assert_array_equal(o, np.full((3,), float(i)))

    def test_gather_array_compiled(self):
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            got = mpi_tpu.gather(
                np.full((2, 2), float(r), np.float32), root=3)
            mpi_tpu.finalize()
            return got

        out, net = self._run(main)
        assert out[3] is not None and len(out[3]) == N
        for i, row in enumerate(out[3]):
            np.testing.assert_array_equal(row, np.full((2, 2), float(i)))
        assert all(out[i] is None for i in range(N) if i != 3)
        assert ("allgather", "", False) in net._world_coll._jit_cache

    def test_alltoall_array_compiled(self):
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            payloads = [np.asarray([r * 10 + j], np.int32)
                        for j in range(N)]
            got = mpi_tpu.alltoall(payloads)
            mpi_tpu.finalize()
            return [int(np.asarray(g)[0]) for g in got]

        out, net = self._run(main)
        for dst in range(N):
            assert out[dst] == [src * 10 + dst for src in range(N)]
        assert ("alltoall", "", False) in net._world_coll._jit_cache

    def test_alltoall_object_fallback(self):
        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            got = mpi_tpu.alltoall([f"{r}->{j}" for j in range(N)])
            mpi_tpu.finalize()
            return got

        out, _ = self._run(main)
        for dst in range(N):
            assert out[dst] == [f"{src}->{dst}" for src in range(N)]

    def test_reduce_scatter_matches_generic(self):
        rng = np.random.default_rng(5)
        contribs = rng.standard_normal((N, 16)).astype(np.float32)

        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            got = mpi_tpu.reduce_scatter(contribs[r])
            mpi_tpu.finalize()
            return np.asarray(got)

        out, net = self._run(main, XlaNetwork(
            n=N, deterministic_collectives=True))
        total = contribs.sum(axis=0)
        for i, o in enumerate(out):
            assert o.shape == (2,)
            np.testing.assert_allclose(o, total[i * 2:(i + 1) * 2],
                                       rtol=1e-5)
        assert ("reduce_scatter", "sum", True) in net._world_coll._jit_cache

    def test_reduce_scatter_bitwise_vs_tcp(self):
        """Deterministic XLA reduce_scatter == generic tree order over the
        TCP driver, bit for bit (the north-star parity contract)."""
        rng = np.random.default_rng(11)
        contribs = rng.standard_normal((4, 8)).astype(np.float32)

        def xla_main():
            mpi_tpu.init()
            got = mpi_tpu.reduce_scatter(contribs[mpi_tpu.rank()])
            mpi_tpu.finalize()
            return np.asarray(got)

        xla_out = run_spmd(
            xla_main, net=XlaNetwork(n=4, deterministic_collectives=True))

        from mpi_tpu import collectives_generic as G

        with tcp_cluster(4) as nets:
            tcp_out = run_on_ranks(
                nets, lambda net, r: G.reduce_scatter(net, contribs[r]))
        for a, b in zip(xla_out, tcp_out):
            np.testing.assert_array_equal(a, np.asarray(b))

    def test_reduce_scatter_indivisible_raises_everywhere(self):
        def main():
            mpi_tpu.init()
            try:
                with pytest.raises(mpi_tpu.MpiError, match="divide"):
                    mpi_tpu.reduce_scatter(np.ones((N + 1,), np.float32))
            finally:
                mpi_tpu.finalize()

        self._run(main)

    def test_config4_mixed_dtype_ring_suite(self):
        """BASELINE.json config 4: Bcast + Allgather, mixed int64/float64
        payloads, all on compiled collective paths (x64 is enabled in
        tests, so 64-bit dtypes are canonical)."""
        i64 = np.arange(8, dtype=np.int64)
        f64 = np.linspace(0, 1, 8)

        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            got_i = mpi_tpu.bcast(i64 if r == 0 else None, root=0)
            got_f = mpi_tpu.bcast(f64 * 2 if r == 1 else None, root=1)
            rows_i = mpi_tpu.allgather(i64 + r)
            rows_f = mpi_tpu.allgather(f64 + r)
            mpi_tpu.finalize()
            return got_i, got_f, rows_i, rows_f

        out, net = self._run(main)
        for got_i, got_f, rows_i, rows_f in out:
            assert np.asarray(got_i).dtype == np.int64
            assert np.asarray(got_f).dtype == np.float64
            np.testing.assert_array_equal(got_i, i64)
            np.testing.assert_array_equal(got_f, f64 * 2)
            for r in range(N):
                np.testing.assert_array_equal(rows_i[r], i64 + r)
                np.testing.assert_allclose(rows_f[r], f64 + r)
        assert ("bcast", "", False, 0) in net._world_coll._jit_cache
        assert ("bcast", "", False, 1) in net._world_coll._jit_cache
        assert ("allgather", "", False) in net._world_coll._jit_cache


class TestNonblocking:
    def test_isend_irecv_inherits_rank_binding(self):
        """Request worker threads must inherit the rank binding of the
        rank thread that created them (the patched Thread.start), so the
        facade's nonblocking ops work under thread-per-rank SPMD."""
        def main():
            mpi_tpu.init()
            r, n = mpi_tpu.rank(), mpi_tpu.size()
            right, left = (r + 1) % n, (r - 1) % n
            rs = mpi_tpu.isend(np.full(3, r, np.float32), right, tag=11)
            rr = mpi_tpu.irecv(left, tag=11)
            got = rr.wait(timeout=20)
            rs.wait(timeout=20)
            return got

        out = spmd(main)
        for r in range(N):
            np.testing.assert_array_equal(
                out[r], np.full(3, (r - 1) % N, np.float32))


# --------------------------------------------------------------------------
# Device payloads (ISSUE 28): a jax.Array with ndim >= 1 goes into the
# compiled collective as the shard it is, and the rank gets its result as
# a jax.Array on its own device; everything else takes the numpy path.
# --------------------------------------------------------------------------

DEV_RANKS = 4


def _payload(rank, elems=8):
    """Float32 noise whose sum depends on the order of the additions."""
    return np.random.default_rng(100 + rank).standard_normal(
        elems).astype(np.float32) * np.float32(1 + rank)


def _oracle(kind, op, values, me, root=1):
    """What rank ``me`` of ``len(values)`` gets, by numpy in the canonical
    order (``collectives_generic``)."""
    from mpi_tpu.collectives_generic import canonical_combine, combine

    n = len(values)
    if kind in ("allreduce", "reduce"):
        total = canonical_combine(values, op)
        return total if kind == "allreduce" or me == root else None
    if kind == "reduce_scatter":
        m = values[0].shape[0] // n
        return canonical_combine(values, op)[me * m:(me + 1) * m]
    upto = me if kind == "exscan" else me + 1
    if upto == 0:
        return None
    acc = values[0]
    for v in values[1:upto]:
        acc = combine(acc, v, op)
    return acc


class _SpyNumpy:
    """``numpy`` for ``backends/xla.py`` that notes every ``asarray`` of a
    ``jax.Array``: the device-to-host reads of the driver."""

    def __init__(self):
        self.reads = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kwargs):
        import jax

        if isinstance(a, jax.Array):
            self.reads.append(a.shape)
        return np.asarray(a, *args, **kwargs)


@pytest.fixture
def host_reads(monkeypatch):
    from mpi_tpu.backends import xla as xla_mod

    spy = _SpyNumpy()
    monkeypatch.setattr(xla_mod, "np", spy)
    return spy.reads


@pytest.fixture
def recording():
    from mpi_tpu.utils import trace

    was = trace.enabled()
    trace.clear()
    trace.enable()
    yield trace
    if not was:
        trace.disable()
    trace.clear()


def _device_program(kind, op, engine, how="own"):
    """A rank program: the collective ``kind`` three times over the same
    values — device payloads on every rank, numpy payloads on every rank,
    and mixed (odd ranks device, even ranks numpy)."""
    import jax
    import jax.numpy as jnp

    from mpi_tpu.comm import comm_world

    def main():
        mpi_tpu.init()
        try:
            net = api.registered()
            world = comm_world()
            comm = world if engine == "world" \
                else world.split(color=world.rank() % 2)
            me, dev = comm.rank(), net.device()
            x = _payload(world.rank())
            if how == "own":
                on_device = jax.device_put(x, dev)
            elif how == "other_device":
                on_device = jax.device_put(
                    x, net.device((world.rank() + 1) % world.size()))
            else:  # uncommitted: wherever jax's default device is
                on_device = jnp.asarray(x)
                assert not on_device.committed
            kw = {"op": op, **({"root": 1} if kind == "reduce" else {})}
            call = getattr(comm, kind)
            got = {"device": call(on_device, **kw),
                   "numpy": call(x, **kw),
                   "mixed": call(on_device if me % 2 else x, **kw)}
            # No donation: the caller's payload is still there, unchanged.
            np.testing.assert_array_equal(np.asarray(on_device), x)
            return {"me": me, "members": comm.members, "device": dev,
                    "got": got}
        finally:
            mpi_tpu.finalize()

    return main


def _check_device_results(seen, kind, op):
    import jax

    for s in seen:
        me, dev = s["me"], s["device"]
        values = [_payload(w) for w in s["members"]]
        want = _oracle(kind, op, values, me)
        for how, got in s["got"].items():
            stays = how == "device" or (how == "mixed" and me % 2 == 1)
            if want is None:
                assert got is None, (kind, how, me)
                continue
            if stays:
                assert isinstance(got, jax.Array), (kind, how, type(got))
                assert got.committed and got.devices() == {dev}, \
                    (kind, how, got.devices(), dev)
            else:
                assert type(got) is np.ndarray, (kind, how, type(got))
            assert got.shape == want.shape and got.dtype == want.dtype
            # Bitwise: the program for a device payload is the program
            # for the same numpy payload, and both replay the host tree.
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=f"{kind} {how} {me}")


DEVICE_KINDS = [("allreduce", "sum"), ("allreduce", "max"),
                ("allreduce", "min"), ("allreduce", "prod"),
                ("reduce", "sum"), ("reduce_scatter", "sum"),
                ("scan", "sum"), ("exscan", "prod")]


class TestDevicePayloads:
    @pytest.mark.parametrize("engine", ["world", "group"])
    @pytest.mark.parametrize("kind,op", DEVICE_KINDS)
    def test_result_follows_payload_type_bitwise(self, kind, op, engine):
        """Device in -> jax.Array out on the rank's own device; numpy in
        -> numpy out; mixed ranks -> each by its own payload; all three
        bitwise equal to the canonical host order."""
        n = DEV_RANKS if engine == "world" else 2 * DEV_RANKS
        seen = run_spmd(_device_program(kind, op, engine), net=XlaNetwork(
            n=n, deterministic_collectives=True))
        assert all(len(s["members"]) == DEV_RANKS for s in seen)
        _check_device_results(seen, kind, op)

    @pytest.mark.parametrize("how", ["other_device", "uncommitted"])
    def test_payload_elsewhere_is_moved_to_the_ranks_device(self, how,
                                                            host_reads):
        seen = run_spmd(_device_program("allreduce", "sum", "world", how),
                        net=XlaNetwork(n=DEV_RANKS,
                                       deterministic_collectives=True))
        _check_device_results(seen, "allreduce", "sum")
        # Moved device to device: the only host reads are the numpy
        # ranks' results (the all-numpy call, then the mixed call's
        # even ranks).
        assert len(host_reads) == DEV_RANKS + DEV_RANKS // 2, host_reads

    @pytest.mark.parametrize("kind,op", [("allreduce", "sum"),
                                         ("reduce_scatter", "sum"),
                                         ("scan", "max")])
    def test_device_call_reads_nothing_to_the_host(self, kind, op,
                                                   host_reads):
        import jax

        def main():
            mpi_tpu.init()
            try:
                x = jax.device_put(_payload(mpi_tpu.rank()),
                                   api.registered().device())
                return getattr(mpi_tpu, kind)(x, op=op)
            finally:
                mpi_tpu.finalize()

        out = run_spmd(main, net=XlaNetwork(n=DEV_RANKS))
        assert host_reads == []
        assert all(isinstance(o, jax.Array) for o in out)

    def test_zero_d_device_array_keeps_the_numpy_answer(self):
        import jax.numpy as jnp

        def main():
            mpi_tpu.init()
            try:
                r = mpi_tpu.rank()
                return (mpi_tpu.allreduce(jnp.float32(r + 0.5)),
                        mpi_tpu.allreduce(np.float32(r + 0.5)))
            finally:
                mpi_tpu.finalize()

        for from_device, from_numpy in run_spmd(
                main, net=XlaNetwork(n=DEV_RANKS)):
            assert type(from_device) is type(from_numpy) is np.float32
            assert from_device == from_numpy == 8.0

    @pytest.mark.parametrize("fault", ["shape", "float64_without_x64"])
    def test_bad_device_payloads_raise_everywhere_unread(self, fault,
                                                         host_reads):
        import jax

        def main():
            mpi_tpu.init()
            r = mpi_tpu.rank()
            dev = api.registered().device()
            if fault == "shape":
                x = jax.device_put(
                    np.ones(3 if r != 2 else 4, np.float32), dev)
            else:
                x = jax.device_put(np.ones(3, np.float64), dev)
                assert x.dtype == np.float64
                mpi_tpu.barrier()  # every rank has its float64 payload
                if r == 0:
                    jax.config.update("jax_enable_x64", False)
                mpi_tpu.barrier()
            try:
                mpi_tpu.allreduce(x)
                return None
            except mpi_tpu.MpiError as exc:
                return str(exc)
            finally:
                mpi_tpu.barrier()
                if r == 0:
                    jax.config.update("jax_enable_x64", True)

        try:
            out = run_spmd(main, net=XlaNetwork(n=DEV_RANKS))
        finally:
            jax.config.update("jax_enable_x64", True)  # conftest's setting
        word = "mismatch" if fault == "shape" else "downcast"
        assert all(o is not None and word in o for o in out), out
        assert host_reads == []

    @pytest.mark.parametrize("payloads", ["device", "numpy", "mixed"])
    def test_spans_and_counters_by_path(self, payloads, recording):
        import jax

        def main():
            mpi_tpu.init()
            try:
                r = mpi_tpu.rank()
                x = _payload(r)
                if payloads == "device" or (payloads == "mixed" and r % 2):
                    x = jax.device_put(x, api.registered().device())
                return mpi_tpu.allreduce(x)
            finally:
                mpi_tpu.finalize()

        run_spmd(main, net=XlaNetwork(n=DEV_RANKS))
        spans = [e for e in recording.events()
                 if e.get("op") == "allreduce"]
        names = [e["name"] for e in spans]
        leader, = [e for e in spans if e["name"] == "xla.coll.leader"]
        assert leader["path"] == {"numpy": "host"}.get(payloads, payloads)
        assert names.count("xla.coll.device_put") == 1
        assert names.count("xla.coll.launch") == 1
        copies = 0 if payloads == "device" else 1
        assert names.count("xla.coll.host_read") == copies
        assert names.count("xla.coll.read_back") == copies
        on_device = {"device": DEV_RANKS, "numpy": 0,
                     "mixed": DEV_RANKS // 2}[payloads]
        counters = recording.counters()
        assert counters.get("xla.coll.device_payloads", 0) == on_device
        assert counters.get("xla.coll.host_payloads", 0) \
            == DEV_RANKS - on_device

    @pytest.mark.parametrize("why", ["oversubscribed", "callable_op"])
    def test_host_tree_keeps_numpy_results(self, why, recording):
        """No mesh, or an op XLA cannot compile: the host tree, as
        before, whatever the payload's type."""
        import jax

        def main():
            mpi_tpu.init()
            try:
                x = _payload(mpi_tpu.rank())
                op = (lambda a, b: a + b) if why == "callable_op" else "sum"
                return (mpi_tpu.allreduce(jax.device_put(
                    x, api.registered().device()), op=op),
                    mpi_tpu.allreduce(x, op=op))
            finally:
                mpi_tpu.finalize()

        n = 12 if why == "oversubscribed" else DEV_RANKS
        out = run_spmd(main, net=XlaNetwork(n=n, oversubscribe=True))
        from mpi_tpu.collectives_generic import canonical_combine

        want = canonical_combine([_payload(r) for r in range(n)], "sum")
        for from_device, from_numpy in out:
            assert type(from_device) is type(from_numpy) is np.ndarray
            np.testing.assert_array_equal(from_device, want)
            np.testing.assert_array_equal(from_numpy, want)
        leaders = [e for e in recording.events()
                   if e["name"] == "xla.coll.leader"
                   and e.get("op") == "allreduce"]
        assert [e["path"] for e in leaders] == ["host", "host"]

    @pytest.mark.parametrize("kind", ["allgather", "gather", "bcast",
                                      "alltoall", "scatter"])
    def test_replicated_and_list_collectives_keep_numpy_results(
            self, kind, host_reads):
        """Not this PR's collectives: a device payload gives what it gave
        (numpy), though allgather / gather / bcast no longer read it to
        the host on the way in."""
        import jax

        n = DEV_RANKS

        def main():
            mpi_tpu.init()
            try:
                r, dev = mpi_tpu.rank(), api.registered().device()
                if kind in ("allgather", "gather"):
                    got = getattr(mpi_tpu, kind)(
                        jax.device_put(_payload(r), dev))
                    if kind == "gather" and r != 0:
                        assert got is None
                        return []
                    return got
                if kind == "bcast":
                    return [mpi_tpu.bcast(jax.device_put(_payload(2), dev)
                                          if r == 2 else None, root=2)]
                if kind == "alltoall":
                    return mpi_tpu.alltoall([
                        jax.device_put(_payload(r * n + j), dev)
                        for j in range(n)])
                return [mpi_tpu.scatter([
                    jax.device_put(_payload(j), dev) for j in range(n)]
                    if r == 1 else None, root=1)]
            finally:
                mpi_tpu.finalize()

        out = run_spmd(main, net=XlaNetwork(n=n))
        for r, got in enumerate(out):
            assert all(type(g) is np.ndarray for g in got), kind
            want = {"allgather": [_payload(i) for i in range(n)],
                    "gather": [_payload(i) for i in range(n)],
                    "bcast": [_payload(2)],
                    "alltoall": [_payload(i * n + r) for i in range(n)],
                    "scatter": [_payload(r)]}[kind]
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)
        if kind in ("allgather", "gather", "bcast"):
            # Only the replicated result crosses to the host, once.
            assert len(host_reads) == 1, host_reads
