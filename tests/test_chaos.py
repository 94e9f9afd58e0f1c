"""Chaos layer tests (mpi_tpu/chaos.py + the robustness machinery it
exercises: CRC wire integrity, operation deadlines, peer-death
bookkeeping, abort propagation, launcher reaping).

Proves the four tentpole behaviors of docs/FAULT_TOLERANCE.md:

  (a) delay/reorder-only chaos is semantics-preserving — a mixed
      collective/p2p schedule produces bit-exact results;
  (b) an injected corrupted frame raises a typed ``ERR_TRUNCATE`` error
      naming source rank and tag — never a garbage decode;
  (c) a receive from a killed/wedged peer raises a deadline or
      peer-dead error within ``--mpi-optimeout`` instead of hanging;
  (d) one rank aborting (or crashing under ``mpirun``) terminates all
      ranks promptly with nonzero exit — no test relies on the outer
      CI timeout.
"""

import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from mpi_tpu import errclass
from mpi_tpu.api import MpiError
from mpi_tpu.backends.rendezvous import DeadlineError
from mpi_tpu.backends.tcp import (ChecksumError, PeerDeadError,
                                  RemoteAbortError, TcpNetwork)
from mpi_tpu.chaos import (CRASH_EXIT_CODE, ChaosEngine, ChaosNetwork,
                           parse_chaos)
from mpi_tpu.comm import comm_world

from conftest import _free_port_block, run_on_ranks, tcp_cluster

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Spec grammar + determinism
# ---------------------------------------------------------------------------


class TestChaosSpec:
    def test_parse_full(self):
        cfg = parse_chaos("42:0.25:delay,corrupt,crash@100")
        assert cfg.seed == 42
        assert cfg.rate == 0.25
        assert cfg.modes == {"delay", "corrupt"}
        assert cfg.crash_at == 100
        assert cfg.wire_modes == {"corrupt"}

    def test_malformed_specs_fail_loudly(self):
        # A typo'd chaos flag must not silently run the job fault-free.
        for bad in ["", "42", "42:0.5", "x:0.5:delay", "42:q:delay",
                    "42:1.5:delay", "42:0.5:warp", "42:0.5:crash@x",
                    "42:0.5:crash@0", "42:0.5:"]:
            with pytest.raises(MpiError):
                parse_chaos(bad)

    def test_decisions_are_deterministic(self):
        # Same spec, same op sequence => identical fault plans — thread
        # scheduling and hash randomization must not leak in.
        def trace(spec):
            eng = ChaosEngine(parse_chaos(spec))
            out = []
            for step in range(40):
                f = eng.on_op("send", step % 3, step, wire=True)
                out.append(None if f is None else
                           (f.corrupt_offset, f.corrupt_bit,
                            f.truncate_at, f.reset))
            return out

        a = trace("9:0.5:corrupt,truncate,reset")
        b = trace("9:0.5:corrupt,truncate,reset")
        assert a == b
        assert any(x is not None for x in a)
        assert trace("10:0.5:corrupt,truncate,reset") != a

    def test_wrapper_requires_spec_or_engine(self):
        with pytest.raises(MpiError, match="chaos spec"):
            ChaosNetwork(TcpNetwork())


class TestChaosNetworkWrapper:
    def test_op_plane_wrapping_of_generic_backend(self):
        # A backend without the TCP wire attachment point gets op-plane
        # injection from the wrapper itself; everything else passes
        # through untouched (the facade's capability probing relies on
        # that).
        calls = []

        class Dummy:
            def init(self): calls.append("init")
            def finalize(self): calls.append("finalize")
            def rank(self): return 0
            def size(self): return 1
            def send(self, data, dest, tag): calls.append(("send", dest, tag))
            def receive(self, source, tag, out=None): return ("recv", source)
            def host_key(self): return "dummy-host"

        net = ChaosNetwork(Dummy(), spec="3:1.0:latency")
        assert not net._wire_level
        net.init()
        net.send("x", 0, 5)
        assert net.receive(0, 5) == ("recv", 0)
        assert net.host_key() == "dummy-host"  # __getattr__ passthrough
        net.finalize()
        assert calls == ["init", ("send", 0, 5), "finalize"]

    def test_tcp_backend_gets_wire_level_engine(self):
        inner = TcpNetwork()
        net = ChaosNetwork(inner, spec="3:0.5:delay")
        assert net._wire_level
        assert inner._chaos is net._engine


# ---------------------------------------------------------------------------
# (a) delay/reorder chaos is semantics-preserving
# ---------------------------------------------------------------------------


def _schedule(comm, r, steps):
    """Mixed collective/p2p schedule; returns the observable log —
    identical across runs iff transport semantics are timing-independent."""
    log = []
    n = comm.size()
    for step in range(steps):
        log.append(int(comm.allreduce(r * 3 + step)))
        log.append(comm.bcast(step * 7 + 1 if r == step % n else None,
                              root=step % n))
        log.append(int(comm.sendrecv(r * 10 + step, dest=(r + 1) % n,
                                     source=(r - 1) % n, tag=step)))
        log.append([int(x) for x in comm.allgather(r + step)])
        if step % 3 == 0:
            arr = np.arange(2 * n, dtype=np.int64) + r + step
            log.append([int(x) for x in comm.reduce_scatter(arr)])
        comm.barrier()
    return log


class TestDelayChaosBitExact:
    N = 3

    def _run(self, chaos_spec, steps=8):
        with tcp_cluster(self.N) as nets:
            if chaos_spec:
                for net in nets:
                    net._chaos = ChaosEngine(parse_chaos(chaos_spec))
            return run_on_ranks(
                nets, lambda net, r: _schedule(comm_world(net), r, steps),
                timeout=120.0)

    def test_torture_schedule_bit_exact_under_delay_chaos(self):
        clean = self._run(None)
        chaotic = self._run("11:0.7:delay,latency")
        assert clean == chaotic

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_soak_many_seeds(self, seed):
        # Heavier soak: more steps, full-rate delay — tier-2 coverage.
        # tools/chaos_soak.sh sweeps further seed ranges by exporting
        # MPI_TPU_CHAOS_SOAK_SEED as an offset.
        seed += int(os.environ.get("MPI_TPU_CHAOS_SOAK_SEED", "0")) * 3
        clean = self._run(None, steps=20)
        chaotic = self._run(f"{seed}:1.0:delay,latency", steps=20)
        assert clean == chaotic


# ---------------------------------------------------------------------------
# (b) wire integrity: corrupted frame -> typed ERR_TRUNCATE
# ---------------------------------------------------------------------------


class TestWireIntegrity:
    def test_crc_negotiated_roundtrip_including_zero_copy_path(self):
        with tcp_cluster(2, crc=True) as nets:
            for net in nets:
                for peer in net._peers.values():
                    assert peer.dial_crc and peer.listen_crc
            big = np.arange(65536, dtype=np.float64)  # scatter-gather path

            def fn(net, r):
                if r == 0:
                    net.send(big, 1, 5)
                    net.send({"k": [1, 2, 3]}, 1, 6)
                    return None
                got = net.receive(0, 5)
                obj = net.receive(0, 6)
                return bool(np.array_equal(got, big)) and obj == {"k": [1, 2, 3]}

            assert run_on_ranks(nets, fn)[1] is True

    def test_crc_negotiation_is_both_sided(self):
        # One side without the feature => CRC stays off on every conn
        # (mixed-version interop), and plain traffic still works.
        from conftest import _free_ports
        ports = _free_ports(2)
        addrs = sorted(f"127.0.0.1:{p:05d}" for p in ports)
        nets = [TcpNetwork(addr=addrs[0], addrs=addrs, timeout=20.0,
                           proto="tcp", crc=True),
                TcpNetwork(addr=addrs[1], addrs=addrs, timeout=20.0,
                           proto="tcp", crc=False)]
        threads = [threading.Thread(target=n.init, daemon=True)
                   for n in nets]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        try:
            nets_by_rank = sorted(nets, key=lambda m: m.rank())
            for net in nets_by_rank:
                for peer in net._peers.values():
                    assert not peer.dial_crc and not peer.listen_crc

            def fn(net, r):
                if r == 0:
                    net.send([r, "ok"], 1, 9)
                    return None
                return net.receive(0, 9)

            assert run_on_ranks(nets_by_rank, fn)[1] == [0, "ok"]
        finally:
            for n in nets:
                n.finalize()

    def test_corrupted_frame_raises_typed_err_truncate(self):
        # Chaos flips one payload bit AFTER the sender computes the CRC
        # — genuine line damage. The receive must raise a typed error
        # naming source rank and tag, never decode garbage.
        with tcp_cluster(2, crc=True, optimeout=5.0) as nets:
            nets[0]._chaos = ChaosEngine(parse_chaos("5:1.0:corrupt"))
            errs = [None, None]

            def fn(net, r):
                try:
                    if r == 0:
                        net.send(list(range(200)), 1, 42)
                    else:
                        net.receive(0, 42)
                except MpiError as exc:
                    errs[r] = exc

            run_on_ranks(nets, fn, timeout=30.0)
            exc = errs[1]
            assert isinstance(exc, ChecksumError)
            assert exc.src == 0 and exc.tag == 42
            assert "rank 0" in str(exc) and "tag 42" in str(exc)
            assert errclass.classify(exc) == errclass.ERR_TRUNCATE
            assert exc.Get_error_class() == errclass.ERR_TRUNCATE
            # The sender never gets the ack for the damaged frame — its
            # deadline fires instead of hanging forever.
            assert isinstance(errs[0], MpiError)

    def test_corruption_fails_the_sender_without_optimeout(self):
        # "Retiring the connection" must be real: the receiver closes
        # both conns on a CRC failure, so the SENDER's ack wait fails
        # via peer-death even with no deadline configured — corruption
        # never reintroduces the infinite hang.
        with tcp_cluster(2, crc=True) as nets:  # optimeout unset
            nets[0]._chaos = ChaosEngine(parse_chaos("5:1.0:corrupt"))
            errs = [None, None]

            def fn(net, r):
                try:
                    if r == 0:
                        net.send(b"y" * 128, 1, 8)
                    else:
                        net.receive(0, 8)
                except MpiError as exc:
                    errs[r] = exc

            run_on_ranks(nets, fn, timeout=20.0)
            assert isinstance(errs[1], ChecksumError)
            assert isinstance(errs[0], MpiError)  # typed, and promptly

    def test_future_ops_to_corrupting_peer_fail_fast(self):
        with tcp_cluster(2, crc=True, optimeout=5.0) as nets:
            nets[0]._chaos = ChaosEngine(parse_chaos("5:1.0:corrupt"))

            def fn(net, r):
                if r == 0:
                    try:
                        net.send(b"x" * 64, 1, 1)
                    except MpiError:
                        pass
                    return None
                with pytest.raises(MpiError):
                    net.receive(0, 1)
                # Stream is retired after corruption: later ops raise
                # immediately instead of waiting out a deadline.
                t0 = time.monotonic()
                with pytest.raises(MpiError):
                    net.receive(0, 2)
                return time.monotonic() - t0

            elapsed = run_on_ranks(nets, fn, timeout=30.0)[1]
            assert elapsed < 2.0


# ---------------------------------------------------------------------------
# (c) operation deadlines + peer-death detection
# ---------------------------------------------------------------------------


class TestOperationDeadlines:
    def test_receive_with_no_sender_hits_deadline(self):
        with tcp_cluster(2, optimeout=1.0) as nets:
            t0 = time.monotonic()
            with pytest.raises(DeadlineError) as ei:
                nets[0].receive(1, 99)
            elapsed = time.monotonic() - t0
            assert 0.9 <= elapsed < 10.0
            assert errclass.classify(ei.value) == errclass.ERR_PENDING
            assert "receive(source=1, tag=99)" in str(ei.value)

    def test_send_with_no_receiver_hits_ack_deadline(self):
        with tcp_cluster(2, optimeout=1.0) as nets:
            t0 = time.monotonic()
            with pytest.raises(DeadlineError) as ei:
                nets[0].send([1, 2], 1, 77)
            assert time.monotonic() - t0 < 10.0
            assert "ack wait" in str(ei.value)
            assert errclass.classify(ei.value) == errclass.ERR_PENDING

    def test_receive_from_killed_peer_fails_fast(self):
        # A peer that dies mid-wait: the reader thread's ConnectionError
        # marks the peer dead and the pending receive raises well before
        # the (long) deadline.
        with tcp_cluster(2, optimeout=30.0) as nets:
            err = [None]
            done = threading.Event()

            def blocked():
                try:
                    nets[0].receive(1, 7)
                except MpiError as exc:
                    err[0] = exc
                done.set()

            t = threading.Thread(target=blocked, daemon=True)
            t.start()
            time.sleep(0.3)
            t0 = time.monotonic()
            nets[1].finalize()  # rank 1 dies
            assert done.wait(timeout=5.0)
            assert time.monotonic() - t0 < 5.0
            assert isinstance(err[0], PeerDeadError)
            assert err[0].peer == 1
            assert errclass.classify(err[0]) == errclass.ERR_PENDING

    def test_future_ops_to_dead_peer_fail_immediately(self):
        with tcp_cluster(2) as nets:
            nets[1].finalize()
            time.sleep(0.5)  # let rank 0's readers observe the loss
            t0 = time.monotonic()
            with pytest.raises(MpiError):
                nets[0].receive(1, 1)
            with pytest.raises(MpiError):
                nets[0].send("x", 1, 2)
            assert time.monotonic() - t0 < 2.0

    def test_self_path_honors_deadline(self):
        # The in-process self-send rendezvous is covered like the
        # remote path: a self receive with no matching self send (and
        # vice versa) raises DeadlineError instead of hanging.
        with tcp_cluster(2, optimeout=1.0) as nets:
            t0 = time.monotonic()
            with pytest.raises(DeadlineError, match="self rendezvous"):
                nets[0].receive(0, 31)
            with pytest.raises(DeadlineError, match="self rendezvous"):
                nets[0].send("x", 0, 32)
            assert time.monotonic() - t0 < 10.0
            # The timed-out receive retired its entry: a fresh matched
            # pair on the same tag still works.
            done = []

            def sender():
                nets[0].send("again", 0, 31)
                done.append(True)

            t = threading.Thread(target=sender, daemon=True)
            t.start()
            assert nets[0].receive(0, 31) == "again"
            t.join(timeout=5)
            assert done

    def test_send_on_dead_socket_raises_typed_error(self):
        # A conn that died under a sender (peer crash / chaos reset on
        # a sibling thread) must surface a typed MpiError, not a raw
        # EBADF OSError.
        with tcp_cluster(2) as nets:
            peer = nets[0]._peers[1]
            peer.dial_sock.close()
            with pytest.raises(MpiError):
                nets[0].send("x", 1, 3)

    def test_no_deadline_by_default(self):
        # Without --mpi-optimeout nothing changes: a slow sender inside
        # the old infinite-wait contract still completes.
        with tcp_cluster(2) as nets:
            assert nets[0].optimeout is None

            def fn(net, r):
                if r == 0:
                    return net.receive(1, 3)
                time.sleep(0.5)
                net.send("late", 0, 3)
                return None

            assert run_on_ranks(nets, fn)[0] == "late"


# ---------------------------------------------------------------------------
# (d) abort propagation + launcher reaping
# ---------------------------------------------------------------------------


def _run_mpirun(args, timeout=90):
    return subprocess.run(
        [sys.executable, "-m", "mpi_tpu.launch.mpirun", *args],
        cwd=REPO, capture_output=True, text=True, timeout=timeout)


class TestAbortPropagation:
    def test_abort_frame_fails_pending_ops_jobwide(self):
        # 3 ranks: rank 2 aborts; rank 0's pending receive from rank 1
        # (NOT the aborter) must also raise — MPI_Abort terminates the
        # job, not one link.
        with tcp_cluster(3) as nets:
            err = [None]
            done = threading.Event()

            def blocked():
                try:
                    nets[0].receive(1, 11)
                except MpiError as exc:
                    err[0] = exc
                done.set()

            t = threading.Thread(target=blocked, daemon=True)
            t.start()
            time.sleep(0.3)
            nets[2].notify_abort(5)
            assert done.wait(timeout=5.0)
            assert isinstance(err[0], RemoteAbortError)
            assert err[0].peer == 2 and err[0].code == 5
            assert "rank 2 aborted" in str(err[0])

    def test_comm_abort_exists(self):
        # Comm.Abort is the mpi4py spelling; it must exist and delegate
        # (not called here — it would exit the test process).
        with tcp_cluster(2) as nets:
            assert callable(comm_world(nets[0]).Abort)


@pytest.mark.integration
class TestJobTermination:
    def test_abort_terminates_all_ranks_promptly(self, tmp_path):
        prog = tmp_path / "aborter.py"
        prog.write_text(
            "import sys, time\n"
            "sys.path.insert(0, %r)\n"
            "import mpi_tpu\n"
            "mpi_tpu.init()\n"
            "if mpi_tpu.rank() == 1:\n"
            "    time.sleep(0.5)\n"
            "    mpi_tpu.abort(7)\n"
            "try:\n"
            "    mpi_tpu.receive(1, 123)  # never satisfied\n"
            "except Exception:\n"
            "    sys.exit(21)  # abort propagated as a typed error\n"
            "sys.exit(0)\n" % str(REPO))
        port = _free_port_block(3)
        t0 = time.monotonic()
        res = _run_mpirun(["--port-base", str(port), "--timeout", "30",
                           "3", str(prog)])
        elapsed = time.monotonic() - t0
        # Without propagation+reaping the non-aborting ranks would block
        # in receive() until the CI timeout. The job must end in seconds
        # with the abort code (rank 1) or the propagated failure (21).
        assert res.returncode in (7, 21), (res.returncode, res.stderr)
        assert elapsed < 40.0
        assert "abort(7)" in res.stderr

    def test_chaos_crash_is_reaped(self, tmp_path):
        prog = tmp_path / "crasher.py"
        prog.write_text(
            "import os, sys\n"
            "sys.path.insert(0, %r)\n"
            "os.environ['MPI_TPU_CHAOS'] = '3:1:crash@4'\n"
            "import mpi_tpu\n"
            "mpi_tpu.init()\n"
            "r, n = mpi_tpu.rank(), mpi_tpu.size()\n"
            "for step in range(100):\n"
            "    mpi_tpu.sendrecv(r, dest=(r + 1) %% n,\n"
            "                     source=(r - 1) %% n, tag=step)\n"
            "sys.exit(0)\n" % str(REPO))
        port = _free_port_block(2)
        t0 = time.monotonic()
        res = _run_mpirun(["--port-base", str(port), "--timeout", "30",
                           "2", str(prog)])
        elapsed = time.monotonic() - t0
        assert res.returncode != 0
        assert elapsed < 40.0
        assert "chaos crash@4" in res.stderr

    def test_sigterm_ignorer_is_killed_after_grace(self, tmp_path):
        # A survivor stuck ignoring SIGTERM must not wedge the launcher:
        # the grace period expires and SIGKILL reaps it.
        prog = tmp_path / "stubborn.py"
        prog.write_text(
            "import signal, sys, time\n"
            "base = int(sys.argv[1])\n"
            "addr = sys.argv[sys.argv.index('--mpi-addr') + 1]\n"
            "port = int(addr.rsplit(':', 1)[1])\n"
            "if port == base:\n"
            "    time.sleep(1.5)   # let the survivor reach its SIG_IGN\n"
            "    sys.exit(3)\n"
            "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
            "time.sleep(60)\n")
        port = _free_port_block(2)
        t0 = time.monotonic()
        res = _run_mpirun(["--port-base", str(port), "--kill-grace", "1",
                           "2", str(prog), str(port)], timeout=45)
        elapsed = time.monotonic() - t0
        assert res.returncode == 3
        assert elapsed < 20.0, elapsed
        assert "killing" in res.stderr


# ---------------------------------------------------------------------------
# Flag-driven smoke (tier-1): chaos reaches any program unchanged
# ---------------------------------------------------------------------------


class TestChaosSmoke:
    def test_env_spec_installs_engine_and_preserves_results(self, monkeypatch):
        # MPI_TPU_CHAOS alone puts the default backend under chaos — no
        # program changes. Seeded delay at full rate; results exact.
        monkeypatch.setenv("MPI_TPU_CHAOS", "21:1.0:latency")
        with tcp_cluster(2) as nets:
            for net in nets:
                assert isinstance(net._chaos, ChaosEngine)
                assert net._chaos.config.seed == 21

            def fn(net, r):
                out = []
                for step in range(5):
                    out.append(net_sendrecv(net, r, step))
                return out

            def net_sendrecv(net, r, step):
                if r == 0:
                    net.send(step * 10, 1, step)
                    return net.receive(1, 100 + step)
                got = net.receive(0, step)
                net.send(got + 1, 0, 100 + step)
                return got

            res = run_on_ranks(nets, fn, timeout=60.0)
            assert res[0] == [1, 11, 21, 31, 41]
            assert res[1] == [0, 10, 20, 30, 40]

    def test_flagless_cluster_has_no_engine(self):
        with tcp_cluster(2) as nets:
            assert all(net._chaos is None for net in nets)


@pytest.mark.slow
class TestCorruptionSoak:
    @pytest.mark.parametrize("seed", [13, 77])
    def test_low_rate_corruption_never_hangs_or_garbage_decodes(self, seed):
        seed += int(os.environ.get("MPI_TPU_CHAOS_SOAK_SEED", "0")) * 100
        # Under sparse random corruption every op either succeeds with
        # the exact value or raises a typed MpiError — and the run ends
        # by itself (deadlines + peer-death, no outer timeout reliance).
        with tcp_cluster(2, crc=True, optimeout=3.0) as nets:
            nets[0]._chaos = ChaosEngine(parse_chaos(f"{seed}:0.2:corrupt"))

            def fn(net, r):
                ok = bad = 0
                for step in range(30):
                    try:
                        if r == 0:
                            net.send([step] * 10, 1, step)
                        else:
                            got = net.receive(0, step)
                            assert got == [step] * 10  # no garbage
                        ok += 1
                    except MpiError:
                        bad += 1
                        break  # stream retired after first corruption
                return ok, bad

            results = run_on_ranks(nets, fn, timeout=120.0)
            assert all(ok + bad >= 1 for ok, bad in results)
