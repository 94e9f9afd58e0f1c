"""Shared-memory protocol tests (``-mpi-protocol shm``).

The shm engine (backends/shm.py + native/shmcore.cpp) must preserve the
TCP driver's observable semantics — same handshake contract
(network.go:198-263), same tagged rendezvous data path
(network.go:518-625) — while carrying frames through SPSC rings in
POSIX shared memory. Both the native engine and the pure-Python
fallback ring are covered; the cluster-level tests run the *same*
assertions as the TCP harness, which is the parity argument.
"""

import os
import subprocess
import sys
import threading
import uuid
from pathlib import Path

import numpy as np
import pytest

from mpi_tpu import native as native_mod
from mpi_tpu.backends import shm as shm_mod
from mpi_tpu.backends.shm import (ShmConn, attach_ring, create_ring,
                                  ring_name, session_key, unlink_ring)
from mpi_tpu.backends.tcp import InitError, TcpNetwork

from conftest import run_on_ranks, tcp_cluster

REPO = Path(__file__).resolve().parent.parent


def _addrs(n: int):
    """Opaque per-test world ids (shm addresses never hit the network;
    the uuid keeps concurrent test processes collision-free)."""
    base = uuid.uuid4().hex[:8]
    return [f"{base}-{i}" for i in range(n)]


def shm_cluster(n: int, password: str = "", timeout: float = 20.0):
    return tcp_cluster(n, password=password, timeout=timeout, proto="shm",
                       addrs=_addrs(n))


@pytest.fixture(params=["native", "python"])
def ring_mode(request, monkeypatch):
    """Run ring-level tests against both engines."""
    if request.param == "python":
        monkeypatch.setenv("MPI_TPU_NO_NATIVE", "1")
        native_mod._reset_for_testing()
        yield "python"
        native_mod._reset_for_testing()
    else:
        if native_mod.shmcore() is None:
            pytest.skip(f"native shmcore unavailable: "
                        f"{native_mod.build_error('shmcore')}")
        yield "native"


class TestRing:
    def test_create_attach_frame_roundtrip(self, ring_mode):
        name = f"/mpitpu-test-{uuid.uuid4().hex[:10]}"
        creator = create_ring(name, 1 << 14)
        try:
            attached = attach_ring(name)
            assert attached is not None
            # One loopback conn: the creator handle is the ring's sole
            # producer, the attached handle its sole consumer (each
            # handle carries its own resumable-op state).
            conn = ShmConn(creator, attached)
            payload = os.urandom(1000)
            conn.send_frame(0, 1234, payload)
            kind, tag, got = conn.recv_frame()
            assert (kind, tag, bytes(got)) == (0, 1234, payload)
        finally:
            creator.mark_closed()
            creator.close()
            if attached is not None:
                attached.close()
            unlink_ring(name)

    def test_send_frame2_roundtrip(self, ring_mode):
        # The codec's scatter-gather path (encode_parts): prefix +
        # array view stream as ONE frame, byte-identical on the wire
        # to the single-buffer form — including resumed streaming when
        # the frame is larger than the ring.
        import numpy as np

        name = f"/mpitpu-test-{uuid.uuid4().hex[:10]}"
        creator = create_ring(name, 1 << 12)
        attached = attach_ring(name)
        try:
            conn = ShmConn(creator, attached)
            arr = np.random.default_rng(7).standard_normal(
                (1 << 14)).astype(np.float32)   # 16x the ring
            from mpi_tpu.utils import serialize as S

            prefix, view = S.encode_parts(arr)
            assert view is not None
            got = {}

            def reader():
                got["frame"] = conn.recv_frame()

            t = threading.Thread(target=reader)
            t.start()
            conn.send_frame2(5, 99, prefix, view)
            t.join(20)
            kind, tag, payload = got["frame"]
            assert (kind, tag) == (5, 99)
            assert bytes(payload) == S.encode(arr)
            back = S.decode(payload)
            np.testing.assert_array_equal(back, arr)
        finally:
            creator.mark_closed()
            creator.close()
            if attached is not None:
                attached.close()
            unlink_ring(name)

    def test_payload_larger_than_ring_streams(self, ring_mode):
        # Capacity bounds memory, not message size: a payload 8x the
        # ring streams through while the reader drains.
        name = f"/mpitpu-test-{uuid.uuid4().hex[:10]}"
        creator = create_ring(name, 1 << 12)
        attached = attach_ring(name)
        try:
            conn = ShmConn(creator, attached)  # produce via creator,
            payload = os.urandom(8 << 12)      # consume via attached
            got = {}

            def reader():
                got["frame"] = conn.recv_frame()

            t = threading.Thread(target=reader)
            t.start()
            conn.send_frame(0, 7, payload)
            t.join(10)
            assert not t.is_alive()
            assert bytes(got["frame"][2]) == payload
        finally:
            creator.mark_closed()
            creator.close()
            attached.close()
            unlink_ring(name)

    def test_attach_missing_returns_none(self, ring_mode):
        assert attach_ring(f"/mpitpu-test-{uuid.uuid4().hex[:10]}") is None

    def test_closed_ring_raises_connectionerror(self, ring_mode):
        name = f"/mpitpu-test-{uuid.uuid4().hex[:10]}"
        creator = create_ring(name, 1 << 12)
        attached = attach_ring(name)
        try:
            conn = ShmConn(creator, attached)
            creator.mark_closed()
            with pytest.raises(ConnectionError):
                conn.recv_frame()
        finally:
            creator.close()
            attached.close()
            unlink_ring(name)

    def test_recv_timeout(self, ring_mode):
        import socket as socketmod

        name = f"/mpitpu-test-{uuid.uuid4().hex[:10]}"
        creator = create_ring(name, 1 << 12)
        try:
            rx = ShmConn(creator, creator)
            rx.settimeout(0.1)
            with pytest.raises(socketmod.timeout):
                rx.recv_frame()
        finally:
            creator.mark_closed()
            creator.close()
            unlink_ring(name)


    def test_midframe_timeout_poisons_native_handles(self):
        """ADVICE r2: after a mid-frame -ETIMEDOUT the stream position
        is inside a half-written frame; silently resuming a NEW frame
        from the stale offset would corrupt the byte stream. The native
        handle latches a poison flag instead: every later op fails
        loudly (EPIPE) until the ring is closed."""
        import errno as errnomod
        import socket as socketmod

        if native_mod.shmcore() is None:
            pytest.skip(f"native shmcore unavailable: "
                        f"{native_mod.build_error('shmcore')}")
        name = f"/mpitpu-test-{uuid.uuid4().hex[:10]}"
        creator = create_ring(name, 1 << 12)
        attached = attach_ring(name)
        try:
            conn = ShmConn(creator, attached)
            conn.settimeout(0.1)
            # No reader drains: an 8 KiB payload cannot fit the 4 KiB
            # ring, so the send strands mid-frame and times out.
            with pytest.raises(socketmod.timeout):
                conn.send_frame(0, 1, os.urandom(1 << 13))
            # A NEW frame on the poisoned tx handle fails loudly and
            # immediately (EPIPE), not silently corrupting the stream.
            with pytest.raises(OSError) as exc:
                conn.send_frame(0, 2, b"tiny")
            assert exc.value.errno == errnomod.EPIPE
            # Receive side: the header of the stranded frame IS
            # readable, but its payload can never fully arrive — the
            # payload timeout is mid-frame by definition, so the rx
            # handle poisons too.
            with pytest.raises(socketmod.timeout):
                conn.recv_frame()
            with pytest.raises(OSError) as exc:
                conn.recv_frame()
            assert exc.value.errno == errnomod.EPIPE
        finally:
            creator.mark_closed()
            creator.close()
            if attached is not None:
                attached.close()
            unlink_ring(name)

    def test_python_side_abandonment_poisons_via_shm_abandon(self):
        """The Python wrapper abandons a native op when ITS deadline
        expires between -EINTR resumes; shm_abandon must latch poison
        for mid-frame abandonment (or force=1) and leave a clean
        handle retryable (force=0, no progress)."""
        import ctypes
        import errno as errnomod

        if native_mod.shmcore() is None:
            pytest.skip(f"native shmcore unavailable: "
                        f"{native_mod.build_error('shmcore')}")
        lib = native_mod.shmcore()
        name = f"/mpitpu-test-{uuid.uuid4().hex[:10]}"
        creator = create_ring(name, 1 << 12)
        try:
            h = creator._h
            # Clean handle, no progress: abandonment does NOT poison.
            assert lib.shm_abandon(h, 0) == 0
            conn = ShmConn(creator, creator)
            conn.send_frame(0, 1, b"still works")
            assert bytes(conn.recv_frame()[2]) == b"still works"
            # force=1 (e.g. a payload read whose header was consumed):
            # poisons even at op_done == 0.
            assert lib.shm_abandon(h, 1) == 1
            with pytest.raises(OSError) as exc:
                conn.send_frame(0, 2, b"x")
            assert exc.value.errno == errnomod.EPIPE
        finally:
            creator.mark_closed()
            creator.close()
            unlink_ring(name)

class TestNames:
    def test_session_key_binds_addrs_and_password(self):
        a = session_key(["x", "y"], "pw")
        assert session_key(["y", "x"], "pw") == a      # order-insensitive
        assert session_key(["x", "y"], "other") != a   # password folds in
        assert session_key(["x", "z"], "pw") != a

    def test_ring_name_shape(self):
        n = ring_name("deadbeef", 2, 5, "d")
        assert n.startswith("/") and "2to5d" in n and len(n) < 250


class TestShmCluster:
    def test_ranks_agree_and_host_key(self):
        with shm_cluster(3) as nets:
            assert [m.rank() for m in nets] == [0, 1, 2]
            assert all(m.size() == 3 for m in nets)
            assert all(m.host_key() == "shm" for m in nets)

    def test_all_to_all_concurrent_including_self(self):
        # The helloworld pattern (helloworld.go:53-81) over shm.
        with shm_cluster(3) as nets:
            def body(net, r):
                n = net.size()
                out = {}

                def send_all():
                    for d in range(n):
                        net.send(f"hi {r}->{d}", d, 50 + r)

                t = threading.Thread(target=send_all, daemon=True)
                t.start()
                for s in range(n):
                    out[s] = net.receive(s, 50 + s)
                t.join(10)
                return out

            results = run_on_ranks(nets, body)
            for r, out in enumerate(results):
                for s in range(3):
                    assert out[s] == f"hi {s}->{r}"

    def test_ndarray_roundtrip_bitwise(self):
        with shm_cluster(2) as nets:
            arr = np.random.default_rng(3).standard_normal(4096)

            def body(net, r):
                if r == 0:
                    net.send(arr, 1, 9)
                    return None
                return net.receive(0, 9)

            got = run_on_ranks(nets, body)[1]
            assert got.dtype == arr.dtype
            np.testing.assert_array_equal(got, arr)  # bitwise

    def test_large_payload_exceeding_ring(self, monkeypatch):
        # 64 KiB rings, 1 MiB payload: must stream, not deadlock.
        monkeypatch.setenv("MPI_TPU_SHM_RING_BYTES", str(1 << 16))
        with shm_cluster(2) as nets:
            blob = os.urandom(1 << 20)

            def body(net, r):
                if r == 0:
                    net.send(blob, 1, 1)
                    return None
                return net.receive(0, 1)

            assert run_on_ranks(nets, body)[1] == blob

    def test_rendezvous_send_blocks_until_receive(self):
        with shm_cluster(2) as nets:
            state = {"sent": None, "received_at": None}

            def body(net, r):
                import time as _t
                if r == 0:
                    net.send(b"x", 1, 3)
                    state["sent"] = _t.monotonic()
                else:
                    _t.sleep(0.5)
                    state["received_at"] = _t.monotonic()
                    net.receive(0, 3)

            run_on_ranks(nets, body)
            # sender returned only after the receiver engaged
            assert state["sent"] >= state["received_at"] - 0.05

    def test_password_mismatch_fails_init(self):
        addrs = _addrs(2)
        a = TcpNetwork(proto="shm", addr=addrs[0], addrs=addrs,
                       password="right", timeout=2.0)
        b = TcpNetwork(proto="shm", addr=addrs[1], addrs=addrs,
                       password="wrong", timeout=2.0)
        errs = []

        def _init(net):
            try:
                net.init()
            except InitError as exc:
                errs.append(exc)

        ts = [threading.Thread(target=_init, args=(n,), daemon=True)
              for n in (a, b)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(15)
        for n in (a, b):
            try:
                n.finalize()
            except BaseException:  # noqa: BLE001
                pass
        # Different passwords change the session key, so the worlds
        # cannot even find each other's rings: both sides time out.
        assert errs

    def test_finalize_unlinks_rings(self):
        addrs = _addrs(2)
        key = session_key(addrs, "")
        with shm_cluster(2, timeout=10.0) as nets:
            assert nets[0].size() == 2
        leftovers = [f for f in os.listdir("/dev/shm")
                     if key in f]
        assert leftovers == []

    def test_python_fallback_cluster(self, monkeypatch):
        monkeypatch.setenv("MPI_TPU_NO_NATIVE", "1")
        native_mod._reset_for_testing()
        try:
            with shm_cluster(2, timeout=10.0) as nets:
                def body(net, r):
                    if r == 0:
                        net.send(list(range(100)), 1, 2)
                        return None
                    return net.receive(0, 2)

                assert run_on_ranks(nets, body)[1] == list(range(100))
        finally:
            native_mod._reset_for_testing()


@pytest.mark.integration
class TestShmEndToEnd:
    def test_helloworld_3_ranks_shm_protocol(self):
        # The reference's launcher story with -mpi-protocol swapped to
        # shm: same program, same flag ABI, ring transport underneath.
        # Unique password → unique session key, so concurrent test runs
        # on one machine can never collide on ring names.
        res = subprocess.run(
            [sys.executable, "-m", "mpi_tpu.launch.mpirun",
             "--timeout", "30", "--password", uuid.uuid4().hex,
             "3", "examples/helloworld.py", "--mpi-protocol", "shm"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert res.returncode == 0, res.stderr
        # Count substrings, not lines: concurrent children may interleave
        # mid-line on the shared stdout pipe.
        assert res.stdout.count("<- rank") == 9  # 3 ranks x 3 greetings
