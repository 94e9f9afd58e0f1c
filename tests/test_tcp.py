"""TCP driver tests (reference: network.go).

Runs N in-process ranks on localhost — the single-machine full-stack
distributed harness (the reference's gompirun-on-loopback story,
gompirun.go:46-51, compressed into one process)."""

import threading
import time
import uuid

import numpy as np
import pytest

from mpi_tpu.api import MpiError, TagError
from mpi_tpu.backends.tcp import InitError, PeerDeadError, TcpNetwork

from conftest import run_on_ranks, tcp_cluster


class TestRankAssignment:
    def test_sorted_addr_consensus(self):
        # network.go:94-109: rank = index in sorted address list.
        addrs = ["127.0.0.1:09002", "127.0.0.1:09000", "127.0.0.1:09001"]
        net = TcpNetwork(addr="127.0.0.1:09001", addrs=addrs)
        net._assign_ranks()
        assert net.rank() == 1
        assert net.size() == 3

    def test_duplicate_addr_rejected(self):
        net = TcpNetwork(addr=":1", addrs=[":1", ":1"])
        with pytest.raises(InitError, match="duplicate"):
            net._assign_ranks()

    def test_own_addr_missing_rejected(self):
        net = TcpNetwork(addr=":9", addrs=[":1", ":2"])
        with pytest.raises(InitError, match="not in addrs"):
            net._assign_ranks()

    def test_single_node_default(self):
        # network.go:55-58: no addrs → ":5000", rank 0 of 1.
        net = TcpNetwork(timeout=1.0)
        net.init()
        try:
            assert net.rank() == 0
            assert net.size() == 1
            assert net.addr == ":5000"
        finally:
            net.finalize()


class TestClusterBootstrap:
    def test_ranks_agree(self, cluster4):
        assert [m.rank() for m in cluster4] == [0, 1, 2, 3]
        assert all(m.size() == 4 for m in cluster4)

    def test_password_mismatch_fails_init(self):
        from conftest import _free_ports

        ports = _free_ports(2)
        addrs = sorted(f"127.0.0.1:{p:05d}" for p in ports)
        a = TcpNetwork(addr=addrs[0], addrs=addrs, password="right", timeout=2.0)
        b = TcpNetwork(addr=addrs[1], addrs=addrs, password="wrong", timeout=2.0)
        errs = [None, None]

        def _init(net, i):
            try:
                net.init()
            except BaseException as exc:  # noqa: BLE001
                errs[i] = exc

        ts = [threading.Thread(target=_init, args=(n, i), daemon=True)
              for i, n in enumerate((a, b))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=15)
        assert any(isinstance(e, InitError) for e in errs)
        for n in (a, b):
            n.finalize()

    def test_dial_timeout(self):
        # Peer never comes up → init fails within the timeout
        # (network.go:297-312 retry-until-deadline).
        from conftest import _free_ports

        ports = _free_ports(2)
        addrs = sorted(f"127.0.0.1:{p:05d}" for p in ports)
        net = TcpNetwork(addr=addrs[0], addrs=addrs, timeout=1.0)
        t0 = time.monotonic()
        with pytest.raises(InitError):
            net.init()
        assert time.monotonic() - t0 < 10


class TestSendReceive:
    def test_pairwise_bytes(self, cluster4):
        def body(net, r):
            if r == 0:
                net.send(b"hello from 0", dest=1, tag=7)
            elif r == 1:
                assert net.receive(0, tag=7) == b"hello from 0"

        run_on_ranks(cluster4, body)

    def test_ndarray_roundtrip(self, cluster4):
        payload = np.arange(1000, dtype=np.float64).reshape(10, 100)

        def body(net, r):
            if r == 2:
                net.send(payload, dest=3, tag=1)
            elif r == 3:
                got = net.receive(2, tag=1)
                np.testing.assert_array_equal(got, payload)

        run_on_ranks(cluster4, body)

    def test_all_to_all_concurrent(self, cluster4):
        # The helloworld pattern (helloworld.go:53-81): every rank sends to
        # and receives from every rank, including itself, concurrently.
        n = len(cluster4)

        def body(net, r):
            errs = []

            def _send(dst):
                try:
                    net.send(f"{r}->{dst}", dest=dst, tag=100 + r)
                except BaseException as exc:  # noqa: BLE001
                    errs.append(exc)

            got = {}

            def _recv(src):
                try:
                    got[src] = net.receive(src, tag=100 + src)
                except BaseException as exc:  # noqa: BLE001
                    errs.append(exc)

            ts = [threading.Thread(target=_send, args=(d,), daemon=True)
                  for d in range(n)]
            ts += [threading.Thread(target=_recv, args=(s,), daemon=True)
                   for s in range(n)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=20)
            assert not errs, errs
            assert got == {s: f"{s}->{r}" for s in range(n)}

        run_on_ranks(cluster4, body)

    def test_rendezvous_send_blocks_until_receive(self, cluster4):
        # network.go:569: Send returns only after the receiver accepted.
        state = {"send_done_at": None, "recv_called_at": None}

        def body(net, r):
            if r == 0:
                net.send(b"x", dest=1, tag=5)
                state["send_done_at"] = time.monotonic()
            elif r == 1:
                time.sleep(0.5)
                state["recv_called_at"] = time.monotonic()
                net.receive(0, tag=5)

        run_on_ranks(cluster4, body)
        assert state["send_done_at"] >= state["recv_called_at"]

    def test_tag_demux_out_of_order(self, cluster4):
        # Two messages, receives issued in the opposite order of sends.
        def body(net, r):
            if r == 0:
                net.send(b"first", dest=1, tag=1)
                net.send(b"second", dest=1, tag=2)
            elif r == 1:
                time.sleep(0.3)  # let both arrive (early-arrival buffering)
                assert net.receive(0, tag=2) == b"second"
                assert net.receive(0, tag=1) == b"first"

        # Sequential sends would rendezvous-block; use a thread for send #1.
        def body_async(net, r):
            if r == 0:
                t = threading.Thread(
                    target=net.send, args=(b"first", 1, 1), daemon=True)
                t.start()
                net.send(b"second", dest=1, tag=2)
                t.join(timeout=10)
            elif r == 1:
                time.sleep(0.3)
                assert net.receive(0, tag=2) == b"second"
                assert net.receive(0, tag=1) == b"first"

        run_on_ranks(cluster4, body_async)

    def test_large_payload(self, cluster4):
        big = np.random.default_rng(1).integers(0, 255, 10_000_000,
                                                dtype=np.uint8)

        def body(net, r):
            if r == 0:
                net.send(big.tobytes(), dest=1, tag=9)
            elif r == 1:
                got = net.receive(0, tag=9)
                assert got == big.tobytes()

        run_on_ranks(cluster4, body, timeout=60)

    def test_large_ndarray_scatter_gather(self, cluster4):
        # >= PARTS_MIN_BYTES contiguous arrays take the encode_parts
        # zero-copy frame (prefix + view via writev); the receiver
        # must get an identical typed round-trip.
        big = np.random.default_rng(3).standard_normal(
            (512, 1024)).astype(np.float32)          # 2 MiB, 2-D

        def body(net, r):
            if r == 0:
                net.send(big, dest=1, tag=11)
                net.send(big[::2], dest=1, tag=12)   # non-contiguous
            elif r == 1:
                got = net.receive(0, tag=11)
                np.testing.assert_array_equal(got, big)
                got2 = net.receive(0, tag=12)
                np.testing.assert_array_equal(got2, big[::2])

        run_on_ranks(cluster4, body, timeout=60)

    def test_receive_out_buffer(self, cluster4):
        src_arr = np.arange(64, dtype=np.float32)

        def body(net, r):
            if r == 0:
                net.send(src_arr, dest=1, tag=3)
            elif r == 1:
                buf = np.zeros(64, np.float32)
                got = net.receive(0, tag=3, out=buf)
                assert got is buf
                np.testing.assert_array_equal(buf, src_arr)

        run_on_ranks(cluster4, body)

    def test_peer_out_of_range(self, cluster4):
        with pytest.raises(MpiError, match="out of range"):
            cluster4[0].send(b"x", dest=99, tag=0)

    def test_tag_reuse_after_completion_ok(self, cluster4):
        # mpi.go:123-125: the {dest, tag} pair may be reused once the
        # earlier call returns.
        def body(net, r):
            for i in range(5):
                if r == 0:
                    net.send(f"msg{i}", dest=1, tag=42)
                elif r == 1:
                    assert net.receive(0, tag=42) == f"msg{i}"

        run_on_ranks(cluster4, body)

    def test_duplicate_concurrent_send_tag_raises(self, cluster4):
        # Misuse detection: two live sends, same {dest, tag}
        # (network.go:469 panic → TagError here).
        def body(net, r):
            if r == 0:
                t = threading.Thread(target=net.send, args=(b"a", 1, 8),
                                     daemon=True)
                t.start()
                time.sleep(0.2)  # first send is parked in rendezvous
                with pytest.raises(TagError):
                    net.send(b"b", dest=1, tag=8)
                net.send(b"unblock", dest=1, tag=99)
                t.join(timeout=10)
            elif r == 1:
                assert net.receive(0, tag=99) == b"unblock"
                assert net.receive(0, tag=8) == b"a"

        run_on_ranks(cluster4, body)


class TestSelfSend:
    def test_self_send_concurrent(self, cluster4):
        def body(net, r):
            t = threading.Thread(target=net.send, args=(f"self{r}", r, 11),
                                 daemon=True)
            t.start()
            assert net.receive(r, tag=11) == f"self{r}"
            t.join(timeout=10)

        run_on_ranks(cluster4, body)

    def test_self_send_receiver_first(self, cluster4):
        # First-arrival-creates semantics (network.go:388-446): the
        # receiver can park before the sender shows up.
        def body(net, r):
            if r != 0:
                return
            box = []
            t = threading.Thread(target=lambda: box.append(net.receive(0, 13)),
                                 daemon=True)
            t.start()
            time.sleep(0.2)
            net.send(b"late", dest=0, tag=13)
            t.join(timeout=10)
            assert box == [b"late"]

        run_on_ranks(cluster4, body)

    def test_self_send_tag_not_leaked(self, cluster4):
        # Regression for reference defect (a) (SURVEY.md §2): a second
        # self-send with the same tag must work after the first completes.
        def body(net, r):
            if r != 1:
                return
            for i in range(3):
                t = threading.Thread(target=net.send,
                                     args=(f"pass{i}", 1, 77), daemon=True)
                t.start()
                assert net.receive(1, tag=77) == f"pass{i}"
                t.join(timeout=10)

        run_on_ranks(cluster4, body)

    def test_double_concurrent_self_send_same_tag_raises(self, cluster4):
        def body(net, r):
            if r != 2:
                return
            t = threading.Thread(target=net.send, args=(b"a", 2, 5),
                                 daemon=True)
            t.start()
            time.sleep(0.2)
            with pytest.raises(TagError):
                net.send(b"b", dest=2, tag=5)
            assert net.receive(2, tag=5) == b"a"
            t.join(timeout=10)

        run_on_ranks(cluster4, body)


class TestTwoRanks:
    def test_minimal_pair(self):
        with tcp_cluster(2) as nets:
            def body(net, r):
                if r == 0:
                    net.send(b"ping", dest=1, tag=0)
                    assert net.receive(1, tag=1) == b"pong"
                else:
                    assert net.receive(0, tag=0) == b"ping"
                    net.send(b"pong", dest=0, tag=1)

            run_on_ranks(nets, body)


class TestCancelReceive:
    def test_cancel_parked_receive(self, cluster4):
        from mpi_tpu.backends.tcp import ReceiveCancelled

        def body(net, r):
            if r != 0:
                return
            box = []

            def _recv():
                try:
                    net.receive(1, tag=55)
                except BaseException as exc:  # noqa: BLE001
                    box.append(exc)

            t = threading.Thread(target=_recv, daemon=True)
            t.start()
            time.sleep(0.2)
            assert net.cancel_receive(1, 55) is True
            t.join(timeout=5)
            assert box and isinstance(box[0], ReceiveCancelled)
            # Tag must be reusable afterwards.
            assert net.cancel_receive(1, 55) is False  # nothing pending

        run_on_ranks(cluster4, body)

    def test_stale_cancel_does_not_poison_next_claim(self, cluster4):
        def body(net, r):
            if r == 0:
                box = []

                def _recv():
                    try:
                        box.append(net.receive(1, tag=56))
                    except BaseException as exc:  # noqa: BLE001
                        box.append(exc)

                t = threading.Thread(target=_recv, daemon=True)
                t.start()
                time.sleep(0.2)
                net.cancel_receive(1, 56)
                t.join(timeout=5)
                # New receive on the same tag must work normally.
                got = net.receive(1, tag=56)
                assert got == b"fresh"
            elif r == 1:
                time.sleep(0.8)
                net.send(b"fresh", dest=0, tag=56)

        run_on_ranks(cluster4, body)

    def test_send_before_init_raises_mpi_error(self):
        net = TcpNetwork()
        with pytest.raises(MpiError, match="before init"):
            net.send(b"x", 0, 0)


class TestProtocols:
    """-mpi-protocol is honored: unix-domain sockets work end to end,
    anything unsupported raises loudly (VERDICT round-1 item 9;
    reference: NetProto accepts net-package protocols, network.go:26)."""

    def test_unix_socket_cluster(self, tmp_path):
        from mpi_tpu import collectives_generic as G

        addrs = sorted(str(tmp_path / f"rank{i}.sock") for i in range(3))
        with tcp_cluster(3, proto="unix", addrs=addrs) as nets_by_rank:
            def prog(net, r):
                import numpy as _np

                if r == 0:
                    net.send(b"over-unix", 1, 7)
                elif r == 1:
                    assert net.receive(0, 7) == b"over-unix"
                return G.allreduce(net, _np.float32(r + 1))

            totals = run_on_ranks(nets_by_rank, prog)
            assert all(float(t) == 6.0 for t in totals)
        # Socket files are cleaned up on finalize.
        assert not any((tmp_path / f"rank{i}.sock").exists()
                       for i in range(3))

    @staticmethod
    def _pair(proto, tmp_path):
        """A 2-rank cluster over ``proto``."""
        addrs = {"tcp": None,
                 "unix": [str(tmp_path / f"rank{i}.sock") for i in range(2)],
                 "shm": [f"{uuid.uuid4().hex[:8]}-{i}" for i in range(2)]}
        return tcp_cluster(2, proto=proto, addrs=addrs[proto])

    @pytest.mark.parametrize("proto", ["tcp", "unix", "shm"])
    def test_send_acked_before_receiver_finalized_returns(
            self, proto, tmp_path):
        # Rank 1 receives (which writes the ack) and finalizes at once.
        # Rank 0 then has the ack and an EOF on its dial conn and a bare
        # EOF on its listen conn, read by two threads; its dial reader
        # is held back so the listen reader meets its EOF first. That
        # EOF is an orderly close of ONE connection: it must not fail
        # the send whose ack was written ahead of the other one's.
        with self._pair(proto, tmp_path) as nets:
            sendtags = nets[0]._peers[1].sendtags
            route = sendtags.route

            def late_route(tag, item):
                time.sleep(0.5)
                route(tag, item)

            sendtags.route = late_route

            def prog(net, r):
                if r == 0:
                    net.send(b"last words", 1, 9)
                    return None
                got = net.receive(0, 9)
                net.finalize()
                return got

            assert run_on_ranks(nets, prog)[1] == b"last words"

    @pytest.mark.parametrize("proto", ["tcp", "unix", "shm"])
    def test_send_to_receiver_that_closed_unreceived_fails_fast(
            self, proto, tmp_path):
        # The other side of that line: no ack was ever written, so the
        # close is the peer's death as far as this send can tell — and
        # it says so within seconds with no --mpi-optimeout set.
        with self._pair(proto, tmp_path) as nets:
            assert nets[0].optimeout is None
            err = []

            def blocked():
                try:
                    nets[0].send(b"unheard", 1, 9)
                except MpiError as exc:
                    err.append(exc)

            t = threading.Thread(target=blocked, daemon=True)
            t.start()
            time.sleep(0.3)
            nets[1].finalize()
            t.join(timeout=5.0)
            assert not t.is_alive()
            assert len(err) == 1 and isinstance(err[0], PeerDeadError)
            assert err[0].peer == 1

    def test_unsupported_protocol_raises(self):
        from mpi_tpu.backends.tcp import InitError, TcpNetwork

        net = TcpNetwork(proto="sctp", addr=":1", addrs=[":1"])
        with pytest.raises(InitError, match="unsupported -mpi-protocol"):
            net.init()

    def test_tcp4_alias_still_works(self):
        with tcp_cluster(2) as nets:
            for n in nets:
                assert n.proto == "tcp"
        # explicit tcp4 single-node init
        from mpi_tpu.backends.tcp import TcpNetwork

        net = TcpNetwork(proto="tcp4", addr=":0", addrs=[":0"])
        net.init()
        assert net.size() == 1
        net.finalize()

    def test_tcp6_cluster_over_ipv6_loopback(self):
        """proto="tcp6" with Go's bracket address syntax ("[::1]:p") —
        full 2-rank bootstrap + p2p roundtrip over IPv6 (the reference
        accepts any net-package protocol, network.go:26)."""
        import socket as socketmod
        import threading as threadingmod

        import numpy as np

        from mpi_tpu.backends.tcp import TcpNetwork

        try:
            probe = socketmod.socket(socketmod.AF_INET6,
                                     socketmod.SOCK_STREAM)
            probe.bind(("::1", 0))
            probe.close()
        except OSError:
            pytest.skip("IPv6 loopback unavailable")

        from conftest import _free_ports

        ports = _free_ports(2)
        addrs = sorted(f"[::1]:{p:05d}" for p in ports)
        nets = [TcpNetwork(addr=a, addrs=list(addrs), timeout=20.0,
                           proto="tcp6") for a in addrs]
        errs = [None, None]
        out = {}

        def run(i):
            try:
                nets[i].init()
                r = nets[i].rank()
                if r == 0:
                    nets[i].send(np.arange(4, dtype=np.float32), 1, 5)
                else:
                    out["got"] = nets[i].receive(source=0, tag=5)
                nets[i].finalize()
            except BaseException as exc:  # noqa: BLE001
                errs[i] = exc

        threads = [threadingmod.Thread(target=run, args=(i,), daemon=True)
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(40)
        assert errs == [None, None], errs
        np.testing.assert_array_equal(out["got"],
                                      np.arange(4, dtype=np.float32))

    def test_split_hostport_brackets(self):
        from mpi_tpu.backends.tcp import _split_hostport

        assert _split_hostport("[::1]:5000") == ("::1", 5000)
        assert _split_hostport("[fe80::2]:08080") == ("fe80::2", 8080)
        assert _split_hostport("127.0.0.1:5000") == ("127.0.0.1", 5000)
        assert _split_hostport(":5000") == ("", 5000)


class TestFinalizeIdempotent:
    def test_finalize_twice_is_noop(self):
        net = TcpNetwork(timeout=1.0)
        net.init()
        net.finalize()
        net.finalize()  # second call must not raise or re-close

    def test_finalize_without_init(self):
        # Error-path cleanup (tests, chaos harness) calls finalize()
        # unconditionally — including on a never-inited backend.
        TcpNetwork().finalize()

    def test_finalize_after_failed_init(self):
        from conftest import _free_ports

        port = _free_ports(1)[0]
        addrs = [f"127.0.0.1:{port:05d}", f"127.0.0.1:{port + 1:05d}"]
        net = TcpNetwork(addr=addrs[0], addrs=addrs, timeout=0.3)
        with pytest.raises(InitError):
            net.init()  # peer never shows up
        net.finalize()  # bootstrap already cleaned up; this is a no-op
        net.finalize()

    def test_cluster_finalize_all_twice(self, cluster4):
        for net in cluster4:
            net.finalize()
        for net in cluster4:
            net.finalize()


class TestRecvExactHardening:
    """A socket.timeout mid-frame desynchronizes the stream: it must be
    a fatal ConnectionError for that peer, never a retryable timeout
    (a later retry would read from the middle of the frame)."""

    def _pair(self):
        import socket as socketmod

        a, b = socketmod.socketpair()
        return a, b

    def test_timeout_on_frame_boundary_stays_timeout(self):
        import socket as socketmod

        from mpi_tpu.backends.tcp import _recv_exact

        a, b = self._pair()
        try:
            a.settimeout(0.2)
            with pytest.raises(socketmod.timeout):
                _recv_exact(a, 4)  # nothing sent: clean boundary
        finally:
            a.close()
            b.close()

    def test_timeout_mid_read_is_fatal(self):
        from mpi_tpu.backends.tcp import _recv_exact

        a, b = self._pair()
        try:
            a.settimeout(0.3)
            b.sendall(b"\x01\x02")  # 2 of 8 bytes, then silence
            with pytest.raises(ConnectionError, match="desynchronized"):
                _recv_exact(a, 8)
        finally:
            a.close()
            b.close()

    def test_timeout_on_later_segment_is_fatal(self):
        # The payload read of a frame whose header already arrived is
        # mid-frame even when 0 of its own bytes arrived yet.
        from mpi_tpu.backends.tcp import _recv_exact

        a, b = self._pair()
        try:
            a.settimeout(0.3)
            with pytest.raises(ConnectionError, match="desynchronized"):
                _recv_exact(a, 4, midframe=True)
        finally:
            a.close()
            b.close()
