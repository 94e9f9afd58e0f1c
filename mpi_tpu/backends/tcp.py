"""TCP all-to-all driver — CPU fallback and bitwise-parity oracle.

Rebuild of the reference's ``Network`` backend (/root/reference/network.go),
preserving its observable semantics:

  * leaderless deterministic rank assignment: sort the address list, rank =
    index of own address; duplicate or missing addresses are errors
    (network.go:94-118);
  * eager all-to-all bootstrap at init: every pair of ranks holds two TCP
    connections, one dialed by each side; ``dial`` carries my sends and the
    peer's acks, ``listen`` carries the peer's sends and my acks
    (network.go:122-159, 499-506);
  * password-validated handshake with accept timeout on the listen side and
    a 100 ms dial-retry loop until the init timeout on the dial side
    (network.go:198-263, 294-351);
  * tag-demultiplexed **rendezvous** messaging: ``send`` blocks until the
    matching ``receive`` has accepted the payload, signalled by an ack
    frame written back on the same connection the data arrived on
    (network.go:518-625);
  * in-process self-send rendezvous with first-arrival-creates semantics
    (network.go:371-446);
  * config resolution: explicit constructor args win over ``-mpi-*`` flags,
    with a single-node ``":5000"`` default (network.go:55-58, 69-90).

Deliberate fixes of the reference's latent defects (SURVEY.md §2), none of
which change the documented contracts:

  * self-send releases its tag on completion (the reference leaks it —
    ``Send`` registers the tag at network.go:534 but the local path returns
    without ``Delete`` at network.go:546-547, so tag reuse panics);
  * one write lock per socket — the reference lets concurrent sends to the
    same destination interleave gob streams on one conn (network.go:562);
  * persistent per-connection reader threads replace per-call reader
    goroutines, removing the reference's race where a reader spawned by
    ``Receive(tagB)`` decodes a message for not-yet-registered ``tagA`` and
    panics (network.go:587, 614);
  * early-arriving messages for unregistered tags are buffered; rendezvous
    is unaffected because the ack is only written when a ``receive``
    actually dequeues.

Wire protocol (replaces gob; all integers little-endian)::

    frame      := kind:u8  tag:i64  length:u32  payload[length]  [crc:u32]
    kind       := 0 DATA   payload = mpi_tpu.utils.serialize codec bytes
                  1 ACK    payload = empty (length 0)
                  2 HELLO  payload = utf-8 password, optionally followed
                           by "\\0mpi-feat:" and a comma-separated feature
                           list (see below); tag field carries the
                           sender's claimed rank id (initialMessage
                           {Password, Id}, network.go:198-201)
                  3 ABORT  payload = empty; tag field carries the abort
                           exit code (failure-propagation control frame,
                           docs/FAULT_TOLERANCE.md — no reference
                           analogue, the reference can only hang)

Integrity (``--mpi-crc``): each side advertises the ``crc32`` feature in
its HELLO; when **both** ends of a connection advertise it, every DATA
frame on that connection carries a CRC32 trailer over header+payload.
Off (the default, or a peer without the feature) the wire is bit-for-bit
today's format and the zero-copy native fast path is untouched; on, a
corrupted frame raises a typed ``ERR_TRUNCATE``-class error naming the
source rank and tag instead of a garbage decode.
"""

from __future__ import annotations

import os
import socket
import struct
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

from .. import flags as flagmod
from ..api import MpiError
from ..utils import trace
from ..utils.serialize import decode as codec_decode
from ..utils.serialize import encode as codec_encode
from ..utils.serialize import encode_parts as codec_encode_parts
from .rendezvous import (DeadlineError, ReceiveCancelled, Rendezvous,
                         TagManager)
from .shm import OrderlyClose, ShmConn

__all__ = ["TcpNetwork", "InitError", "ReceiveCancelled", "DeadlineError",
           "ChecksumError", "PeerDeadError", "RemoteAbortError"]

KIND_DATA = 0
KIND_ACK = 1
KIND_HELLO = 2
KIND_ABORT = 3

_FRAME_HDR = struct.Struct("<BqI")
_CRC_TRAILER = struct.Struct("<I")
_DIAL_RETRY_INTERVAL = 0.1  # network.go:298 — 100 ms poll

# HELLO feature negotiation: the password payload may be followed by this
# separator and a comma-separated feature list. A password that literally
# contains the separator would misparse — NUL bytes in passwords are
# rejected at init instead of risking a silent feature mismatch.
_FEATURE_SEP = b"\x00mpi-feat:"
_FEATURE_CRC = "crc32"

# The reference's NetProto accepts any `net` package protocol
# (network.go:26). Supported here: TCP (the default, "tcp4" an alias,
# "tcp6" for IPv6 with Go's "[::1]:5000" bracket addresses),
# unix-domain stream sockets (addresses = filesystem paths), and "shm"
# — same-host shared-memory rings via the native engine
# (backends/shm.py, native/shmcore.cpp; addresses = opaque ids).
# Anything else raises at init instead of being silently ignored.
_SUPPORTED_PROTOS = ("tcp", "tcp4", "tcp6", "unix", "shm")


class InitError(MpiError):
    """Bootstrap failure; aggregates per-peer handshake errors
    (network.go:185-195, 281-291)."""


class ChecksumError(MpiError):
    """A DATA frame failed its negotiated CRC32 integrity check.

    MPI class ``ERR_TRUNCATE`` (the class an MPI implementation reports
    when a message's bytes do not match what was sent). Carries the
    source rank and tag so the failure is attributable."""

    def __init__(self, src: int, tag: int):
        self.src = src
        self.tag = tag
        super().__init__(
            f"mpi_tpu: frame integrity check failed for message from "
            f"rank {src} tag {tag}: CRC32 mismatch — payload corrupted "
            f"in transit (MPI_ERR_TRUNCATE)")


class PeerDeadError(MpiError):
    """A peer's connection was lost; pending and future operations
    targeting it fail with this instead of hanging (MPI class
    ``ERR_PENDING`` — the operations did not complete)."""

    def __init__(self, peer: int, cause: BaseException):
        import re as _re

        self.peer = peer
        # Strip any (MPI_ERR_XXX) marker the cause carries: this error
        # classifies as ERR_PENDING, and errclass's marker scan takes
        # the FIRST marker in the message.
        cause_text = _re.sub(r"\s*\(MPI_ERR_[A-Z_]+\)", "", str(cause))
        super().__init__(
            f"mpi_tpu: peer rank {peer} is dead ({cause_text}); pending "
            f"and future operations targeting it fail (MPI_ERR_PENDING)")


class RemoteAbortError(MpiError):
    """A remote rank called ``abort()`` — its ABORT control frame
    arrived; this rank's operations involving any peer now raise."""

    def __init__(self, peer: int, code: int):
        self.peer = peer
        self.code = code
        super().__init__(
            f"mpi_tpu: rank {peer} aborted the job with code {code} "
            f"(MPI_ERR_OTHER)")


def _split_hostport(addr: str) -> Tuple[str, int]:
    host, sep, port = addr.rpartition(":")
    if not sep:
        raise MpiError(f"mpi_tpu: address {addr!r} missing :port")
    # Go's net.SplitHostPort bracket syntax for IPv6 literals:
    # "[::1]:5000" -> host "::1".
    if host.startswith("[") and host.endswith("]"):
        host = host[1:-1]
    return host, int(port)


def _view_cptr(view):
    """(c_void_p, keepalive) for a bytes-like without copying. The
    caller must hold ``keepalive`` until the C call returns."""
    import ctypes

    if isinstance(view, bytes):
        return ctypes.cast(ctypes.c_char_p(view), ctypes.c_void_p), view
    mv = memoryview(view).cast("B")
    if mv.readonly:
        b = bytes(mv)  # rare (readonly ndarray): one copy, still sound
        return ctypes.cast(ctypes.c_char_p(b), ctypes.c_void_p), b
    arr = (ctypes.c_ubyte * mv.nbytes).from_buffer(mv)
    return ctypes.cast(arr, ctypes.c_void_p), arr


def _crc32_frame(header: bytes, payload, payload2=None) -> int:
    """CRC32 over header + payload (+ payload2): the trailer value of an
    integrity-negotiated DATA frame. Covers the header too, so a
    corrupted kind/tag/length is also caught (when the length corruption
    still framed plausibly)."""
    c = zlib.crc32(header)
    c = zlib.crc32(payload, c)
    if payload2 is not None:
        view = memoryview(payload2)
        if view.ndim != 1 or view.itemsize != 1:
            view = view.cast("B")
        try:
            c = zlib.crc32(view, c)
        except BufferError:  # non-contiguous: one copy, rare
            c = zlib.crc32(bytes(view), c)
    return c


def _chaos_wire_send(sock, lock: threading.Lock, kind: int, tag: int,
                     payload, payload2, use_crc: bool, fault) -> None:
    """Chaos-plane frame writer: assembles the full frame (including the
    CRC trailer when negotiated — computed over the CLEAN bytes, exactly
    as a real sender would), then applies the injected wire fault so the
    receiver sees genuine line damage: a flipped payload bit, a frame
    cut short, or a vanished connection."""
    body = bytearray(_FRAME_HDR.pack(
        kind, tag,
        len(payload) + (0 if payload2 is None else
                        memoryview(payload2).nbytes)))
    payload_start = len(body)
    body += payload
    if payload2 is not None:
        body += memoryview(payload2)
    payload_len = len(body) - payload_start
    if use_crc:
        body += _CRC_TRAILER.pack(
            _crc32_frame(bytes(body[:payload_start]),
                         bytes(body[payload_start:])))
    if fault.corrupt_offset is not None and payload_len:
        at = payload_start + fault.corrupt_offset % payload_len
        body[at] ^= 1 << (fault.corrupt_bit % 8)
    with lock:
        if fault.reset:
            _shut(sock)
            return
        if fault.truncate_at is not None:
            # A frame cut short desynchronizes the stream permanently,
            # so the connection dies with it — the mid-frame-death
            # scenario (peer crashed while writing).
            cut = fault.truncate_at % max(1, len(body) - 1)
            try:
                sock.sendall(bytes(body[:cut]))
            except OSError:
                pass
            _shut(sock)
            return
        sock.sendall(bytes(body))


def _send_frame(sock, lock: threading.Lock, kind: int,
                tag: int, payload: bytes = b"",
                payload2=None, crc: bool = False, fault=None,
                stages=None) -> None:
    """Write one wire frame. With ``payload2`` (the codec's
    :func:`~mpi_tpu.utils.serialize.encode_parts` view) the frame body
    is ``payload + payload2`` scatter-gathered straight from the
    caller's buffer — the zero-copy ndarray data path; the receiver
    sees one frame either way.

    ``crc`` appends the negotiated CRC32 trailer to DATA frames (the
    integrity option takes the Python write path; with it off this
    function is byte-identical to the pre-CRC implementation).
    ``fault`` (a :class:`mpi_tpu.chaos.WireFault`) routes the frame
    through the chaos wire plane instead. ``stages`` (a caller-zeroed
    ``(ctypes.c_uint64 * 4)`` scratch) makes the native engine
    accumulate per-stage ns/counts — assemble ns, writev ns, writev
    calls, bytes — for the tracer's ``wire.write.*`` child spans; only
    the native path fills it (``stages[2]`` stays 0 otherwise)."""
    use_crc = crc and kind == KIND_DATA and not isinstance(sock, ShmConn)
    if fault is not None and fault.any() and not isinstance(sock, ShmConn):
        _chaos_wire_send(sock, lock, kind, tag, payload, payload2,
                         use_crc, fault)
        return
    n2 = 0 if payload2 is None else memoryview(payload2).nbytes
    if isinstance(sock, ShmConn):
        # shm conns frame in the ring engine; the per-conn lock still
        # serializes concurrent senders (the SPSC ring's one-producer
        # contract).
        if not isinstance(payload, bytes):
            payload = bytes(payload)
        with lock:
            if payload2 is not None:
                sock.send_frame2(kind, tag, payload, payload2)
            else:
                sock.send_frame(kind, tag, payload)
        return
    from .. import native as _native

    # Python socket timeouts make the fd non-blocking at the OS level;
    # the native engine only speaks blocking sockets (post-handshake data
    # path — handshake frames keep the Python path). Payloads past the
    # u32 wire limit fall through so struct.pack rejects them loudly.
    lib = (_native.wirecore()
           if sock.gettimeout() is None and not use_crc else None)
    if lib is not None and isinstance(payload, bytes) \
            and len(payload) + n2 <= 0xFFFFFFFF:
        # Native path: header + payload (+ array view) leave in one
        # writev — no user-space concatenation copy — with the GIL
        # released for the whole syscall loop (ctypes CDLL semantics).
        # -EINTR returns here so pending Python signal handlers
        # (Ctrl+C) run between resumes.
        import ctypes
        import errno as _errno
        import os as _os

        progress = ctypes.c_uint64(0)
        if payload2 is not None:
            ptr, keep = _view_cptr(payload2)
            with lock:
                while True:
                    rc = lib.wc_send_frame2(
                        sock.fileno(), kind, tag, payload, len(payload),
                        ptr, n2, ctypes.byref(progress), stages)
                    if rc != -_errno.EINTR:
                        break
            del keep
        else:
            with lock:
                while True:
                    rc = lib.wc_send_frame(sock.fileno(), kind, tag,
                                           payload, len(payload),
                                           ctypes.byref(progress), stages)
                    if rc != -_errno.EINTR:
                        break
        if rc == 0:
            return
        raise OSError(-rc, _os.strerror(-rc))
    header = _FRAME_HDR.pack(kind, tag, len(payload) + n2)
    trailer = (_CRC_TRAILER.pack(_crc32_frame(header, payload, payload2))
               if use_crc else b"")
    with lock:
        if payload2 is not None:
            # Two sendalls, zero concatenation: sendall accepts the
            # (possibly readonly) view directly and loops partial
            # writes itself. The lock spans both, so the frame stays
            # contiguous on the stream.
            sock.sendall(header + payload)
            sock.sendall(payload2)
            if trailer:
                sock.sendall(trailer)
        else:
            sock.sendall(header + payload + trailer)


def _recv_exact(sock: socket.socket, n: int,
                midframe: bool = False, stages=None) -> bytearray:
    """Read exactly ``n`` bytes. Returns the freshly-owned bytearray
    (no defensive copy — the caller is the sole owner, which lets
    decode() alias large payloads zero-copy).

    A ``socket.timeout`` that fires mid-frame — partway through this
    read, or on a later segment of an already-started frame
    (``midframe``) — leaves the stream desynchronized: a retry would
    resume reading from the middle of the frame and decode garbage. It
    is converted to a fatal :class:`ConnectionError` for this peer; only
    a timeout on a clean frame boundary surfaces as ``socket.timeout``
    (the handshake accept/reply deadlines rely on that). EOF likewise:
    on a frame boundary it is an :class:`OrderlyClose`, inside a frame
    a plain :class:`ConnectionError`."""
    from .. import native as _native

    buf = bytearray(n)
    lib = _native.wirecore() if sock.gettimeout() is None else None
    if lib is not None and n:
        import ctypes
        import errno as _errno

        arr = (ctypes.c_ubyte * n).from_buffer(buf)
        progress = ctypes.c_uint64(0)
        while True:
            rc = lib.wc_recv_exact(sock.fileno(), arr, n,
                                   ctypes.byref(progress), stages)
            if rc != -_errno.EINTR:
                break
        if rc == _native.PEER_CLOSED:
            raise (ConnectionError if midframe or progress.value
                   else OrderlyClose)("connection closed by peer")
        if rc != 0:
            import os as _os

            raise OSError(-rc, _os.strerror(-rc))
        return buf
    view = memoryview(buf)
    got = 0
    while got < n:
        try:
            r = sock.recv_into(view[got:], n - got)
        except socket.timeout:
            if got or midframe:
                raise ConnectionError(
                    f"mpi_tpu: socket timeout mid-frame after {got}/{n} "
                    f"bytes; stream desynchronized — connection is "
                    f"unusable") from None
            raise
        if r == 0:
            raise (ConnectionError if midframe or got
                   else OrderlyClose)("connection closed by peer")
        got += r
    return buf


def _recv_frame(sock, crc: bool = False,
                src: int = -1) -> Tuple[int, int, bytearray]:
    """Read one frame; with ``crc`` (the negotiated integrity option)
    DATA frames carry a CRC32 trailer, verified here — a mismatch
    raises :class:`ChecksumError` naming ``src`` and the frame's tag."""
    if isinstance(sock, ShmConn):
        return sock.recv_frame()
    header = _recv_exact(sock, _FRAME_HDR.size)
    kind, tag, length = _FRAME_HDR.unpack(header)
    if length:
        # Native stage scratch for the payload read (the header read is
        # idle-reader wait, not transfer): the resulting
        # ``wire.recv.syscall`` span lands on this reader thread's lane
        # as the recv-side counterpart of ``wire.write.syscall``.
        stages = None
        t0 = 0
        if trace.enabled():
            import ctypes as _ctypes

            stages = (_ctypes.c_uint64 * 3)()
            t0 = time.perf_counter_ns()
        payload = _recv_exact(sock, length, midframe=True, stages=stages)
        if stages is not None and stages[1]:
            trace.add_span("wire.recv.syscall", t0 / 1e3, stages[0] / 1e3,
                           source=src, tag=tag, bytes=int(stages[2]),
                           recv_calls=int(stages[1]))
            trace.count("wire.native.rx.syscall_ns", int(stages[0]))
            trace.count("wire.native.rx.recv_calls", int(stages[1]))
    else:
        payload = bytearray()
    if crc and kind == KIND_DATA:
        trailer = _recv_exact(sock, _CRC_TRAILER.size, midframe=True)
        if trace.enabled():
            t0 = time.perf_counter_ns()
            ok = _CRC_TRAILER.unpack(trailer)[0] == \
                _crc32_frame(bytes(header), payload)
            trace.count("wire.crc.frames")
            trace.count("wire.crc.ns", time.perf_counter_ns() - t0)
            if not ok:
                raise ChecksumError(src, tag)
        elif _CRC_TRAILER.unpack(trailer)[0] != \
                _crc32_frame(bytes(header), payload):
            raise ChecksumError(src, tag)
    return kind, tag, payload


def _shut(sock) -> None:
    """Shut down and close one connection; never raises. Shutdown
    first so a reader thread blocked in recv on it wakes."""
    if sock is None:
        return
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


class _Peer:
    """Connection pair to one peer (``pairwiseConnection``, network.go:499-506)."""

    def __init__(self, peer_rank: int):
        self.rank = peer_rank
        self.dial_sock: Optional[socket.socket] = None   # my sends + their acks
        self.listen_sock: Optional[socket.socket] = None  # their sends + my acks
        self.dial_lock = threading.Lock()
        self.listen_lock = threading.Lock()
        self.sendtags = TagManager("send", peer_rank)
        self.receivetags = TagManager("receive", peer_rank)
        self.reader_threads: List[threading.Thread] = []
        # Negotiated per-connection CRC (both HELLOs advertised crc32).
        self.dial_crc = False
        self.listen_crc = False
        # First failure that killed this peer's connections; set once by
        # _mark_peer_dead (under dead_lock — both readers can die
        # concurrently), after which every op targeting the peer fails
        # fast instead of hanging.
        self.dead: Optional[BaseException] = None
        self.dead_lock = threading.Lock()


class TcpNetwork:
    """The default backend, as ``&Network{}`` is in the reference (mpi.go:56).

    Constructor args mirror the user-settable ``Network`` fields
    (network.go:25-39): ``proto``, ``addr``, ``addrs``, ``timeout``
    (seconds), ``password``. Unset values resolve from the ``-mpi-*``
    flags / ``MPI_TPU_*`` env at :meth:`init` (network.go:69-90)."""

    def __init__(self, proto: Optional[str] = None, addr: Optional[str] = None,
                 addrs: Optional[List[str]] = None,
                 timeout: Optional[float] = None,
                 password: Optional[str] = None,
                 optimeout: Optional[float] = None,
                 crc: Optional[bool] = None,
                 chaos: Optional[str] = None):
        self.proto = proto
        self.addr = addr
        self.addrs = list(addrs) if addrs else []
        self.timeout = timeout
        self.password = password
        # Robustness extensions (docs/FAULT_TOLERANCE.md); unset values
        # resolve from --mpi-optimeout / --mpi-crc / --mpi-chaos at init.
        self.optimeout = optimeout
        self.crc = crc
        # Chaos engine attachment point: a ChaosEngine (or a raw
        # seed:rate:modes spec string, parsed at init). The send path
        # consults it per operation; None = fault-free (the default).
        self._chaos = chaos

        self._rank: Optional[int] = None
        self._size: Optional[int] = None
        self._peers: Dict[int, _Peer] = {}
        self._local: Optional[Rendezvous] = None
        self._listener: Optional[socket.socket] = None
        self._closed = threading.Event()
        self._initialized = False

    # -- Interface ----------------------------------------------------------

    def rank(self) -> int:
        if self._rank is None:
            raise MpiError("mpi_tpu: rank() before init()")
        return self._rank

    def size(self) -> int:
        if self._size is None:
            raise MpiError("mpi_tpu: size() before init()")
        return self._size

    def host_key(self) -> str:
        """Machine identity for ``Comm.split_type("host")``: the host part
        of this rank's address (textual match — localhost spellings
        collapse to one key; unix-domain sockets are single-machine)."""
        if self.addr is None:
            raise MpiError("mpi_tpu: host_key() before init()")
        if self.proto in ("unix", "shm"):
            return self.proto
        host, _, _ = self.addr.rpartition(":")
        host = host.lower()
        return "127.0.0.1" if host in ("", "localhost", "::1", "[::1]") \
            else host

    def init(self) -> None:
        """Resolve config, assign ranks, build the all-to-all mesh
        (network.go:53-65)."""
        if self._initialized:
            raise MpiError("mpi_tpu: init() called twice")
        self._use_flags()
        if not self.addrs:
            # Single-node default (network.go:55-58).
            self.addr = self.addr or ":5000"
            self.addrs = [self.addr]
        self._assign_ranks()
        self._local = Rendezvous(self._rank, self._rank)
        self._start_connections()
        self._initialized = True

    def finalize(self) -> None:
        """Close every connection (network.go:354-369).

        Safe to call twice and after a failed ``init()`` (the second
        call is a no-op; a bootstrap-failure call sees whatever partial
        state exists) — so error-path cleanup in tests and the chaos
        harness can ``finalize()`` unconditionally."""
        if self._closed.is_set():
            return
        self._closed.set()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
            if self._is_unix() and self.addr:
                try:
                    os.unlink(self.addr)
                except OSError:
                    pass
        for peer in self._peers.values():
            _shut(peer.dial_sock)
            _shut(peer.listen_sock)
        for peer in self._peers.values():
            for t in peer.reader_threads:
                t.join(timeout=2.0)
        # shm conns unmap only now: their reader threads dereference the
        # mapping inside native calls, so release must follow the joins
        # (and is skipped for a reader that refused to die).
        for peer in self._peers.values():
            if any(t.is_alive() for t in peer.reader_threads):
                continue
            for sock in (peer.dial_sock, peer.listen_sock):
                if isinstance(sock, ShmConn):
                    sock.release()
        self._initialized = False

    def send(self, data: Any, dest: int, tag: int) -> None:
        """Rendezvous send (network.go:518-572): encode, frame, block on ack.

        Large contiguous arrays/bytes take the scatter-gather path
        (``encode_parts``): the type prefix and the caller's buffer
        leave as one frame with no tobytes/concat copy — measured ~2x
        on 64 MiB one-way sends, where the two encode copies cost 81 ms
        of a 155 ms transfer.

        With ``--mpi-optimeout`` the ack wait is bounded: a vanished
        receiver raises :class:`DeadlineError` instead of blocking
        forever. Under ``--mpi-chaos`` the engine may sleep here (delay
        modes) or hand back a wire fault applied to this frame."""
        self._check_rank(dest)
        fault = (self._chaos.on_op("send", dest, tag,
                                   wire=dest != self._rank)
                 if self._chaos is not None else None)
        if dest == self._rank:
            # Self path: no tag manager involvement needed beyond the local
            # rendezvous's own misuse detection — and unlike the reference
            # we do not leak the tag (defect (a), SURVEY.md §2). The
            # deadline covers it like the remote ack wait.
            self._local.send(tag, codec_encode(data),
                             timeout=self.optimeout,
                             op=f"send(dest={dest}, tag={tag}) self "
                                f"rendezvous")
            return
        # Per-stage wire spans + per-peer byte counters (observe layer):
        # frame assembly / socket write / ack wait are separately
        # attributable — the decomposition the transport-rewrite work
        # targets (docs/PERF_NOTES.md). One bool check when tracing off.
        tracing = trace.enabled()
        if tracing:
            with trace.span("wire.encode", dest=dest, tag=tag):
                prefix, view = codec_encode_parts(data)
            nbytes = len(prefix) + (0 if view is None
                                    else memoryview(view).nbytes)
            trace.count("wire.tx.frames")
            trace.count(f"wire.{self.proto}.tx.bytes.peer{dest}", nbytes)
        else:
            prefix, view = codec_encode_parts(data)
        peer = self._peers[dest]
        ackq, gen = peer.sendtags.claim(tag)
        try:
            try:
                if tracing:
                    # Native stage scratch: when _send_frame takes the
                    # wirecore path it accumulates per-stage ns here,
                    # which become child spans under wire.write — the
                    # named microseconds the transport rewrite needs
                    # (docs/PERF_NOTES.md).
                    import ctypes as _ctypes

                    stages = (_ctypes.c_uint64 * 4)()
                    with trace.span("wire.write", dest=dest, tag=tag,
                                    bytes=nbytes, crc=peer.dial_crc):
                        t0w = time.perf_counter_ns()
                        _send_frame(peer.dial_sock, peer.dial_lock,
                                    KIND_DATA, tag, prefix, view,
                                    crc=peer.dial_crc, fault=fault,
                                    stages=stages)
                    if stages[2]:
                        asm_us = stages[0] / 1e3
                        trace.add_span("wire.write.assemble", t0w / 1e3,
                                       asm_us, dest=dest, tag=tag)
                        trace.add_span("wire.write.syscall",
                                       t0w / 1e3 + asm_us,
                                       stages[1] / 1e3, dest=dest,
                                       tag=tag, bytes=int(stages[3]),
                                       writev_calls=int(stages[2]))
                        trace.count("wire.native.tx.syscall_ns",
                                    int(stages[1]))
                        trace.count("wire.native.tx.writev_calls",
                                    int(stages[2]))
                else:
                    _send_frame(peer.dial_sock, peer.dial_lock, KIND_DATA,
                                tag, prefix, view, crc=peer.dial_crc,
                                fault=fault)
            except OSError as exc:
                # The conn died under us (peer crashed; chaos reset by a
                # sibling thread) before the reader poisoned the tags —
                # surface the typed peer-death error, not a raw EBADF.
                raise (peer.dead if peer.dead is not None
                       else PeerDeadError(peer.rank, exc)) from exc
            # Blocks until the receiver's ack (network.go:569).
            if tracing:
                with trace.span("wire.ack_wait", dest=dest, tag=tag):
                    peer.sendtags.wait(
                        ackq, gen, timeout=self.optimeout,
                        op=f"send(dest={dest}, tag={tag}) ack wait")
            else:
                peer.sendtags.wait(ackq, gen, timeout=self.optimeout,
                                   op=f"send(dest={dest}, tag={tag}) "
                                      f"ack wait")
        finally:
            peer.sendtags.release(tag)

    def receive(self, source: int, tag: int, out: Optional[Any] = None) -> Any:
        """Blocking receive (network.go:575-602): dequeue payload, ack, decode.

        With ``--mpi-optimeout`` the payload wait is bounded: a sender
        that never arrives (peer wedged or dead without a detectable
        connection loss) raises :class:`DeadlineError`. The deadline
        also covers the decode phase: decode is uninterruptible
        Python/numpy work, so it runs to completion, but if the
        operation as a whole then exceeds the deadline the receive
        raises :class:`DeadlineError` instead of returning late data
        (docs/FAULT_TOLERANCE.md)."""
        self._check_rank(source)
        if self._chaos is not None:
            self._chaos.on_op("receive", source, tag)
        # Op-elapsed origin for the decode-phase deadline check. Taken
        # AFTER the chaos hook: injected pre-op latency has always been
        # outside the deadline and must stay there.
        t0_op = time.monotonic() if self.optimeout is not None else 0.0
        if source == self._rank:
            payload = self._local.receive(
                tag, timeout=self.optimeout,
                op=f"receive(source={source}, tag={tag}) self rendezvous")
            data = codec_decode(payload, out=out)
            self._check_decode_deadline(t0_op, source, tag)
            return data
        peer = self._peers[source]
        slot, gen = peer.receivetags.claim(tag)
        tracing = trace.enabled()
        try:
            if tracing:
                with trace.span("wire.payload_wait", source=source,
                                tag=tag):
                    payload = peer.receivetags.wait(
                        slot, gen, timeout=self.optimeout,
                        op=f"receive(source={source}, tag={tag})")
            else:
                payload = peer.receivetags.wait(
                    slot, gen, timeout=self.optimeout,
                    op=f"receive(source={source}, tag={tag})")
            # Ack on the listen conn — this is what unblocks the sender's
            # rendezvous (network.go:617-624); written only now, when the
            # receive has genuinely accepted the data. A failed ack write
            # means the sender died AFTER transmitting: the payload is
            # fully in hand and the ack has no one left to unblock —
            # deliver the data rather than discard a completed receive.
            try:
                _send_frame(peer.listen_sock, peer.listen_lock, KIND_ACK,
                            tag)
            except OSError:
                pass
        finally:
            peer.receivetags.release(tag)
        if tracing:
            trace.count(f"wire.{self.proto}.rx.bytes.peer{source}",
                        len(payload))
            with trace.span("wire.decode", source=source, tag=tag,
                            bytes=len(payload)):
                data = codec_decode(payload, out=out)
        else:
            data = codec_decode(payload, out=out)
        self._check_decode_deadline(t0_op, source, tag)
        return data

    def _check_decode_deadline(self, t0_op: float, source: int,
                               tag: int) -> None:
        """Deadline coverage for the decode phase: a giant payload
        whose decode outlives ``--mpi-optimeout`` used to complete
        anyway (the known gap in docs/FAULT_TOLERANCE.md). The decode
        itself cannot be interrupted mid-way, so the check runs at its
        completion — the op fails with the same typed error the wait
        phases raise, rather than silently returning after the
        deadline. The ack has already been written by this point, so
        the sender correctly sees its rendezvous complete; deadline
        semantics have always been indeterminate-at-the-boundary
        (docs/FAULT_TOLERANCE.md §--mpi-optimeout)."""
        if self.optimeout is not None and \
                time.monotonic() - t0_op > self.optimeout:
            raise DeadlineError(
                f"receive(source={source}, tag={tag}) decode",
                self.optimeout)

    def notify_abort(self, code: int) -> None:
        """Failure propagation for ``api.abort()``: best-effort ABORT
        control frame to every live peer on both connections, so remote
        ranks raise :class:`RemoteAbortError` on their pending and
        future operations instead of discovering the death by timeout.
        Never raises — the caller is about to ``os._exit``."""
        if not self._initialized:
            return
        for peer in self._peers.values():
            if peer.dead is not None:
                continue
            for sock, lock in ((peer.dial_sock, peer.dial_lock),
                               (peer.listen_sock, peer.listen_lock)):
                if sock is None:
                    continue
                try:
                    if isinstance(sock, ShmConn):
                        _send_frame(sock, lock, KIND_ABORT, code)
                        continue
                    # Timed lock: a sibling thread wedged mid-sendall to
                    # this (possibly dead) peer must not stall the abort.
                    # If the lock can't be had, write anyway — worst
                    # case the interleaved bytes desync the stream and
                    # the peer sees a connection error, which also ends
                    # its pending ops.
                    acquired = lock.acquire(timeout=0.5)
                    try:
                        sock.sendall(_FRAME_HDR.pack(KIND_ABORT, code, 0))
                    finally:
                        if acquired:
                            lock.release()
                except Exception:  # noqa: BLE001 - dying anyway
                    pass

    def cancel_receive(self, source: int, tag: int) -> bool:
        """Best-effort cancellation of a pending receive (no reference
        analogue; supports :func:`mpi_tpu.api.exchange` cleanup). Returns
        False when the receive already completed or cannot be cancelled
        (self-receives with a sender already engaged)."""
        self._check_rank(source)
        exc = ReceiveCancelled(
            f"mpi_tpu: receive(source={source}, tag={tag}) cancelled")
        if source == self._rank:
            return self._local.cancel(tag, exc)
        return self._peers[source].receivetags.cancel(tag, exc)

    def iprobe(self, source: int, tag: int) -> bool:
        """Non-consuming MPI_Iprobe: True when a message from ``source``
        with ``tag`` is already available — its data frame arrived (the
        sender is blocked awaiting the rendezvous ack), or a self-send
        is parked at the local rendezvous."""
        self._check_rank(source)
        if source == self._rank:
            return self._local.probe(tag)
        return self._peers[source].receivetags.has_message(tag)

    # -- bootstrap ----------------------------------------------------------

    def _hello_payload(self) -> bytes:
        """HELLO body: the password, plus this side's advertised features
        when any are enabled. A feature-less HELLO is byte-identical to
        the pre-negotiation wire format, so with the flag off mixed
        versions interoperate transparently; mixed *configs* (crc on one
        side only) negotiate the feature off. Caveat: a peer predating
        feature negotiation entirely sees an advertising HELLO as a
        password mismatch — enable ``--mpi-crc`` only when every rank
        runs a feature-aware build."""
        pw = self.password.encode("utf-8")
        if self.crc:
            return pw + _FEATURE_SEP + _FEATURE_CRC.encode("ascii")
        return pw

    @staticmethod
    def _parse_hello(payload) -> Tuple[str, set]:
        """Split a HELLO body into (password, advertised feature set)."""
        raw = bytes(payload)
        if _FEATURE_SEP in raw:
            pw, _, feats = raw.partition(_FEATURE_SEP)
            return (pw.decode("utf-8"),
                    {f for f in feats.decode("utf-8").split(",") if f})
        return raw.decode("utf-8"), set()

    def _is_unix(self) -> bool:
        return self.proto == "unix"

    def _is_shm(self) -> bool:
        return self.proto == "shm"

    def _tune(self, sock: socket.socket) -> None:
        """Latency tuning where applicable (TCP only)."""
        if self.proto in ("tcp", "tcp4", "tcp6"):
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _use_flags(self) -> None:
        """Explicit fields win over flags/env (network.go:69-90)."""
        fl = flagmod.get_flags()
        if self.proto is None:
            self.proto = fl.protocol or flagmod.DEFAULT_PROTOCOL
        if self.proto not in _SUPPORTED_PROTOS:
            raise InitError(
                f"mpi_tpu: unsupported -mpi-protocol {self.proto!r}; "
                f"supported: {', '.join(_SUPPORTED_PROTOS)}")
        if self.addr is None and fl.addr:
            self.addr = fl.addr
        if not self.addrs and fl.alladdr:
            self.addrs = list(fl.alladdr)
        if self.timeout is None:
            self.timeout = (fl.inittimeout if fl.inittimeout is not None
                            else flagmod.DEFAULT_INIT_TIMEOUT)
        if self.password is None:
            self.password = fl.password or ""
        if "\x00" in self.password:
            raise InitError("mpi_tpu: password must not contain NUL "
                            "bytes (reserved for HELLO feature "
                            "negotiation)")
        if self.optimeout is None:
            self.optimeout = fl.optimeout  # None = no deadline (default)
        if self.crc is None:
            self.crc = bool(fl.crc)
        # CRC protects byte streams; shm rings are process memory and
        # frame in the native engine — integrity there is a follow-on.
        if self._is_shm():
            self.crc = False
        if self._chaos is None and fl.chaos:
            self._chaos = fl.chaos
        if isinstance(self._chaos, str):
            from ..chaos import ChaosEngine, parse_chaos

            self._chaos = ChaosEngine(parse_chaos(self._chaos))

    def _assign_ranks(self) -> None:
        """Sorted-address consensus (network.go:94-118)."""
        if self.addr is None:
            if len(self.addrs) == 1:
                self.addr = self.addrs[0]
            else:
                raise InitError("mpi_tpu: own address unset with multiple addrs")
        ordered = sorted(self.addrs)
        for a, b in zip(ordered, ordered[1:]):
            if a == b:
                raise InitError(f"mpi_tpu: duplicate address {a!r} in addrs")
        try:
            self._rank = ordered.index(self.addr)
        except ValueError:
            raise InitError(
                f"mpi_tpu: own address {self.addr!r} not in addrs {ordered}") from None
        self._size = len(ordered)
        self.addrs = ordered

    def _start_connections(self) -> None:
        """Concurrent listen-side + dial-side all-to-all handshakes
        (network.go:122-159)."""
        n = self._size
        me = self._rank
        for r in range(n):
            if r != me:
                self._peers[r] = _Peer(r)
        if n == 1:
            return

        errors: List[str] = []
        err_lock = threading.Lock()

        def note(err: str) -> None:
            with err_lock:
                errors.append(err)

        if self._is_shm():
            self._shm_bootstrap(note)
        else:
            self._socket_bootstrap(note)

        if not errors:
            for peer in self._peers.values():
                if peer.dial_sock is None:
                    errors.append(f"rank {me}: no dial conn to {peer.rank}")
                if peer.listen_sock is None:
                    errors.append(f"rank {me}: no listen conn from {peer.rank}")
        if errors:
            self.finalize()
            raise InitError("; ".join(sorted(set(errors))))

        # Persistent readers (replace per-call goroutines; see module doc).
        for peer in self._peers.values():
            t1 = threading.Thread(target=self._dial_reader, args=(peer,),
                                  name=f"mpi-ackreader-{peer.rank}", daemon=True)
            t2 = threading.Thread(target=self._listen_reader, args=(peer,),
                                  name=f"mpi-datareader-{peer.rank}", daemon=True)
            peer.reader_threads = [t1, t2]
            t1.start()
            t2.start()

    def _socket_bootstrap(self, note) -> None:
        """TCP/unix all-to-all bootstrap: listen + dial handshakes
        (network.go:122-351). Populates peer dial/listen conns; errors
        go through ``note`` for aggregation."""
        n, me = self._size, self._rank
        # Listen side: accept n-1 peers, each validated by handshake.
        if self._is_unix():
            listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            try:
                # Clear a stale socket file from a crashed previous run;
                # a *live* conflicting listener still fails below, as the
                # reference's bind would.
                os.unlink(self.addr)
            except OSError:
                pass
            try:
                listener.bind(self.addr)
            except OSError as exc:
                raise InitError(
                    f"mpi_tpu: cannot listen on {self.addr!r}: {exc}"
                ) from exc
        else:
            host, port = _split_hostport(self.addr)
            family = (socket.AF_INET6 if self.proto == "tcp6"
                      else socket.AF_INET)
            listener = socket.socket(family, socket.SOCK_STREAM)
            listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                listener.bind((host, port))
            except OSError as exc:
                raise InitError(
                    f"mpi_tpu: cannot listen on {self.addr!r}: {exc}"
                ) from exc
        listener.listen(n)
        listener.settimeout(self.timeout)  # accept timeout (network.go:223-234)
        self._listener = listener

        accepted = threading.Semaphore(0)

        def listen_side() -> None:
            pending = n - 1
            while pending > 0:
                try:
                    conn, _ = listener.accept()
                except (socket.timeout, OSError) as exc:
                    note(f"rank {me}: accept failed/timed out: {exc}")
                    for _ in range(pending):
                        accepted.release()
                    return
                threading.Thread(target=listen_handshake, args=(conn,),
                                 daemon=True).start()
                pending -= 1

        def listen_handshake(conn: socket.socket) -> None:
            """network.go:211-263: read peer hello, validate, reply."""
            try:
                conn.settimeout(self.timeout)
                self._tune(conn)
                kind, claimed_id, payload = _recv_frame(conn)
                if kind != KIND_HELLO:
                    raise InitError(f"expected HELLO, got frame kind {kind}")
                their_pw, their_feats = self._parse_hello(payload)
                if their_pw != self.password:
                    raise InitError("password mismatch")  # network.go:344-347
                if not 0 <= claimed_id < n or claimed_id == me:
                    raise InitError(f"bad peer id {claimed_id}")  # network.go:348-350
                lock = threading.Lock()
                _send_frame(conn, lock, KIND_HELLO, me,
                            self._hello_payload())
                conn.settimeout(None)
                peer = self._peers[claimed_id]
                peer.listen_crc = bool(self.crc) and \
                    _FEATURE_CRC in their_feats
                peer.listen_sock = conn
                peer.listen_lock = lock
            except Exception as exc:  # noqa: BLE001 - aggregated, init fails
                note(f"rank {me}: listen handshake failed: {exc}")
                try:
                    conn.close()
                except OSError:
                    pass
            finally:
                accepted.release()

        def dial_handshake(peer_rank: int) -> None:
            """network.go:297-339: retry-dial peer, send hello, validate reply."""
            target = self.addrs[peer_rank]
            if not self._is_unix():
                target_host, target_port = _split_hostport(target)
            deadline = time.monotonic() + self.timeout
            sock: Optional[socket.socket] = None
            while True:
                try:
                    if self._is_unix():
                        sock = socket.socket(socket.AF_UNIX,
                                             socket.SOCK_STREAM)
                        sock.settimeout(self.timeout)
                        sock.connect(target)
                    else:
                        default_host = ("::1" if self.proto == "tcp6"
                                        else "localhost")
                        sock = socket.create_connection(
                            (target_host or default_host, target_port),
                            timeout=self.timeout)
                    break
                except OSError as exc:
                    if sock is not None:
                        try:
                            sock.close()
                        except OSError:
                            pass
                        sock = None
                    if time.monotonic() >= deadline:
                        note(f"rank {me}: dial {target!r} "
                             f"timed out: {exc}")
                        return
                    time.sleep(_DIAL_RETRY_INTERVAL)
            try:
                self._tune(sock)
                lock = threading.Lock()
                _send_frame(sock, lock, KIND_HELLO, me,
                            self._hello_payload())
                sock.settimeout(self.timeout)
                kind, their_id, payload = _recv_frame(sock)
                if kind != KIND_HELLO:
                    raise InitError(f"expected HELLO reply, got kind {kind}")
                their_pw, their_feats = self._parse_hello(payload)
                if their_pw != self.password:
                    raise InitError("password mismatch in reply")
                if their_id != peer_rank:
                    raise InitError(
                        f"dialed rank {peer_rank} but peer claims {their_id}")
                sock.settimeout(None)
                peer = self._peers[peer_rank]
                peer.dial_crc = bool(self.crc) and \
                    _FEATURE_CRC in their_feats
                peer.dial_sock = sock
                peer.dial_lock = lock
            except Exception as exc:  # noqa: BLE001
                note(f"rank {me}: dial handshake with rank {peer_rank} "
                     f"failed: {exc}")
                try:
                    sock.close()
                except OSError:
                    pass

        lt = threading.Thread(target=listen_side, daemon=True)
        lt.start()
        dial_threads = [threading.Thread(target=dial_handshake, args=(r,),
                                         daemon=True)
                        for r in range(n) if r != me]
        for t in dial_threads:
            t.start()
        for t in dial_threads:
            t.join()
        lt.join()
        for _ in range(n - 1):
            accepted.acquire()

    def _shm_bootstrap(self, note) -> None:
        """All-to-all bootstrap over shared-memory rings (proto ``shm``).

        Same shape as the socket bootstrap: for conn ``a -> me`` the
        listen side *creates* the ring pair and validates the dialer's
        HELLO; the dial side *attaches* with the 100 ms retry loop until
        the init timeout and validates the reply (network.go:198-263,
        294-351). The session-keyed ring names are themselves the
        rendezvous points, so there is no listener socket; a stale ring
        from a crashed run is unlinked at create time, like the unix
        bootstrap's stale socket file. HELLO still carries the password
        and claimed rank for reference parity, though the key already
        binds both (backends/shm.py module doc)."""
        from .shm import (attach_ring, create_ring, ring_capacity,
                          ring_name, session_key)

        n, me = self._size, self._rank
        key = session_key(self.addrs, self.password)
        cap = ring_capacity()

        def listen_handshake(peer_rank: int) -> None:
            names = (ring_name(key, peer_rank, me, "d"),
                     ring_name(key, peer_rank, me, "r"))
            conn: Optional[ShmConn] = None
            rx = tx = None
            try:
                rx = create_ring(names[0], cap)   # dialer's frames to me
                tx = create_ring(names[1], cap)   # my replies out
                conn = ShmConn(tx, rx, owned_names=names)
                conn.settimeout(self.timeout)
                kind, claimed_id, payload = _recv_frame(conn)
                if kind != KIND_HELLO:
                    raise InitError(f"expected HELLO, got frame kind {kind}")
                if self._parse_hello(payload)[0] != self.password:
                    raise InitError("password mismatch")  # network.go:344-347
                if claimed_id != peer_rank:
                    raise InitError(
                        f"ring pair for rank {peer_rank} got HELLO "
                        f"claiming rank {claimed_id}")
                lock = threading.Lock()
                _send_frame(conn, lock, KIND_HELLO, me,
                            self.password.encode("utf-8"))
                conn.settimeout(None)
                peer = self._peers[peer_rank]
                peer.listen_sock = conn
                peer.listen_lock = lock
            except Exception as exc:  # noqa: BLE001 - aggregated, init fails
                note(f"rank {me}: shm listen handshake with rank "
                     f"{peer_rank} failed: {exc}")
                if conn is not None:
                    conn.close()
                    conn.release()  # no reader threads exist yet
                else:
                    # Partial creation: close and unlink whatever ring
                    # exists, or the named /dev/shm object outlives the
                    # process (POSIX shm survives exit).
                    from .shm import unlink_ring
                    for ring in (rx, tx):
                        if ring is not None:
                            ring.mark_closed()
                            ring.close()
                    for name in names:
                        unlink_ring(name)

        def dial_handshake(peer_rank: int) -> None:
            names = (ring_name(key, me, peer_rank, "d"),
                     ring_name(key, me, peer_rank, "r"))
            deadline = time.monotonic() + self.timeout
            tx = rx = None
            try:
                while tx is None or rx is None:
                    if tx is None:
                        tx = attach_ring(names[0])
                    if tx is not None and rx is None:
                        rx = attach_ring(names[1])
                    if tx is not None and rx is not None:
                        break
                    if time.monotonic() >= deadline:
                        raise InitError("timed out waiting for rings")
                    time.sleep(_DIAL_RETRY_INTERVAL)
            except Exception as exc:  # noqa: BLE001 - aggregated, init fails
                # Route unexpected attach errors (EACCES on a stale
                # ring, ...) through note() like every other handshake
                # path, instead of dying silently in the thread.
                note(f"rank {me}: shm dial to rank {peer_rank} "
                     f"failed: {exc}")
                for ring in (tx, rx):
                    if ring is not None:
                        ring.close()
                return
            conn = ShmConn(tx, rx)  # listener owns/unlinks the names
            try:
                # Timeout BEFORE the HELLO send (as the listen side does):
                # a nearly-full stale ring attached in the unlink/recreate
                # window would otherwise block the write forever and hang
                # init past its deadline.
                conn.settimeout(self.timeout)
                lock = threading.Lock()
                _send_frame(conn, lock, KIND_HELLO, me,
                            self.password.encode("utf-8"))
                kind, their_id, payload = _recv_frame(conn)
                if kind != KIND_HELLO:
                    raise InitError(f"expected HELLO reply, got kind {kind}")
                if self._parse_hello(payload)[0] != self.password:
                    raise InitError("password mismatch in reply")
                if their_id != peer_rank:
                    raise InitError(
                        f"dialed rank {peer_rank} but peer claims {their_id}")
                conn.settimeout(None)
                peer = self._peers[peer_rank]
                peer.dial_sock = conn
                peer.dial_lock = lock
            except Exception as exc:  # noqa: BLE001
                note(f"rank {me}: shm dial handshake with rank {peer_rank} "
                     f"failed: {exc}")
                conn.close()
                conn.release()  # no reader threads exist yet

        threads = [threading.Thread(target=listen_handshake, args=(r,),
                                    daemon=True)
                   for r in range(n) if r != me]
        threads += [threading.Thread(target=dial_handshake, args=(r,),
                                     daemon=True)
                    for r in range(n) if r != me]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    # -- data path ----------------------------------------------------------

    def _dial_reader(self, peer: _Peer) -> None:
        """Reads the peer's acks off my dial conn → unblocks my sends
        (the ack-reader goroutine of network.go:551-559)."""
        try:
            while not self._closed.is_set():
                kind, tag, _ = _recv_frame(peer.dial_sock)
                if kind == KIND_ABORT:
                    raise RemoteAbortError(peer.rank, tag)
                if kind != KIND_ACK:
                    raise MpiError(f"unexpected frame kind {kind} on dial conn")
                peer.sendtags.route(tag, True)
        except RemoteAbortError as exc:
            self._mark_job_aborted(exc)
        except OrderlyClose as exc:
            self._mark_conn_closed(peer, peer.sendtags, peer.dial_sock, exc)
        except (ConnectionError, OSError, MpiError) as exc:
            self._mark_peer_dead(peer, exc)

    def _listen_reader(self, peer: _Peer) -> None:
        """Reads the peer's data frames off my listen conn → routes by tag
        (``receiveReader``, network.go:607-625; ack deferred to receive())."""
        try:
            while not self._closed.is_set():
                kind, tag, payload = _recv_frame(peer.listen_sock,
                                                 crc=peer.listen_crc,
                                                 src=peer.rank)
                if kind == KIND_ABORT:
                    raise RemoteAbortError(peer.rank, tag)
                if kind != KIND_DATA:
                    raise MpiError(f"unexpected frame kind {kind} on listen conn")
                peer.receivetags.route(tag, payload)
        except RemoteAbortError as exc:
            self._mark_job_aborted(exc)
        except ChecksumError as exc:
            # Deliver the integrity failure to the receive it damages
            # first (so that call raises the attributable ERR_TRUNCATE
            # error), then retire the connection — after corruption the
            # framing cannot be trusted. Other pending/future ops on
            # this peer see peer-death (ERR_PENDING), not a ChecksumError
            # naming another operation's tag.
            peer.receivetags.route(exc.tag, exc)
            self._mark_peer_dead(peer, PeerDeadError(peer.rank, exc))
        except OrderlyClose as exc:
            self._mark_conn_closed(peer, peer.receivetags, peer.listen_sock,
                                   exc)
        except (ConnectionError, OSError, MpiError) as exc:
            self._mark_peer_dead(peer, exc)

    def _mark_job_aborted(self, exc: "RemoteAbortError") -> None:
        """A remote rank aborted: the whole job is over, not just one
        link — every peer's pending and future operations raise the
        abort error (MPI_Abort terminates the communicator, not an
        edge). Under ``mpirun`` the launcher reaps this process moments
        later; in-process harnesses see the typed error instead."""
        for p in self._peers.values():
            self._mark_peer_dead(p, exc)

    def _mark_peer_dead(self, peer: _Peer, exc: BaseException) -> None:
        """On connection loss (either direction's reader died of anything
        but an orderly close, :meth:`_mark_conn_closed`) the whole peer
        is dead: fail all pending *and future* ops targeting it
        instead of hanging (replaces the reference's reader panics,
        network.go:555,611). Ops already blocked get the exception via
        their slot; ops issued after the loss fail at claim(). Raw
        socket errors are wrapped in :class:`PeerDeadError` so callers
        always see a typed, classifiable MpiError."""
        exc = self._first_cause(peer, exc)
        peer.sendtags.poison(exc)
        peer.receivetags.poison(exc)
        # Drop both connections: the PEER's readers then observe EOF and
        # mark us dead too, so its blocked ops (e.g. the ack wait of the
        # send whose frame failed our CRC check) fail fast instead of
        # hanging until a deadline that may not be configured. During
        # finalize the sockets are being closed anyway. The sibling
        # reader of this conn pair wakes with a ConnectionError and
        # re-enters here idempotently.
        if not self._closed.is_set():
            _shut(peer.dial_sock)
            _shut(peer.listen_sock)

    def _mark_conn_closed(self, peer: _Peer, tags: TagManager, sock,
                          exc: OrderlyClose) -> None:
        """The reader of ``sock`` met EOF on a frame boundary: fail the
        pending and future ops of ITS direction only. A peer that
        finalizes (or dies) closes both connections, so the sibling
        reader gets here on its own moments later — after it has routed
        whatever the peer wrote ahead of the EOF. That order is the
        point: the ack of a message the peer received just before it
        finalized sits in the dial conn, and a listen reader that wins
        the race to EOF must neither poison ``sendtags`` nor close the
        dial socket over it (docs/FAULT_TOLERANCE.md)."""
        tags.poison(self._first_cause(peer, exc))
        if not self._closed.is_set():
            _shut(sock)

    def _first_cause(self, peer: _Peer, exc: BaseException) -> BaseException:
        """Poison with the FIRST cause of death: the sibling reader
        dying of this one's close must not rebrand the failure."""
        if self._closed.is_set():
            exc = MpiError("mpi_tpu: network finalized")
        elif not isinstance(exc, MpiError):
            exc = PeerDeadError(peer.rank, exc)
        with peer.dead_lock:
            if peer.dead is None:
                peer.dead = exc
            return peer.dead

    def _check_rank(self, r: int) -> None:
        if self._size is None:
            raise MpiError("mpi_tpu: send/receive before init()")
        if not 0 <= r < self._size:
            raise MpiError(f"mpi_tpu: peer rank {r} out of range [0, {self._size})")
