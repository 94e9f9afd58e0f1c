"""Shared-memory transport engine — the ``shm`` protocol's data plane.

The reference's ``NetProto`` field accepts any ``net``-package protocol
(/root/reference/network.go:26); ranks on one machine still pay the full
TCP stack. This engine is the rebuild's native answer for that case:
``-mpi-protocol shm`` keeps the driver's semantics (same frame stream,
same handshake, same rendezvous acks — backends/tcp.py) but carries the
frames through single-producer/single-consumer byte rings in POSIX
shared memory, implemented in C++ (native/shmcore.cpp) with futex
blocking and a spin fast path. Payloads larger than a ring stream
through it chunk-by-chunk (the reader drains while the writer fills),
so ring capacity bounds memory, not message size.

Addressing: with ``shm`` the ``-mpi-addr``/``-mpi-alladdr`` values are
arbitrary unique identifiers (they never hit the network); rank
assignment is still the sorted-address consensus (network.go:94-109).
Ring names are derived from a session key — a hash of the sorted
address list and the password — so concurrent shm worlds on one machine
cannot collide, and a wrong-password dialer simply finds no rings (the
HELLO password check still runs for defense in depth and reference
parity, network.go:343-351).

Topology per ordered rank pair ``a -> b`` (the conn ``a`` dials):

    ring "<key>-<a>to<b>-d"   a's frames to b   (created by b, the listener)
    ring "<key>-<a>to<b>-r"   b's frames to a   (created by b)

Each :class:`ShmConn` wraps one such ring pair; the TCP driver stores
it where a socket would go (``peer.dial_sock`` / ``peer.listen_sock``)
and the frame helpers dispatch on the type.

A pure-Python fallback ring (:class:`_PyRing`) speaks the identical
memory layout via ``mmap`` with sleep-polling, used when the native
library is unavailable (``MPI_TPU_NO_NATIVE=1``, no compiler). The
native side's futex waits are bounded (2 ms) precisely so a Python
peer — which never issues futex wakes — costs at most that latency,
never a hang. Mixing native and fallback processes in one world is
supported **on x86-64 only**: the fallback publishes head/tail with
plain mmap stores, which x86's total-store-order makes visible after
the preceding payload bytes, but a weakly-ordered CPU (aarch64) could
reorder them and a *native* peer might then read a torn frame. On
non-x86 hosts run the world all-native or all-fallback (homogeneous
installs do this naturally; the fallback-vs-fallback pairing is safe
everywhere because both sides poll whole values).
"""

from __future__ import annotations

import ctypes
import errno as _errno
import hashlib
import mmap
import os
import socket
import struct
import time
from typing import List, Optional, Tuple, Union

from ..api import MpiError
from .. import native as _native

__all__ = ["ShmConn", "OrderlyClose", "ring_name", "session_key",
           "create_ring", "attach_ring", "unlink_ring", "DEFAULT_RING_BYTES"]


class OrderlyClose(ConnectionError):
    """EOF met on a frame boundary: the peer shut this connection with
    no frame in flight — what an orderly ``finalize()`` looks like from
    the other end. Everything the peer wrote before it is still
    readable ahead of it, so the reader that meets it answers for its
    own direction only (backends/tcp.py ``_mark_conn_closed``). An EOF
    inside a frame stays a plain :class:`ConnectionError`."""


DEFAULT_RING_BYTES = 1 << 20

_FRAME_HDR = struct.Struct("<BqI")

# Mirror of native/shmcore.cpp RingHdr field offsets (alignas(64)):
_OFF_MAGIC = 0       # u32
_OFF_CAPACITY = 4    # u32
_OFF_READY = 8       # u32
_OFF_CLOSED = 12     # u32
_OFF_HEAD = 64       # u64 bytes produced
_OFF_WSEQ = 72       # u32 producer progress counter
_OFF_TAIL = 128      # u64 bytes consumed
_OFF_RSEQ = 136      # u32 consumer progress counter
_HDR_BYTES = 4096
_MAGIC = 0x524D4853

_POLL_S = 50e-6      # fallback ring sleep-poll interval


def session_key(addrs: List[str], password: str) -> str:
    """16-hex-char key shared by all ranks of one world (the sorted
    address list is the world's identity, network.go:94-109; the
    password folds in so a mismatched world cannot attach)."""
    h = hashlib.sha256()
    h.update("\x00".join(sorted(addrs)).encode())
    h.update(b"\x01")
    h.update(password.encode())
    return h.hexdigest()[:16]


def ring_name(key: str, src: int, dst: int, role: str) -> str:
    """POSIX shm object name for one ring of conn ``src -> dst``.
    ``role``: ``"d"`` = dialer's frames, ``"r"`` = listener's replies."""
    return f"/mpitpu-{key}-{src}to{dst}{role}"


def ring_capacity() -> int:
    try:
        return max(1 << 12, int(os.environ.get("MPI_TPU_SHM_RING_BYTES",
                                               DEFAULT_RING_BYTES)))
    except ValueError:
        return DEFAULT_RING_BYTES


# --------------------------------------------------------------------------
# Pure-Python fallback ring (same layout; sleep-polling instead of futex)
# --------------------------------------------------------------------------

class _PyRing:
    """One ring endpoint over ``mmap`` — byte-compatible with the native
    engine. u64 counters are written as single aligned 8-byte stores
    (atomic on every platform CPython runs on in practice); the seq
    words are bumped so a *native* peer's bounded futex wait re-checks
    promptly."""

    def __init__(self, fd: int, mm: mmap.mmap, name: str):
        self._fd = fd
        self._mm = mm
        self.name = name
        self.capacity = struct.unpack_from("<I", mm, _OFF_CAPACITY)[0]

    # -- shared-field accessors --------------------------------------------

    def _u64(self, off: int) -> int:
        return struct.unpack_from("<Q", self._mm, off)[0]

    def _set_u64(self, off: int, v: int) -> None:
        struct.pack_into("<Q", self._mm, off, v)

    def _bump_u32(self, off: int) -> None:
        v = struct.unpack_from("<I", self._mm, off)[0]
        struct.pack_into("<I", self._mm, off, (v + 1) & 0xFFFFFFFF)

    def _closed(self) -> bool:
        return struct.unpack_from("<I", self._mm, _OFF_CLOSED)[0] != 0

    # -- ops ----------------------------------------------------------------

    def mark_closed(self) -> None:
        struct.pack_into("<I", self._mm, _OFF_CLOSED, 1)
        self._bump_u32(_OFF_WSEQ)
        self._bump_u32(_OFF_RSEQ)

    def close(self) -> None:
        try:
            self._mm.close()
        except (BufferError, ValueError):
            pass
        try:
            os.close(self._fd)
        except OSError:
            pass

    def write(self, data: memoryview, deadline: Optional[float]) -> None:
        cap = self.capacity
        done = 0
        n = len(data)
        while done < n:
            if self._closed():
                raise ConnectionError("shm ring closed by peer")
            head = self._u64(_OFF_HEAD)
            tail = self._u64(_OFF_TAIL)
            space = cap - (head - tail)
            if space == 0:
                if deadline is not None and time.monotonic() > deadline:
                    raise socket.timeout("shm ring write timed out")
                time.sleep(_POLL_S)
                continue
            chunk = min(space, n - done)
            off = head % cap
            first = min(chunk, cap - off)
            self._mm[_HDR_BYTES + off:_HDR_BYTES + off + first] = \
                data[done:done + first]
            if chunk > first:
                self._mm[_HDR_BYTES:_HDR_BYTES + chunk - first] = \
                    data[done + first:done + chunk]
            self._set_u64(_OFF_HEAD, head + chunk)
            self._bump_u32(_OFF_WSEQ)
            done += chunk

    def read_into(self, buf: bytearray, start: int, n: int,
                  deadline: Optional[float]) -> None:
        cap = self.capacity
        done = 0
        view = memoryview(buf)
        while done < n:
            head = self._u64(_OFF_HEAD)
            tail = self._u64(_OFF_TAIL)
            avail = head - tail
            if avail == 0:
                if self._closed() and self._u64(_OFF_HEAD) == tail:
                    raise ConnectionError("connection closed by peer")
                if deadline is not None and time.monotonic() > deadline:
                    raise socket.timeout("shm ring read timed out")
                time.sleep(_POLL_S)
                continue
            chunk = min(avail, n - done)
            off = tail % cap
            first = min(chunk, cap - off)
            view[start + done:start + done + first] = \
                self._mm[_HDR_BYTES + off:_HDR_BYTES + off + first]
            if chunk > first:
                view[start + done + first:start + done + chunk] = \
                    self._mm[_HDR_BYTES:_HDR_BYTES + chunk - first]
            self._set_u64(_OFF_TAIL, tail + chunk)
            self._bump_u32(_OFF_RSEQ)
            done += chunk


class _NativeRing:
    """One ring endpoint backed by native/shmcore.cpp via ctypes."""

    def __init__(self, handle: ctypes.c_void_p, name: str):
        self._h = handle
        self.name = name

    def mark_closed(self) -> None:
        _native.shmcore().shm_ring_mark_closed(self._h)

    def close(self) -> None:
        _native.shmcore().shm_ring_close(self._h)


def _shm_dir() -> str:
    return "/dev/shm"


def _py_path(name: str) -> str:
    # shm_open("/x") maps to /dev/shm/x — the fallback uses the same
    # files so native and fallback processes interoperate.
    return os.path.join(_shm_dir(), name.lstrip("/"))


def create_ring(name: str, capacity: int) -> Union[_NativeRing, _PyRing]:
    """Create (as listener) one ring; clears any stale object first, as
    the unix-socket bootstrap clears a stale socket file."""
    lib = _native.shmcore()
    if lib is not None:
        lib.shm_ring_unlink(name.encode())
        out = ctypes.c_void_p()
        rc = lib.shm_ring_create(name.encode(), capacity, ctypes.byref(out))
        if rc != 0:
            raise MpiError(f"mpi_tpu: shm ring create {name!r} failed: "
                           f"{os.strerror(-rc)}")
        return _NativeRing(out, name)
    path = _py_path(name)
    try:
        os.unlink(path)
    except OSError:
        pass
    fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600)
    try:
        os.ftruncate(fd, _HDR_BYTES + capacity)
        mm = mmap.mmap(fd, _HDR_BYTES + capacity)
        struct.pack_into("<I", mm, _OFF_CAPACITY, capacity)
        for off in (_OFF_HEAD, _OFF_TAIL):
            struct.pack_into("<Q", mm, off, 0)
        for off in (_OFF_WSEQ, _OFF_RSEQ, _OFF_CLOSED):
            struct.pack_into("<I", mm, off, 0)
        struct.pack_into("<I", mm, _OFF_MAGIC, _MAGIC)
        struct.pack_into("<I", mm, _OFF_READY, 1)
    except BaseException:
        os.close(fd)
        try:
            os.unlink(path)
        except OSError:
            pass
        raise
    return _PyRing(fd, mm, name)


def attach_ring(name: str) -> Optional[Union[_NativeRing, _PyRing]]:
    """One attach attempt (as dialer); None when the ring does not exist
    or is not initialized yet — the caller retries until its timeout
    (the 100 ms dial-retry loop, network.go:297-312)."""
    lib = _native.shmcore()
    if lib is not None:
        out = ctypes.c_void_p()
        rc = lib.shm_ring_attach(name.encode(), ctypes.byref(out))
        if rc == 0:
            return _NativeRing(out, name)
        if rc in (-_errno.ENOENT, -_errno.EAGAIN):
            return None
        raise MpiError(f"mpi_tpu: shm ring attach {name!r} failed: "
                       f"{os.strerror(-rc)}")
    path = _py_path(name)
    try:
        fd = os.open(path, os.O_RDWR)
    except FileNotFoundError:
        return None
    try:
        size = os.fstat(fd).st_size
        if size < _HDR_BYTES:
            os.close(fd)
            return None
        mm = mmap.mmap(fd, size)
    except OSError:
        os.close(fd)
        return None
    magic, = struct.unpack_from("<I", mm, _OFF_MAGIC)
    ready, = struct.unpack_from("<I", mm, _OFF_READY)
    cap, = struct.unpack_from("<I", mm, _OFF_CAPACITY)
    if magic != _MAGIC or ready != 1 or size < _HDR_BYTES + cap:
        mm.close()
        os.close(fd)
        return None
    return _PyRing(fd, mm, name)


def unlink_ring(name: str) -> None:
    lib = _native.shmcore()
    if lib is not None:
        lib.shm_ring_unlink(name.encode())
        return
    try:
        os.unlink(_py_path(name))
    except OSError:
        pass


# --------------------------------------------------------------------------
# Frame connection over a ring pair
# --------------------------------------------------------------------------

class ShmConn:
    """Bidirectional frame connection: ``tx`` carries this side's
    frames, ``rx`` the peer's. Duck-types the slice of the socket API
    the TCP driver uses (``settimeout``/``close``); the driver's frame
    helpers dispatch here for the actual I/O. One sender at a time per
    conn (the driver's per-conn write lock) and one reader (the
    persistent reader thread) — exactly the SPSC contract the rings
    require."""

    def __init__(self, tx, rx, owned_names: Tuple[str, ...] = ()):
        self._tx = tx
        self._rx = rx
        self.owned_names = owned_names  # rings this side created → unlink
        self._timeout: Optional[float] = None
        self._released = False

    # -- socket-API slice ---------------------------------------------------

    def settimeout(self, t: Optional[float]) -> None:
        self._timeout = t

    def gettimeout(self) -> Optional[float]:
        return self._timeout

    def shutdown(self, _how: int = 0) -> None:
        self._tx.mark_closed()
        self._rx.mark_closed()

    def close(self) -> None:
        """Mark both rings closed and wake any blocked peer/reader.

        Deliberately does NOT unmap: a reader thread blocked inside the
        native recv dereferences the mapping, so tearing it down here
        would be a use-after-munmap. The driver calls :meth:`release`
        after joining its reader threads."""
        self._tx.mark_closed()
        self._rx.mark_closed()

    def release(self) -> None:
        """Unmap the rings and unlink owned names. Only safe once no
        thread can be inside this conn's frame ops (readers joined)."""
        if self._released:
            return
        self._released = True
        self._tx.close()
        self._rx.close()
        for name in self.owned_names:
            unlink_ring(name)

    # -- frame I/O ----------------------------------------------------------

    def _deadline(self) -> Optional[float]:
        return None if self._timeout is None \
            else time.monotonic() + self._timeout

    @staticmethod
    def _remaining_ms(deadline: Optional[float], what: str) -> int:
        """Milliseconds left until ``deadline`` (-1 = infinite). The
        deadline is computed ONCE per frame op and only the remainder
        is passed on each EINTR resume — restarting the full timeout
        per resume would let any periodic signal (SIGCHLD from the
        launcher, profiling timers) extend the deadline forever."""
        if deadline is None:
            return -1
        left = deadline - time.monotonic()
        if left <= 0:
            raise socket.timeout(f"shm {what} timed out")
        return max(1, int(left * 1000))

    def _native_send(self, what: str, call) -> None:
        """Run one resumable native ring op to completion: EINTR
        resumes (returning to the interpreter so pending Python signal
        handlers run between resumes), a Python-side deadline expiry
        abandons the op exactly like a native -ETIMEDOUT would
        (poisoning if that strands the stream mid-frame), and native
        rc values map to the same exceptions everywhere. ``call`` is
        ``(lib, timeout_ms) -> rc``."""
        lib = _native.shmcore()
        deadline = self._deadline()
        try:
            while True:
                rc = call(lib, self._remaining_ms(deadline, what))
                if rc != -_errno.EINTR:
                    break
        except socket.timeout:
            lib.shm_abandon(self._tx._h, 0)
            raise
        if rc == _native.PEER_CLOSED:
            raise ConnectionError("shm ring closed by peer")
        if rc == -_errno.ETIMEDOUT:
            raise socket.timeout(f"shm {what} timed out")
        if rc != 0:
            raise OSError(-rc, os.strerror(-rc))

    def send_frame(self, kind: int, tag: int, payload: bytes = b"") -> None:
        if len(payload) > 0xFFFFFFFF:
            # The wire length field is u32; ctypes would silently
            # truncate (the TCP path's struct.pack raises — match it).
            raise MpiError(
                f"mpi_tpu: shm frame payload of {len(payload)} bytes "
                f"exceeds the u32 wire limit")
        tx = self._tx
        if isinstance(tx, _NativeRing):
            buf = bytes(payload) if not isinstance(payload, bytes) else payload
            self._native_send("send", lambda lib, ms: lib.shm_send_frame(
                tx._h, kind, tag, buf, len(buf), ms))
            return
        deadline = self._deadline()
        header = _FRAME_HDR.pack(kind, tag, len(payload))
        tx.write(memoryview(header), deadline)
        if payload:
            tx.write(memoryview(payload), deadline)

    def send_frame2(self, kind: int, tag: int, prefix: bytes,
                    view) -> None:
        """One frame whose body is ``prefix + view``, streamed without
        concatenation — the shm side of the codec's zero-copy ndarray
        path (``encode_parts``). The receiver sees an ordinary frame
        of the combined length."""
        mv = memoryview(view).cast("B")
        total = len(prefix) + mv.nbytes
        if total > 0xFFFFFFFF:
            raise MpiError(
                f"mpi_tpu: shm frame payload of {total} bytes "
                f"exceeds the u32 wire limit")
        tx = self._tx
        if isinstance(tx, _NativeRing):
            from .tcp import _view_cptr

            ptr, keep = _view_cptr(mv)
            try:
                self._native_send(
                    "send", lambda lib, ms: lib.shm_send_frame2(
                        tx._h, kind, tag, prefix, len(prefix),
                        ptr, mv.nbytes, ms))
            finally:
                del keep
            return
        deadline = self._deadline()
        header = _FRAME_HDR.pack(kind, tag, total)
        tx.write(memoryview(header), deadline)
        if prefix:
            tx.write(memoryview(prefix), deadline)
        if mv.nbytes:
            tx.write(mv, deadline)

    def recv_frame(self) -> Tuple[int, int, bytearray]:
        rx = self._rx
        if isinstance(rx, _NativeRing):
            lib = _native.shmcore()
            kind = ctypes.c_uint8()
            tag = ctypes.c_int64()
            length = ctypes.c_uint32()
            deadline = self._deadline()
            try:
                while True:
                    rc = lib.shm_recv_hdr(
                        rx._h, ctypes.byref(kind), ctypes.byref(tag),
                        ctypes.byref(length),
                        self._remaining_ms(deadline, "recv header"))
                    if rc != -_errno.EINTR:
                        break
            except socket.timeout:
                lib.shm_abandon(rx._h, 0)  # poison only if mid-header
                raise
            if rc == _native.PEER_CLOSED:
                raise OrderlyClose("connection closed by peer")
            self._check_rc(rc, "recv header")
            n = length.value
            payload = bytearray(n)
            if n:
                arr = (ctypes.c_ubyte * n).from_buffer(payload)
                try:
                    while True:
                        rc = lib.shm_recv_payload(
                            rx._h, arr, n,
                            self._remaining_ms(deadline, "recv payload"))
                        if rc != -_errno.EINTR:
                            break
                except socket.timeout:
                    # mid-frame by definition: the header announcing
                    # this payload was already consumed (force=1).
                    lib.shm_abandon(rx._h, 1)
                    raise
                self._check_rc(rc, "recv payload")
            return kind.value, tag.value, payload
        deadline = None if self._timeout is None \
            else time.monotonic() + self._timeout
        hdr = bytearray(_FRAME_HDR.size)
        try:
            rx.read_into(hdr, 0, _FRAME_HDR.size, deadline)
        except ConnectionError as exc:
            raise OrderlyClose(str(exc)) from None
        kind_v, tag_v, length_v = _FRAME_HDR.unpack(bytes(hdr))
        payload = bytearray(length_v)
        if length_v:
            rx.read_into(payload, 0, length_v, deadline)
        return kind_v, tag_v, payload

    @staticmethod
    def _check_rc(rc: int, what: str) -> None:
        if rc == 0:
            return
        if rc == _native.PEER_CLOSED:
            raise ConnectionError("connection closed by peer")
        if rc == -_errno.ETIMEDOUT:
            raise socket.timeout(f"shm {what} timed out")
        raise OSError(-rc, os.strerror(-rc))
