"""Native wirecore: build, frame roundtrips, and python-fallback parity."""

import socket
import threading

import pytest

from mpi_tpu import native
from mpi_tpu.backends.tcp import _recv_frame, _send_frame


requires_native = pytest.mark.skipif(
    not native.available(), reason=f"wirecore unavailable: "
    f"{native.build_error()}")


@requires_native
def test_native_builds_and_loads():
    lib = native.wirecore()
    # v4 since the stage-scratch ABI (native/__init__.py
    # _configure_wirecore refuses any other version at load).
    assert lib.wc_version() == 4


def _roundtrip(payload: bytes, tag: int = 42, kind: int = 0):
    a, b = socket.socketpair()
    try:
        lk = threading.Lock()
        t = threading.Thread(target=_send_frame,
                             args=(a, lk, kind, tag, payload), daemon=True)
        t.start()
        got = _recv_frame(b)
        t.join(timeout=10)
        return got
    finally:
        a.close()
        b.close()


@requires_native
@pytest.mark.parametrize("size", [0, 1, 13, 4096, 1 << 20])
def test_frame_roundtrip_sizes(size):
    payload = bytes(i % 251 for i in range(size))
    kind, tag, got = _roundtrip(payload)
    assert (kind, tag, bytes(got)) == (0, 42, payload)


@requires_native
def test_negative_tag_roundtrip():
    # i64 wire tags must round-trip sign-correctly through the C layer
    kind, tag, got = _roundtrip(b"x", tag=-7)
    assert tag == -7 and bytes(got) == b"x"


@requires_native
def test_peer_close_raises_connectionerror():
    a, b = socket.socketpair()
    a.close()
    with pytest.raises(ConnectionError):
        _recv_frame(b)
    b.close()


def test_fallback_forced(monkeypatch):
    # With the native core disabled the pure-Python path must carry the
    # identical frames.
    monkeypatch.setattr(native, "wirecore", lambda: None)
    payload = b"fallback" * 1000
    kind, tag, got = _roundtrip(payload, tag=9)
    assert (kind, tag, bytes(got)) == (0, 9, payload)


@requires_native
def test_native_to_python_interop(monkeypatch):
    # Frame written by the native engine, read by the python fallback —
    # byte-identical wire format.
    a, b = socket.socketpair()
    try:
        lk = threading.Lock()
        payload = bytes(range(256)) * 16
        t = threading.Thread(target=_send_frame,
                             args=(a, lk, 1, 77, payload), daemon=True)
        t.start()  # native (blocking socket, bytes payload)
        monkeypatch.setattr(native, "wirecore", lambda: None)
        kind, tag, got = _recv_frame(b)  # python
        t.join(timeout=10)
        assert (kind, tag, bytes(got)) == (1, 77, payload)
    finally:
        a.close()
        b.close()
