"""Test harness configuration.

Forces JAX onto the CPU backend with 8 virtual devices *before* jax is
imported anywhere, so every multi-chip code path (mesh collectives, sharded
training steps, ppermute p2p) is exercised on a laptop/CI exactly as it
would run on a v4-8 — the tpu-native replacement for the reference's
"N real processes on localhost" test story (gompirun.go:46-51).
"""

import os
import socket
import threading
from contextlib import contextmanager

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# 64-bit payload parity with the TCP/numpy oracle (float64/int64 must not
# silently downcast in the XLA driver).
os.environ.setdefault("JAX_ENABLE_X64", "1")


_port_lock = threading.Lock()


def _free_ports(n: int) -> list:
    """Reserve n distinct localhost ports (bind-probe then release)."""
    socks, ports = [], []
    with _port_lock:
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
        for s in socks:
            s.close()
    return ports


@contextmanager
def tcp_cluster(n: int, password: str = "", timeout: float = 20.0,
                proto: str = "tcp", addrs=None, **net_kwargs):
    """Spin up n in-process TcpNetwork ranks on localhost and init them
    concurrently; yields the list ordered by rank. The in-process analogue
    of the reference's N-OS-process localhost harness. ``proto`` other
    than ``"tcp"`` needs its own ``addrs`` (socket paths for ``unix``,
    opaque ids for ``shm``). Extra keyword args (``crc=True``,
    ``optimeout=2.0``, ``chaos="7:1:delay"``, ...) pass through to every
    rank's TcpNetwork constructor."""
    from mpi_tpu.backends.tcp import TcpNetwork

    if addrs is None:
        # Fixed-width port strings sort lexically == numerically, giving
        # a deterministic rank order we can predict in tests.
        addrs = sorted(f"127.0.0.1:{p:05d}" for p in _free_ports(n))
    nets = [TcpNetwork(addr=a, addrs=list(addrs), timeout=timeout,
                       password=password, proto=proto, **net_kwargs)
            for a in addrs]
    errs = [None] * n

    def _init(i):
        try:
            nets[i].init()
        except BaseException as exc:  # noqa: BLE001
            errs[i] = exc

    threads = [threading.Thread(target=_init, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout + 10)
    for e in errs:
        if e is not None:
            raise e
    nets_by_rank = sorted(nets, key=lambda m: m.rank())
    try:
        yield nets_by_rank
    finally:
        for m in nets_by_rank:
            try:
                m.finalize()
            except Exception:
                pass


def _free_port_block(n: int, lo: int = 20000, hi: int = 60000) -> int:
    """Find a base port such that base..base+n-1 are all bindable — needed
    because mpirun assigns N *consecutive* ports from --port-base."""
    import random

    rng = random.Random()
    with _port_lock:
        for _ in range(200):
            base = rng.randrange(lo, hi - n)
            socks = []
            try:
                for p in range(base, base + n):
                    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                    s.bind(("127.0.0.1", p))
                    socks.append(s)
                return base
            except OSError:
                continue
            finally:
                for s in socks:
                    s.close()
    raise RuntimeError("no free consecutive port block found")


@pytest.fixture
def traced():
    """``mpi_tpu.utils.trace`` with its buffer and counters empty and
    recording on; left as it was found."""
    from mpi_tpu.utils import trace

    was = trace.enabled()
    trace.clear()
    trace.enable()
    try:
        yield trace
    finally:
        trace.clear()
        if not was:
            trace.disable()


@pytest.fixture
def cluster4():
    with tcp_cluster(4) as nets:
        yield nets


def run_on_ranks(nets, fn, timeout: float = 30.0):
    """Run fn(net, rank) on a thread per rank; re-raise the first error.
    Returns the per-rank results ordered by rank."""
    results = [None] * len(nets)
    errs = [None] * len(nets)

    def _run(i):
        try:
            results[i] = fn(nets[i], i)
        except BaseException as exc:  # noqa: BLE001
            errs[i] = exc

    threads = [threading.Thread(target=_run, args=(i,), daemon=True)
               for i in range(len(nets))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
        if t.is_alive():
            raise TimeoutError("rank thread hung (possible deadlock)")
    for e in errs:
        if e is not None:
            raise e
    return results


def run_hybrid_world(fn_for, hosts: int = 2, local: int = 2,
                     timeout: float = 60.0):
    """Run fn_for(net)() on every rank of a hosts x local hybrid world
    (one HybridNetwork per simulated host, threads standing in for host
    processes); returns results indexed by global rank. The thread
    harness is run_on_ranks — one copy of the fan-out/join/error logic.
    Shared by test_hybrid and the cross-backend torture test."""
    from mpi_tpu.backends.hybrid import HybridNetwork, run_spmd_hybrid
    from mpi_tpu.backends.tcp import TcpNetwork

    ports = _free_ports(hosts)
    addrs = sorted(f"127.0.0.1:{p:05d}" for p in ports)
    nets = [HybridNetwork(
        local_ranks=local,
        tcp=TcpNetwork(addr=a, addrs=list(addrs), timeout=30.0,
                       proto="tcp")) for a in addrs]
    per_host = run_on_ranks(
        nets,
        lambda net, h: run_spmd_hybrid(fn_for(net), net,
                                       register_facade=False),
        timeout=timeout)
    return [per_host[h][l] for h in range(hosts) for l in range(local)]
