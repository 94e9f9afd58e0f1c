"""Compiled tagged point-to-point — Send/Receive lowered to ICI programs.

The reference's entire data path is tagged blocking Send/Receive over TCP
sockets (/root/reference/network.go:518-625, tag routing :448-497). The
tpu-native re-expression has to respect XLA's compilation model: a jitted
SPMD program is traced once, so the communication *pattern* (who talks to
whom) must be static, while the payloads are device-resident arrays moving
over ICI. This module provides that re-expression at three levels:

1. :func:`exchange` — a static ``(src, dst)`` pattern as one
   ``lax.ppermute``: the compiled equivalent of a matched Send/Receive
   set. Ranks outside the pattern receive zeros (XLA's ppermute
   contract).
2. :func:`tagged_exchange` — multiple concurrent *channels*: each tag is
   an independent static pattern with its own payload, lowered to one
   ppermute per tag. This is the in-jit realization of the reference's
   tag demultiplexing (network.go:449-497): a live ``{pair, tag}`` maps
   to a distinct collective channel instead of a ``chan []byte``, and
   the uniqueness contract (mpi.go:122-125) becomes "one (src, dst) pair
   per tag per exchange" — checked at trace time, turning the
   reference's runtime panics into trace-time errors.
3. :func:`pallas_sendrecv` — the same static pattern hand-lowered to
   Pallas remote DMA (``pltpu.make_async_remote_copy``): sender devices
   push their buffer straight into the receiver's output ref and signal
   a DMA semaphore — the chip-to-chip RDMA twin of the reference's
   socket write + ack (network.go:562-569, 617-624), with the semaphore
   pair playing the ack's role.

All three are jittable inside ``shard_map`` over the rank axis; the
``*_sharded`` wrappers handle the shard_map plumbing for global arrays.
The host-driven driver path (:class:`mpi_tpu.backends.xla.XlaNetwork`)
uses :class:`DevicePipe` to run these compiled transfers for dynamically
tagged traffic: each distinct ``(src_device, dst_device, shape, dtype)``
gets one cached compiled program, so steady-state tagged p2p costs one
program launch and zero host round-trips of the payload.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import RANK_AXIS

__all__ = [
    "exchange",
    "tagged_exchange",
    "pallas_sendrecv",
    "exchange_sharded",
    "pallas_sendrecv_sharded",
    "DevicePipe",
]

Pair = Tuple[int, int]


def _check_pattern(perm: Sequence[Pair], n: Optional[int] = None) -> List[Pair]:
    """Trace-time misuse detection (the reference panics at runtime,
    network.go:469): each rank sends at most once and receives at most
    once per channel."""
    seen_src: Dict[int, int] = {}
    seen_dst: Dict[int, int] = {}
    out: List[Pair] = []
    for s, d in perm:
        s, d = int(s), int(d)
        if n is not None and not (0 <= s < n and 0 <= d < n):
            raise ValueError(
                f"mpi_tpu: p2p pair ({s}, {d}) out of range [0, {n})")
        if s in seen_src:
            raise ValueError(
                f"mpi_tpu: rank {s} sends twice in one channel "
                f"(to {seen_src[s]} and {d}) — use distinct tags "
                f"(mpi.go:122-125 uniqueness contract)")
        if d in seen_dst:
            raise ValueError(
                f"mpi_tpu: rank {d} receives twice in one channel "
                f"(from {seen_dst[d]} and {s}) — use distinct tags "
                f"(mpi.go:153-156 uniqueness contract)")
        seen_src[s] = d
        seen_dst[d] = s
        out.append((s, d))
    return out


def exchange(x: jnp.ndarray, perm: Sequence[Pair],
             axis_name: str = RANK_AXIS) -> jnp.ndarray:
    """One matched Send/Receive set as a single compiled collective.

    ``perm`` is the static pattern: ``(s, d)`` means rank ``s``'s ``x``
    lands on rank ``d``. Ranks that receive nothing get zeros. Call
    inside ``shard_map`` over ``axis_name``."""
    perm = _check_pattern(perm)
    return lax.ppermute(x, axis_name, perm)


def tagged_exchange(values: Dict[int, jnp.ndarray],
                    sends: Dict[int, Sequence[Pair]],
                    axis_name: str = RANK_AXIS) -> Dict[int, jnp.ndarray]:
    """Concurrent tagged channels inside one jitted program.

    ``sends[tag]`` is the static pattern for channel ``tag``;
    ``values[tag]`` is this rank's payload on that channel (ignored by
    ranks that don't send on it). Returns ``{tag: received}`` — each tag
    an independent ppermute, so XLA may overlap them; payloads on
    different tags never mix, which is exactly the tagManager guarantee
    (network.go:449-497)."""
    if set(values) != set(sends):
        raise ValueError(
            f"mpi_tpu: tagged_exchange values/sends tag mismatch: "
            f"{sorted(values)} vs {sorted(sends)}")
    out: Dict[int, jnp.ndarray] = {}
    for tag in sorted(sends):
        out[tag] = exchange(values[tag], sends[tag], axis_name)
    return out


def exchange_sharded(x: jnp.ndarray, mesh: Mesh, perm: Sequence[Pair],
                     axis_name: str = RANK_AXIS) -> jnp.ndarray:
    """Global view of :func:`exchange`: ``x`` sharded over ``axis_name``
    on axis 0 (one block per rank) → permuted global array."""
    body = functools.partial(exchange, perm=perm, axis_name=axis_name)
    return jax.shard_map(body, mesh=mesh, in_specs=P(axis_name),
                         out_specs=P(axis_name), check_vma=False)(x)


# --------------------------------------------------------------------------
# Pallas remote-DMA path — the hand-lowered twin of `exchange`.
# --------------------------------------------------------------------------

def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _complete_permutation(perm: Tuple[Pair, ...], n: int) -> List[Pair]:
    """Extend a partial (src, dst) pattern to a full permutation of
    ``range(n)`` by matching idle senders to idle receivers in sorted
    order. Keeps the kernel SPMD-uniform: every device runs exactly one
    remote DMA (idle devices ship filler that gets masked to zero), so
    no device skips the collective — required both by the Pallas
    interpreter's emulation and for a deadlock-free schedule on hardware."""
    srcs = {s for s, _ in perm}
    dsts = {d for _, d in perm}
    idle_src = sorted(set(range(n)) - srcs)
    idle_dst = sorted(set(range(n)) - dsts)
    return list(perm) + list(zip(idle_src, idle_dst))


def _sendrecv_kernel(x_ref, out_ref, send_sem, recv_sem, *,
                     perm: Tuple[Pair, ...], axis_name: str):
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    full = _complete_permutation(perm, n)

    # Every device pushes its buffer to its (statically resolved)
    # destination's out_ref and signals the DMA semaphore pair: send_sem
    # = "my buffer is reusable", recv_sem = "the message arrived" —
    # together the rendezvous the reference builds from the ack message
    # (network.go:569, 617-624), expressed as chip-to-chip RDMA.
    dst = me
    for s, d in full:
        if s != d:
            dst = jnp.where(me == s, d, dst)
    copy = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=out_ref,
        send_sem=send_sem, recv_sem=recv_sem,
        device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)
    copy.start()
    copy.wait()

    # ppermute semantics: ranks outside the requested pattern get zeros
    # (their arrival was idle-sender filler).
    real_dsts = [d for _, d in perm]
    if len(real_dsts) < n:
        is_recv = jnp.zeros((), jnp.bool_)
        for d in real_dsts:
            is_recv = jnp.logical_or(is_recv, me == d)

        @pl.when(jnp.logical_not(is_recv))
        def _mask():
            out_ref[...] = jnp.zeros_like(out_ref)


def pallas_sendrecv(x: jax.Array, perm: Sequence[Pair],
                    axis_name: str = RANK_AXIS,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Per-device body: the static pattern ``perm`` executed as remote
    DMA pushes. Semantics match :func:`exchange` (non-receivers get
    zeros). Call inside ``shard_map`` over ``axis_name``. Compiles for
    the v5e (tests/test_tpu_compile.py), never executed on hardware;
    like the ring kernels it takes no start barrier (ROADMAP S4)."""
    perm = tuple(_check_pattern(perm))
    itp = _should_interpret() if interpret is None else interpret
    kernel = functools.partial(_sendrecv_kernel, perm=perm,
                               axis_name=axis_name)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=itp,
    )(x)


def pallas_sendrecv_sharded(x: jax.Array, mesh: Mesh, perm: Sequence[Pair],
                            axis_name: str = RANK_AXIS,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Global view of :func:`pallas_sendrecv` (x sharded on axis 0)."""
    body = functools.partial(pallas_sendrecv, perm=perm,
                             axis_name=axis_name, interpret=interpret)
    return jax.shard_map(body, mesh=mesh, in_specs=P(axis_name),
                         out_specs=P(axis_name), check_vma=False)(x)


# --------------------------------------------------------------------------
# DevicePipe — compiled transfers for the host-driven driver.
# --------------------------------------------------------------------------

class DevicePipe:
    """Compiled device→device transfer engine for dynamically tagged p2p.

    The driver's Send/Receive calls carry dynamic ``(dest, tag)``
    (mpi.go:126-159) that no single compiled program can cover, so the
    pipe compiles one two-device ppermute program per distinct
    ``(src_device, dst_device, shape, dtype)`` and reuses it: the payload
    (already resident on the source device) becomes shard 0 of a
    two-shard global array, the program runs ``ppermute [(0, 1)]`` over
    a private two-device mesh — a pure ICI hop on TPU — and shard 1 *is*
    the received array on the destination device. The payload bytes
    never visit the host; steady state is one cached-executable launch.
    """

    # Distinct payload shapes seen recently; bounds destination-side HBM
    # held by cached filler shards (one per (device, shape, dtype)).
    FILLER_CACHE = 32

    def __init__(self) -> None:
        # One jitted fn per (src_dev, dst_dev) — jax.jit caches the
        # per-shape executables internally, so the key needs no shape.
        self._progs: Dict[Tuple, Tuple] = {}
        self._fillers: "OrderedDict[Tuple, jax.Array]" = OrderedDict()
        self._lock = threading.Lock()

    def _filler(self, device, shape, dtype) -> jax.Array:
        """A zeros array resident on ``device`` — the placeholder shard a
        two-shard global array needs on the destination side. Its
        contents are never read (ppermute overwrites shard 1). LRU-capped
        so long-running drivers with many payload shapes don't pin
        unbounded device memory."""
        key = (device, shape, str(dtype))
        with self._lock:
            arr = self._fillers.get(key)
            if arr is not None:
                self._fillers.move_to_end(key)
                return arr
        arr = jax.device_put(np.zeros((1, *shape), dtype), device)
        with self._lock:
            self._fillers[key] = arr
            while len(self._fillers) > self.FILLER_CACHE:
                self._fillers.popitem(last=False)
        return arr

    def transfer(self, payload: jax.Array, src_dev, dst_dev) -> jax.Array:
        """Move ``payload`` (resident on ``src_dev``) to ``dst_dev`` via
        the compiled ppermute program; returns the device-resident result."""
        shape, dtype = payload.shape, payload.dtype
        key = (src_dev, dst_dev)
        with self._lock:
            entry = self._progs.get(key)
        if entry is None:
            mesh = Mesh(np.asarray([src_dev, dst_dev]), ("pt",))

            def hop(x):
                return lax.ppermute(x, "pt", [(0, 1)])

            entry = (
                jax.jit(jax.shard_map(hop, mesh=mesh, in_specs=P("pt"),
                                      out_specs=P("pt"), check_vma=False)),
                NamedSharding(mesh, P("pt")),
            )
            with self._lock:
                self._progs[key] = entry
        fn, sharding = entry
        blocks = [
            payload.reshape((1, *shape)),
            self._filler(dst_dev, shape, dtype),
        ]
        garr = jax.make_array_from_single_device_arrays(
            (2, *shape), sharding, blocks)
        out = fn(garr)
        for shard in out.addressable_shards:
            if shard.device == dst_dev:
                return shard.data.reshape(shape)
        raise RuntimeError(
            "mpi_tpu: DevicePipe output missing destination shard")
