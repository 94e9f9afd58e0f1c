"""Pallas ring collectives — hand-scheduled ICI neighbour DMA.

XLA's built-in collectives (``lax.psum`` et al., used by
:mod:`mpi_tpu.parallel.collectives`) are the production path; these
kernels are the framework's *native* collective implementations, written
directly against the TPU interconnect with
``pltpu.make_async_remote_copy``: each device pushes a buffer to its ring
neighbour's VMEM and signals a DMA semaphore — exactly the transfer the
reference performs with a TCP socket write + ack (network.go:518-625),
re-expressed as chip-to-chip RDMA. They exist (a) as the lowest-level
point on the framework's collective stack, (b) to support custom fusion
(compute folded into the ring step) that XLA's opaque collectives can't
express, and (c) as executable documentation of the pallas_guide.md ring
pattern.

Algorithms:
  * :func:`ring_allgather` — n-1 ring hops, double-buffered;
  * :func:`ring_allreduce` — bandwidth-optimal two-phase ring:
    reduce-scatter (n-1 hops, each folding the arriving partial into the
    resident chunk) then allgather of the reduced chunks (n-1 hops).
    2·(n-1)/n · bytes moved per device — the classic ring bound.

Both are per-device bodies to be traced inside ``shard_map`` over the
ring axis; ``*_sharded`` wrappers handle that. On non-TPU backends the
kernels run in the Pallas interpreter (exact same code path the tests
exercise on the virtual CPU mesh).

Status: the v5e compiler accepts both kernels
(tests/test_tpu_compile.py); neither has executed on hardware. They
take no start barrier (``pltpu.get_barrier_semaphore``, which is what
``CompilerParams(collective_id=...)`` exists for) — whether a device may
DMA into a neighbour that has not yet entered the kernel without one is
open (ROADMAP S4).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

__all__ = ["ring_allgather", "ring_allreduce",
           "ring_allgather_sharded", "ring_allreduce_sharded"]


def _combine(a, b, op: str):
    if op == "sum":
        return a + b
    if op == "max":
        return jnp.maximum(a, b)
    if op == "min":
        return jnp.minimum(a, b)
    if op == "prod":
        return a * b
    raise ValueError(f"mpi_tpu: unknown ring op {op!r}")


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# All-gather
# --------------------------------------------------------------------------

def _allgather_kernel(x_ref, out_ref, comm, send_sem, recv_sem, *,
                      axis_name: str):
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    chunk = x_ref.shape[0]
    out_ref[pl.ds(me * chunk, chunk)] = x_ref[...]
    comm[0] = x_ref[...]
    for step in range(n - 1):
        src = (me - step - 1) % n
        dst = (me + 1) % n
        s_slot, r_slot = step % 2, (step + 1) % 2
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm.at[s_slot], dst_ref=comm.at[r_slot],
            send_sem=send_sem.at[s_slot], recv_sem=recv_sem.at[r_slot],
            device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma.wait()
        out_ref[pl.ds(src * chunk, chunk)] = comm[r_slot]


def ring_allgather(x: jax.Array, axis_name: str = "rank",
                   interpret: Optional[bool] = None) -> jax.Array:
    """Per-device body: gather every device's ``x`` (concatenated along
    axis 0 in ring order). Call inside shard_map over ``axis_name``."""
    itp = _should_interpret() if interpret is None else interpret
    n = lax.axis_size(axis_name)
    kernel = functools.partial(_allgather_kernel, axis_name=axis_name)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((x.shape[0] * n, *x.shape[1:]),
                                       x.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, *x.shape), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=itp,
    )(x)


# --------------------------------------------------------------------------
# All-reduce (reduce-scatter ring + allgather ring)
# --------------------------------------------------------------------------

def _allreduce_kernel(x_ref, out_ref, comm, send_sem, recv_sem, *,
                      axis_name: str, op: str, n: int):
    me = lax.axis_index(axis_name)
    m = x_ref.shape[0]
    chunk = m // n
    out_ref[...] = x_ref[...]

    def hop(value, slot_step):
        """One neighbour push: send `value`, return the arriving buffer."""
        s_slot, r_slot = slot_step % 2, (slot_step + 1) % 2
        comm[s_slot] = value
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm.at[s_slot], dst_ref=comm.at[r_slot],
            send_sem=send_sem.at[s_slot], recv_sem=recv_sem.at[r_slot],
            device_id=(me + 1) % n,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma.wait()
        return comm[r_slot]

    # Phase 1 — reduce-scatter: after step t every device has folded t+1
    # partials into chunk (me - t) % n; chunk (me + 1) % n ends fully
    # reduced here.
    for step in range(n - 1):
        send_idx = (me - step) % n
        recv_idx = (me - step - 1) % n
        arrived = hop(out_ref[pl.ds(send_idx * chunk, chunk)], step)
        out_ref[pl.ds(recv_idx * chunk, chunk)] = _combine(
            out_ref[pl.ds(recv_idx * chunk, chunk)], arrived, op)

    # Phase 2 — allgather of the reduced chunks around the same ring.
    for step in range(n - 1):
        send_idx = (me + 1 - step) % n
        recv_idx = (me - step) % n
        arrived = hop(out_ref[pl.ds(send_idx * chunk, chunk)],
                      (n - 1) + step)
        out_ref[pl.ds(recv_idx * chunk, chunk)] = arrived


def ring_allreduce(x: jax.Array, axis_name: str = "rank", op: str = "sum",
                   interpret: Optional[bool] = None) -> jax.Array:
    """Per-device body: bandwidth-optimal ring allreduce of ``x`` across
    ``axis_name``. ``x.shape[0]`` must be divisible by the ring size (the
    sharded wrapper pads). Reduction order is ring order — deterministic,
    but not the binomial tree of the bitwise-parity path."""
    itp = _should_interpret() if interpret is None else interpret
    n = lax.axis_size(axis_name)
    if x.shape[0] % n:
        raise ValueError(
            f"mpi_tpu: ring_allreduce needs axis-0 divisible by ring size "
            f"{n}, got {x.shape[0]} (use ring_allreduce_sharded, which pads)")
    kernel = functools.partial(_allreduce_kernel, axis_name=axis_name,
                               op=op, n=n)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, x.shape[0] // n, *x.shape[1:]), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=itp,
    )(x)


# --------------------------------------------------------------------------
# shard_map wrappers
# --------------------------------------------------------------------------

def ring_allgather_sharded(x: jax.Array, mesh, axis_name: str = "rank",
                           interpret: Optional[bool] = None) -> jax.Array:
    """Global view: ``x`` sharded over ``axis_name`` on axis 0 → gathered
    (replicated) result."""
    body = functools.partial(ring_allgather, axis_name=axis_name,
                             interpret=interpret)
    fn = jax.shard_map(body, mesh=mesh, in_specs=P(axis_name),
                       out_specs=P(), check_vma=False)
    return fn(x)


def ring_allreduce_sharded(contribs: jax.Array, mesh,
                           axis_name: str = "rank", op: str = "sum",
                           interpret: Optional[bool] = None) -> jax.Array:
    """Global view: ``contribs`` is ``(n, m, ...)`` — device i's
    contribution at index i, sharded over ``axis_name`` — and the result
    is the ``(m, ...)`` reduction, replicated. Pads ``m`` to a multiple
    of the ring size internally."""
    n = mesh.shape[axis_name]
    if contribs.shape[0] != n:
        raise ValueError(
            f"mpi_tpu: contribs leading axis {contribs.shape[0]} != ring "
            f"size {n}")
    m = contribs.shape[1]
    pad = (-m) % n
    if pad:
        contribs = jnp.pad(
            contribs, ((0, 0), (0, pad)) + ((0, 0),) * (contribs.ndim - 2))

    def body(c):
        return ring_allreduce(c[0], axis_name=axis_name, op=op,
                              interpret=interpret)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(axis_name),
                       out_specs=P(), check_vma=False)
    out = fn(contribs)
    return out[:m] if pad else out
