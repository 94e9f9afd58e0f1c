"""Int8-quantized allreduce — bandwidth compression for big gradients.

Large-payload allreduce is wire-bound: a float32 ring moves ``~2 x 4``
bytes per element. Quantizing each leg to int8 with per-block float32
scales moves ``~2 x 1`` bytes (+ 1/block overhead) — a ~4x busbw
improvement wherever the interconnect, not the VPU, is the bottleneck
(DCN-crossing data parallelism above all). Where the wire is NOT the
bottleneck the compression is a straight loss (measured 3-10x slower
than the exact path on an in-memory fabric) — use
:func:`allreduce_compressed`, which applies the measured
:func:`quantized_eligible` gate and never loses to plain allreduce,
rather than calling :func:`quantized_allreduce` directly. The technique follows the
published quantized-allreduce design space (blockwise amax scaling,
quantize-per-phase — see PAPERS.md: EQuARX); the implementation is
XLA-native: one ``all_to_all`` + one ``all_gather``, both riding
ICI/DCN as compiled collectives.

Algorithm (one quantization per phase, so error is bounded by TWO
rounding steps regardless of rank count):

1. **reduce-scatter phase** — each rank splits its vector into ``n``
   destination shards, quantizes each shard blockwise (int8 payload +
   float32 scale per ``block`` elements), and exchanges them with one
   personalized ``all_to_all``; every rank dequantizes the ``n``
   received shards in float32 and sums them — its exact-ordered
   partial.
2. **allgather phase** — the reduced shard is quantized once more and
   ``all_gather`` reassembles the full vector everywhere.

The elementwise error obeys ``|err| <= 0.5 * (sum_i s1_i + s2)`` where
``s1_i`` is rank i's phase-1 scale for the element's block and ``s2``
the phase-2 scale — the bound the unit tests assert exactly.

No reference analogue (btracey/mpi stubs collectives entirely,
mpi.go:130); this extends the north-star collective layer
(:mod:`mpi_tpu.parallel.collectives`) beyond parity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import RANK_AXIS

__all__ = ["quantized_allreduce", "quantize_blocks", "dequantize_blocks",
           "quantized_eligible", "allreduce_compressed",
           "QUANTIZED_MIN_BYTES"]

# Measured dispatch gate (mirrors ``collectives_generic.ring_eligible``'s
# measured-crossover discipline): the compression only pays where the
# WIRE is the bottleneck, and below the crossover the extra
# quantize/dequantize compute is a straight regression — the forced
# path measured 8.6x slower than plain allreduce at 1 MiB on the virtual
# CPU mesh.
#
# fabric -> minimum payload bytes where int8+scales beats float32
# (None = never):
#   "cpu"  — measured 2026-07-31 on the 8-device virtual CPU mesh:
#            quantized was 3-10x SLOWER at every size from 1 MiB to
#            128 MiB (ratio shrinking with size but never crossing) —
#            an in-memory "fabric" has no bandwidth shortage for the
#            compression to buy back.
#   "tpu"  — provisional 64 MiB: ICI busbw is high enough that only
#            very large, bandwidth-bound payloads can win; unmeasured
#            on multi-chip hardware (single-chip box — a 1-device axis
#            has no collective), so the gate errs conservative. Re-run
#            the bench sweep on a pod slice to replace this constant.
#   "dcn"  — 1 MiB: cross-host links are the design target (EQuARX,
#            PAPERS.md) — wire-bound from small sizes; the hybrid
#            driver's leader tier is the in-repo analogue.
QUANTIZED_MIN_BYTES = {
    "cpu": None,
    "tpu": 64 << 20,
    "dcn": 1 << 20,
}


def quantized_eligible(nbytes: int, fabric: str | None = None) -> bool:
    """True when an int8-compressed allreduce of ``nbytes`` is expected
    to beat the exact float path on ``fabric`` (``"cpu"``/``"tpu"``/
    ``"dcn"``; default: the current JAX backend). The thresholds are
    measured (or explicitly provisional) constants —
    see ``QUANTIZED_MIN_BYTES``."""
    if fabric is None:
        fabric = jax.default_backend()
    threshold = QUANTIZED_MIN_BYTES.get(fabric)
    return threshold is not None and nbytes >= threshold


def quantize_blocks(x: jnp.ndarray, block: int):
    """Blockwise symmetric int8 quantization of a flat float vector
    whose size divides ``block``: returns ``(q int8 (nblk, block),
    scale float32 (nblk, 1))`` with ``x ~= q * scale``."""
    xb = x.reshape(-1, block).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    finite = jnp.isfinite(amax)
    safe = jnp.where(finite & (amax > 0), amax, jnp.float32(127.0))
    scale = safe / 127.0
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    # A block containing NaN/inf must stay loud: its scale becomes NaN
    # so dequantization yields NaN for the whole block — divergence
    # propagates exactly as through the exact allreduce, instead of
    # being silently laundered into finite garbage.
    scale = jnp.where(finite, scale, jnp.float32(jnp.nan))
    return q, scale.astype(jnp.float32)


def dequantize_blocks(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_blocks` (flattened float32)."""
    return (q.astype(jnp.float32) * scale).reshape(-1)


def quantized_allreduce(x: jnp.ndarray, axis_name: str = RANK_AXIS,
                        block: int = 1024) -> jnp.ndarray:
    """Sum-allreduce over ``axis_name`` with int8-compressed wire
    traffic (module doc). Call inside ``shard_map`` over the axis,
    like every :mod:`.collectives` function. Any shape/float dtype;
    returns ``x``'s shape and dtype (accumulation in float32). This
    is LOSSY (two int8 roundings); use :func:`.collectives.allreduce`
    when exactness matters."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise TypeError(
            f"mpi_tpu: quantized_allreduce compresses float payloads; "
            f"got {x.dtype} (integer reductions must be exact — use "
            f"collectives.allreduce)")
    n = lax.axis_size(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    m = flat.shape[0]
    # Pad so every rank-shard is a whole number of blocks.
    chunk = -(-m // (n * block)) * block       # elements per rank shard
    flat = jnp.pad(flat, (0, n * chunk - m))

    # Phase 1: quantize per destination shard, personalized exchange,
    # dequantized float32 accumulation (rank order — deterministic).
    q, s = quantize_blocks(flat, block)        # (n*nb, block), (n*nb, 1)
    nb = chunk // block                        # blocks per shard
    q = lax.all_to_all(q.reshape(n, nb, block), axis_name,
                       split_axis=0, concat_axis=0, tiled=True)
    s = lax.all_to_all(s.reshape(n, nb, 1), axis_name,
                       split_axis=0, concat_axis=0, tiled=True)
    q = q.reshape(n, nb, block)
    s = s.reshape(n, nb, 1)
    partial = jnp.sum(q.astype(jnp.float32) * s, axis=0)  # (nb, block)

    # Phase 2: one more quantization, allgather, dequantize.
    q2, s2 = quantize_blocks(partial.reshape(-1), block)
    gq = lax.all_gather(q2, axis_name, axis=0, tiled=True)
    gs = lax.all_gather(s2, axis_name, axis=0, tiled=True)
    full = dequantize_blocks(gq, gs)[:m]
    return full.reshape(shape).astype(dtype)


def allreduce_compressed(x: jnp.ndarray, axis_name: str = RANK_AXIS,
                         block: int = 1024,
                         fabric: str | None = None) -> jnp.ndarray:
    """Size/fabric-dispatched allreduce: int8-compressed wire traffic
    when :func:`quantized_eligible` says the payload is big enough to
    be wire-bound on this fabric, the exact float path otherwise — so
    the recommended call never loses to plain
    :func:`.collectives.allreduce` at any size. Call inside
    ``shard_map`` like both underlying paths. The dispatch is on the
    STATIC payload size at trace time (no runtime branch under jit)."""
    nbytes = x.size * jnp.dtype(x.dtype).itemsize
    if jnp.issubdtype(x.dtype, jnp.floating) \
            and quantized_eligible(int(nbytes), fabric):
        return quantized_allreduce(x, axis_name, block)
    from .collectives import allreduce

    return allreduce(x, axis_name)
