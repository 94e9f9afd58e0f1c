"""Pallas flash-decode kernel — fused single-query attention over the
KV cache.

Decode-time attention is the long-context serving hot op: one query
position against the whole cache, every step. The unfused path
materialises (heads, t) logits and probabilities between HBM-visible
ops; this kernel streams the cache through VMEM once per step with an
online softmax (the FlashAttention recurrence, specialised to s_q = 1),
so per-token attention cost is one read of K and V and nothing else —
the op is purely bandwidth-bound, which is exactly what the roofline
says it should be. The caches are consumed IN PLACE in their storage
layout (b, t, kv, hd) via the block index map — no transpose/reshape
copy of the full cache per step, which would have doubled the traffic
the kernel exists to minimise.

Grouped-query layout is native: the kernel's "rows" are the ``group =
n_heads / kv_heads`` queries that share one kv head, so each K/V tile
is read once per kv head (GQA's bandwidth win carries into the kernel;
rows are padded up to the TPU sublane multiple when the group is
small). The cache's dead tail — positions past ``n_valid`` — is
masked, and whole key blocks past it skip their matmuls entirely
(``pl.when``), so compute tracks the LIVE cache length even though
shapes stay static.

Used by the decode path when ``TransformerConfig.decode_attention =
"flash"`` (models/generate.py); the dense jnp path remains the default
and the correctness oracle. Off-TPU the kernel runs in interpreter
mode, so tests cover it everywhere. No reference analogue (btracey/mpi
has no models).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, _pick_block, _should_interpret

__all__ = ["flash_decode_attention"]

_MIN_ROWS = 8  # TPU f32 sublane multiple; small GQA groups pad up


def _decode_kernel(n_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, block_k: int,
                   t: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    n_valid = n_ref[0, 0]

    # Key blocks wholly past the live cache contribute nothing: skip
    # both matmuls (the online-softmax state is untouched, which is the
    # correct skip semantics).
    @pl.when(ki * block_k <= n_valid)
    def _():
        # Stored dtype in, f32 accumulation out: bf16 dots run the MXU
        # at full rate (an f32 upcast first would quarter throughput
        # for the same f32 accumulator); softmax state stays f32.
        q = q_ref[0, 0]                            # (rows, d)
        k = k_ref[0, :, 0]                         # (block_k, d)
        v = v_ref[0, :, 0]
        logits = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        col = ki * block_k + lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        valid = (col <= n_valid) & (col < t)
        logits = jnp.where(valid, logits, NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * corr + jnp.sum(p, axis=-1)
        m_scr[:, 0] = m_new
        acc_scr[:] = acc_scr[:] * corr[:, None] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l[:, None]).astype(o_ref.dtype)
        # log-sum-exp rows: what cache-parallel decode needs to merge
        # shard partials exactly (parallel/cache_parallel.py). A shard
        # whose live prefix is empty reports ~-1e30, which the merge
        # weights to zero.
        lse_ref[0, 0, 0] = m_scr[:, 0] + jnp.log(l)


def flash_decode_attention(q: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, n_valid: jax.Array,
                           block_k: int = 512,
                           interpret: Optional[bool] = None,
                           with_lse: bool = False):
    """Single-position attention against the cache.

    ``q``: (b, h, hd) — the one decode position's queries;
    ``k_cache``/``v_cache``: (b, t, kv, hd) with ``h % kv == 0``;
    ``n_valid``: scalar int32, the query's absolute position (it
    attends to cache columns ``0 .. n_valid`` inclusive — its own k/v
    must already be written at column ``n_valid``). Returns (b, h, hd)
    in the query dtype; with ``with_lse=True`` additionally the
    float32 (b, h) log-sum-exp rows — the sufficient statistic for
    merging shard partials in cache-parallel decode."""
    b, h, hd = q.shape
    _, t, kv, _ = k_cache.shape
    if h % kv:
        raise ValueError(f"mpi_tpu: n_heads {h} not divisible by "
                         f"kv_heads {kv}")
    group = h // kv
    rows = max(group, _MIN_ROWS)
    itp = _should_interpret() if interpret is None else interpret
    # A divisor block size (like the flash kernel's _pick_block) keeps
    # the cache operand un-padded — padding it would copy the whole
    # cache every step.
    bk = _pick_block(t, min(block_k, t))
    nk = t // bk
    scale = 1.0 / math.sqrt(hd)
    n_arr = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)

    # Only the tiny per-step q is re-laid-out; the caches stay in their
    # storage layout and are tiled in place by the index maps.
    qg = q.reshape(b, kv, group, hd)
    if rows != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - group), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=bk, t=t),
        grid=(b, kv, nk),
        in_specs=[
            # Scalar in SMEM: it feeds the pl.when block-skip predicate,
            # and scalar control flow is what SMEM is for (a VMEM load
            # is not a reliable predicate source under Mosaic).
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, rows, hd),
                         lambda bi, kvi, ki: (bi, kvi, 0, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda bi, kvi, ki: (bi, ki, kvi, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda bi, kvi, ki: (bi, ki, kvi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rows, hd),
                         lambda bi, kvi, ki: (bi, kvi, 0, 0)),
            # lse rows live as (b, kv, 1, rows): the block's trailing
            # two dims (1, rows) fit Mosaic's tiling rule (same layout
            # trick as the flash kernel's lse output).
            pl.BlockSpec((1, 1, 1, rows),
                         lambda bi, kvi, ki: (bi, kvi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, rows, hd), q.dtype),
            jax.ShapeDtypeStruct((b, kv, 1, rows), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
        interpret=itp,
    )(n_arr, qg, k_cache, v_cache)

    out, lse = out
    res = out[:, :, :group].reshape(b, h, hd)
    if not with_lse:
        return res
    return res, lse[:, :, 0, :group].reshape(b, h)
