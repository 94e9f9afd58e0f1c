"""Attention kernels: dense reference, blockwise-scan, Pallas flash.

Three implementations of the same math (softmax(q·kᵀ/√d)·v, optionally
causal), in increasing tpu-nativeness:

  * :func:`dense_attention` — the O(s²)-memory reference used by tests and
    tiny models;
  * :func:`blockwise_attention` — online-softmax over key blocks via
    ``lax.scan`` with per-step rematerialisation (``jax.checkpoint``), so
    peak memory is O(s·block) while staying a single differentiable XLA
    program. Its per-block recurrence, :func:`online_softmax_fold`, is
    shared with ring attention
    (:mod:`mpi_tpu.parallel.ring_attention`);
  * :func:`flash_attention` — the Pallas TPU kernel: q/k/v tiles staged
    through VMEM, MXU matmuls with float32 accumulation, running
    (m, l, acc) online-softmax state in VMEM scratch across the key-block
    grid dimension. Backward is the FlashAttention-2 scheme in Pallas
    too: the forward saves only the log-sum-exp rows, and two kernels
    (dq over key blocks; dk/dv over query blocks) rebuild the
    probabilities on the fly — no O(s²) residuals.

All take ``q, k, v`` shaped ``(batch, seq, heads, head_dim)`` — the layout
:mod:`mpi_tpu.models.transformer` uses — and return the same shape. The
reference repo has no attention anywhere (it is a transport library); these
kernels are new tpu-first work layered on it.
"""

from __future__ import annotations

import functools
import os
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import trace

__all__ = ["dense_attention", "blockwise_attention", "flash_attention",
           "flash_attention_with_lse", "flash_chunk_bwd",
           "merge_attention_chunks", "online_softmax_fold", "NEG_INF"]

NEG_INF = -1e30  # finite mask value: keeps exp() well-defined everywhere
_NEG_INF = NEG_INF


def _scale(q):
    return 1.0 / math.sqrt(q.shape[-1])


def online_softmax_fold(q32, kc, vc, m, l, acc, scale, mask=None):
    """One step of the flash-attention recurrence, shared by
    :func:`blockwise_attention` and ring attention.

    ``q32`` is ``(b, h, s, d)`` float32; ``kc``/``vc`` are the visiting
    key/value chunk ``(b, h, t, d)``; ``(m, l, acc)`` is the running
    (row-max, normaliser, unnormalised output) state with shapes
    ``(b, h, s) / (b, h, s) / (b, h, s, d)``; ``mask`` is an optional
    ``(s, t)`` bool array (True = attend). Returns the updated state."""
    logits = jnp.einsum("bhsk,bhtk->bhst", q32,
                        kc.astype(jnp.float32)) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhst,bhtk->bhsk", p, vc.astype(jnp.float32))
    return m_new, l_new, acc_new


# --------------------------------------------------------------------------
# Dense reference
# --------------------------------------------------------------------------

def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True) -> jax.Array:
    """Materialised-logits attention; the correctness oracle."""
    logits = jnp.einsum("bshk,bthk->bhst", q, k) * _scale(q)
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhst,bthk->bshk", probs.astype(q.dtype), v)


# --------------------------------------------------------------------------
# Blockwise scan (differentiable, memory-light)
# --------------------------------------------------------------------------

def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        block_k: int = 128) -> jax.Array:
    """Online-softmax attention scanning over key blocks.

    One ``lax.scan`` step attends the full query tensor to one key/value
    block and folds the result into running ``(m, l, acc)`` state — the
    standard flash-attention recurrence. Each step is wrapped in
    ``jax.checkpoint`` so the backward pass recomputes the block instead
    of storing O(s²) probabilities.
    """
    b, s, h, d = q.shape
    t = k.shape[1]
    bk = min(block_k, t)
    if t % bk:  # pad keys; padded positions are masked out below
        pad = bk - t % bk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = k.shape[1] // bk
    # (b, s, h, d) -> per-block (nk, b, h, bk, d) for the shared fold
    kb = k.reshape(b, nk, bk, h, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, bk, h, d).transpose(1, 0, 3, 2, 4)

    scale = _scale(q)
    q32 = q.transpose(0, 2, 1, 3).astype(jnp.float32)  # (b, h, s, d)
    row_ids = lax.broadcasted_iota(jnp.int32, (s, bk), 0)

    @jax.checkpoint
    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, start = blk
        col_ids = start + lax.broadcasted_iota(jnp.int32, (s, bk), 1)
        valid = col_ids < t
        if causal:
            valid &= row_ids >= col_ids
        return online_softmax_fold(q32, kblk, vblk, m, l, acc, scale,
                                   mask=valid), None

    m0 = jnp.full((b, h, s), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    a0 = jnp.zeros((b, h, s, d), jnp.float32)
    starts = jnp.arange(nk, dtype=jnp.int32) * bk
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), (kb, vb, starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# --------------------------------------------------------------------------
# Pallas flash kernel
# --------------------------------------------------------------------------

def _block_mask(qi, ki, block_q: int, block_k: int, causal: bool,
                prefix=None):
    """The (block_q, block_k) validity mask for a *crossed* grid cell
    (qi, ki) (:func:`_cell_kind`; the other kinds never build it).

    ``prefix=(q_per, k_per)`` is the window-level mask EVA's summaries
    need (:mod:`mpi_tpu.ops.eva_attention`): rows come in groups of
    ``q_per``, columns in groups of ``k_per``, and a row sees the columns
    of strictly earlier groups, ``col // k_per < row // q_per``.
    ``block_q`` divides ``q_per`` (:func:`_prefix_end`), so the block's
    rows share one group and the test is one compare with a scalar.
    No ``col < seq_k`` term: the blocks divide the lengths
    (:func:`_blocks`), so no column lies past the keys."""
    col = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = None
    if causal:
        row = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = row >= col
    if prefix is not None:
        before = col < _prefix_end(qi, block_q, prefix)
        valid = before if valid is None else valid & before
    return valid


def _prefix_end(qi, block_q: int, prefix):
    """First column the rows of query block ``qi`` do NOT see under the
    prefix mask ``(q_per, k_per)``: their group's index times ``k_per``."""
    q_per, k_per = prefix
    assert q_per % block_q == 0, (q_per, block_q)
    return (qi * block_q) // q_per * k_per


def _cell_kind(qi, ki, block_q: int, block_k: int, causal: bool,
               prefix=None):
    """``(live, whole)`` for grid cell (qi, ki) of the three kernels:
    the cell is *dead* (``not live``) when the mask hides every entry,
    *whole* when it hides none, *crossed* (``live & ~whole``) otherwise.
    Decided from the cell's position alone, so ``qi``/``ki`` may be
    Python ints, numpy arrays (the census) or traced scalars (the
    kernels and their index maps). Causal: live iff the block's first
    column is at or before its last row, whole iff its first row is at
    or past its last column. Prefix: live iff the first column is before
    :func:`_prefix_end`, whole iff the last one is. With neither mask
    every cell is whole, and both come back as Python ``True``."""
    live = whole = True
    row0, col0 = qi * block_q, ki * block_k
    if causal:
        live = col0 <= row0 + (block_q - 1)
        whole = row0 >= col0 + (block_k - 1)
    if prefix is not None:
        end = _prefix_end(qi, block_q, prefix)
        live = (col0 < end) & live
        whole = (col0 + (block_k - 1) < end) & whole
    return live, whole


def _resident_ki(qi, ki, block_q: int, block_k: int, causal: bool, prefix):
    """The key block the forward and dq index maps name for cell
    (qi, ki): its own when the cell is live, else the last live one of
    the row (block 0 in a row with none), which is the block already
    resident when a dead cell comes up, so Pallas issues no copy."""
    if causal:
        ki = jnp.minimum(ki, (qi * block_q + (block_q - 1)) // block_k)
    if prefix is not None:
        end = _prefix_end(qi, block_q, prefix)
        ki = jnp.minimum(ki, jnp.maximum(end - 1, 0) // block_k)
    return ki


def _resident_qi(qi, ki, nq: int, block_q: int, block_k: int, causal: bool,
                 prefix):
    """The same for the dk/dv kernel, which walks a column of cells: the
    query block its q, dO, lse and delta maps name is the cell's own
    when live, else the column's first live one (the last block in a
    column with none)."""
    if causal:
        qi = jnp.maximum(qi, jnp.minimum(ki * block_k // block_q, nq - 1))
    if prefix is not None:
        q_per, k_per = prefix
        group = ki * block_k // k_per + 1  # first row group that sees ki
        qi = jnp.maximum(
            qi, jnp.minimum(group * (q_per // block_q), nq - 1))
    return qi


def _census(nq: int, nk: int, block_q: int, block_k: int, causal: bool,
            prefix):
    """``(dead, crossed, whole)`` cells of one head's ``nq x nk`` grid,
    from the function the kernels dispatch on."""
    live, whole = (np.broadcast_to(x, (nq, nk)) for x in _cell_kind(
        np.arange(nq)[:, None], np.arange(nk)[None, :], block_q, block_k,
        causal, prefix))
    return (int((~live).sum()), int((live & ~whole).sum()),
            int(whole.sum()))


def _count_cells(heads: int, nq: int, nk: int, block_q: int, block_k: int,
                 causal: bool, prefix) -> None:
    """Add one kernel call's grid-wide census to ``flash.cells.dead`` /
    ``.crossed`` / ``.whole`` (docs/OBSERVABILITY.md), at trace time.
    Silent with tracing off."""
    if not trace.enabled():
        return
    for kind, n in zip(("dead", "crossed", "whole"),
                       _census(nq, nk, block_q, block_k, causal, prefix)):
        trace.count(f"flash.cells.{kind}", heads * n)


def _for_cell_kind(qi, ki, block_q: int, block_k: int, causal: bool, prefix,
                   compute):
    """Emit ``compute(mask)`` once for each kind of cell that does work
    (:func:`_cell_kind`): for whole cells with ``mask=None`` (no iota,
    no compare, no ``where``), for crossed cells with the block's mask.
    Dead cells run nothing: that saves their MXU matmuls, their index
    maps save the fetch. Without a mask there is one kind and no branch."""
    if not causal and prefix is None:
        compute(None)
        return
    live, whole = _cell_kind(qi, ki, block_q, block_k, causal, prefix)
    pl.when(whole)(lambda: compute(None))
    pl.when(live & ~whole)(lambda: compute(
        _block_mask(qi, ki, block_q, block_k, causal, prefix)))


def _block_probs(q_ref, k_ref, lse_ref, mask, scale: float):
    """Backward-pass helper: rebuild this block's softmax probabilities
    from (q, k, lse) — the FlashAttention-2 trick that replaces O(s²)
    stored residuals. Returns (q, k) in their stored dtype (bf16 dots
    run the MXU at full rate; f32 casts would quarter it) and p in
    float32 (the exp must match the forward's f32 softmax state).
    ``mask`` is the crossed cell's, ``None`` in a whole cell."""
    q = q_ref[0]
    k = k_ref[0]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    p = jnp.exp(logits - lse_ref[0, 0][:, None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return q, k, p


def _flash_kernel_fwd_res(q_ref, k_ref, v_ref, o_ref, lse_ref,
                          m_scr, l_scr, acc_scr, *, causal: bool,
                          scale: float, block_q: int, block_k: int,
                          prefix=None):
    """Forward kernel that also emits the log-sum-exp rows — the only
    residual the backward kernels need (FlashAttention-2 scheme: softmax
    is reconstructed from (q, k, lse), never stored)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute(mask):
        # Inputs stay in their STORED dtype (bf16 on the flagship) so
        # the MXU runs at full bf16 rate; preferred_element_type keeps
        # the accumulation f32 — softmax state is always f32. Casting
        # to f32 first would quarter the matmul throughput on v5e for
        # identical accumulator precision.
        q = q_ref[0]                           # (block_q, d)
        k = k_ref[0]                           # (block_k, d)
        v = v_ref[0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if mask is not None:
            logits = jnp.where(mask, logits, _NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * corr + jnp.sum(p, axis=-1)
        m_scr[:, 0] = m_new
        acc_scr[:] = acc_scr[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_cell_kind(qi, ki, block_q, block_k, causal, prefix, compute)

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[:] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, 0] + jnp.log(l)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, causal: bool, scale: float,
                         block_q: int, block_k: int, prefix=None):
    """dq = Σ_k  ds·K  with ds = P ∘ (dP − δ), P rebuilt from (q, k, lse).
    Grid (bh, nq, nk): each (bh, qi) accumulates over the key blocks."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute(mask):
        v = v_ref[0]
        g = g_ref[0]
        _, k, p = _block_probs(q_ref, k_ref, lse_ref, mask, scale)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_cell_kind(qi, ki, block_q, block_k, causal, prefix, compute)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                          scale: float, block_q: int, block_k: int,
                          nq: int, prefix=None):
    """dv = Σ_q Pᵀ·dO and dk = Σ_q dsᵀ·Q. Grid (b·kv_heads, nk, G·nq):
    each (bh, ki) accumulates over the query blocks of EVERY query head
    in the kv head's group (G = n_heads / kv_heads; 1 for MHA) — the
    third grid axis enumerates (g, qi) pairs g-major, and the index
    maps point q/g/lse/delta at query head g of the group."""
    ki = pl.program_id(1)
    t = pl.program_id(2)
    qi = t % nq  # query-block index within the current group member
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute(mask):
        v = v_ref[0]
        g = g_ref[0]
        q, _, p = _block_probs(q_ref, k_ref, lse_ref, mask, scale)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # Query blocks that see nothing of this key block run no matmul.
    _for_cell_kind(qi, ki, block_q, block_k, causal, prefix, compute)

    @pl.when(t == nt - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pick_block(n: int, preferred: int) -> int:
    """Largest power-of-two ≤ preferred that divides n (n itself if none —
    one full block beats a degenerate 1-element grid)."""
    bsz = preferred
    while bsz > 1:
        if n % bsz == 0:
            return bsz
        bsz //= 2
    return n


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _env_flash_blocks():
    env = os.environ.get("MPI_TPU_FLASH_BLOCKS", "")
    if env:
        try:
            bq, sep, bk = env.partition(",")
            if not sep:
                raise ValueError("expected 'BQ,BK'")
            return [int(bq), int(bk)]
        except ValueError:
            import warnings

            # A bad env var must not kill every `import mpi_tpu`: warn
            # and fall back to the shipped default.
            warnings.warn(
                f"mpi_tpu: ignoring malformed MPI_TPU_FLASH_BLOCKS="
                f"{env!r} (expected 'BQ,BK', e.g. '1024,1024')",
                stacklevel=2)
    return [1024, 1024]


# Default (block_q, block_k) used when flash_attention is called with
# block sizes of None (every internal caller — transformer.py, ring
# attention chunks); :func:`_pick_block` shrinks them to divide a short
# sequence. Chosen on a v5e for the op alone, forward + backward, causal,
# bfloat16, at (2, 4096, 24 -> 2 kv heads, 128), ms a call (PERF.md, PR
# 32; the former default 256 x 512 took 12.53):
#
#     block_q \ block_k    128     256     512    1024
#         256            29.76   20.90   12.53   10.33
#         512            28.67   16.11    9.90    8.36
#        1024            23.07   15.94   10.02    8.05
#
# The matmuls run near the MXU's floor; what the blocks move is the rest,
# which follows the number of (query row, key block) pairs and of grid
# cells, not the scores' area: wide blocks win although they leave fewer
# cells dead or whole. Override with
# :func:`set_flash_block_defaults` (the ops.autotune sweep does) or
# MPI_TPU_FLASH_BLOCKS="bq,bk".
_flash_block_default = _env_flash_blocks()


def set_flash_block_defaults(block_q: int, block_k: int) -> None:
    """Set the process-wide default flash block sizes (autotuner
    output). Takes effect on the next trace; do not call between a
    step's forward and backward."""
    _flash_block_default[0] = int(block_q)
    _flash_block_default[1] = int(block_k)


def flash_block_defaults():
    """Current process-wide default ``(block_q, block_k)``."""
    return tuple(_flash_block_default)


# (seq_q, seq_k) -> (block_q, block_k): shape-exact winners from the
# autotune sweep, consulted at trace time BEFORE the global default —
# so tuning at one shape can never degrade flash calls at another
# (the sweep's winner at a short sequence is shrunk to divide it and
# would be a bad global choice).
_tuned_blocks: dict = {}


def register_tuned_blocks(seq_q: int, seq_k: int, block_q: int,
                          block_k: int) -> None:
    """Record the autotuned block grid for an exact (seq_q, seq_k)
    attention shape; default-block flash calls at that shape use it."""
    _tuned_blocks[(int(seq_q), int(seq_k))] = (int(block_q),
                                               int(block_k))


def _resolve_blocks(block_q, block_k, seq_q=None, seq_k=None):
    if block_q is None and block_k is None and seq_q is not None:
        hit = _tuned_blocks.get((seq_q, seq_k))
        if hit is not None:
            return hit
    return (_flash_block_default[0] if block_q is None else block_q,
            _flash_block_default[1] if block_k is None else block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention: Pallas TPU kernels, forward and backward.

    ``interpret=None`` auto-selects interpreter mode off-TPU so tests run
    on CPU against the same kernel code. Block sizes of ``None`` take
    the process-wide defaults (:func:`flash_block_defaults` — 1024x1024
    from a v5e sweep unless the :mod:`mpi_tpu.ops.autotune` sweep picked
    better for this shape);
    :func:`_pick_block` shrinks them to fit short sequences.
    """
    itp = _should_interpret() if interpret is None else interpret
    # Same kernel as the residual-saving forward; the (b*h, 1, s) lse
    # output is dead here and DCE'd by XLA.
    return _flash_fwd_res_pallas(q, k, v, causal, block_q, block_k,
                                 itp)[0]


def _gqa_layout(q, k, v):
    """Flattened-head layout shared by the kernels: queries as
    ``(b*h, s, d)``, k/v as ``(b*kv_heads, t, d)``, plus the index-map
    taking a flat query-head grid index to its kv head's flat index
    (query head i reads kv head ``i // group`` — the GQA convention;
    the map is the identity for MHA)."""
    b, s, h, d = q.shape
    t = k.shape[1]
    hk = k.shape[2]
    if h % hk or v.shape[2] != hk:
        raise ValueError(
            f"mpi_tpu: flash attention kv heads ({hk}/{v.shape[2]}) must "
            f"divide query heads ({h})")
    group = h // hk
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hk, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hk, t, d)

    def kv_index(bh):
        return (bh // h) * hk + (bh % h) // group

    return qf, kf, vf, kv_index, group


def _blocks(s: int, t: int, block_q, block_k, prefix):
    """The call's ``(block_q, block_k)``: the asked or default sizes
    shrunk to divisors of the two lengths and, under a prefix mask, of
    its row groups too, so no query block straddles two groups. That
    they divide is what lets the kernels drop the ``col < seq_k`` test."""
    block_q, block_k = _resolve_blocks(block_q, block_k, s, t)
    if prefix is None:
        bq = _pick_block(s, block_q)
    elif s % prefix[0]:
        raise ValueError(
            f"mpi_tpu: a prefix mask's row groups of {prefix[0]} must "
            f"divide the {s} query rows")
    else:
        bq = _pick_block(prefix[0], block_q)
    bk = _pick_block(t, block_k)
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    return bq, bk


def _kv_spec(bq: int, bk: int, d: int, kv_index, causal: bool, prefix):
    """k/v block of grid cell (bh, qi, ki) of the forward and dq kernels:
    query head ``bh``'s kv head, the key block :func:`_resident_ki`."""
    return pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (
        kv_index(bh), _resident_ki(qi, ki, bq, bk, causal, prefix), 0))


def _flash_fwd_res_pallas(q, k, v, causal, block_q, block_k, interpret,
                          prefix=None):
    """Forward + log-sum-exp residuals: (out, lse).

    ``out`` is ``(b, s, h, d)``; ``lse`` stays in the kernels'
    ``(b*h, 1, s)`` row layout (the singleton middle dim satisfies
    Mosaic's trailing-two-dims tiling rule) — exactly what the backward
    row specs consume. k/v may carry fewer (grouped/GQA) heads; the
    kernel reads each kv head once per query head via the index map —
    nothing is materialised group-times larger."""
    b, s, h, d = q.shape
    t = k.shape[1]
    bq, bk = _blocks(s, t, block_q, block_k, prefix)
    qf, kf, vf, kv_index, _ = _gqa_layout(q, k, v)
    grid = (b * h, s // bq, t // bk)
    _count_cells(*grid, bq, bk, causal, prefix)
    kernel = functools.partial(
        _flash_kernel_fwd_res, causal=causal, scale=_scale(q), block_q=bq,
        block_k=bk, prefix=prefix)
    kspec = _kv_spec(bq, bk, d, kv_index, causal, prefix)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            kspec,
            kspec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            # Rows live as (bh, 1, s) so the block's trailing two dims are
            # (1, bq) with the middle dim equal to the array's — the shape
            # Mosaic's (8, 128) tiling rule accepts for per-row vectors.
            pl.BlockSpec((1, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd" if prefix is None else "eva_remote_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3), lse


def _flash_bwd_pallas(q, k, v, out, lse, g, causal, block_q, block_k,
                      interpret, prefix=None):
    """FlashAttention-2 backward: two Pallas passes (dq over key blocks;
    dk/dv over query blocks), probabilities rebuilt from lse — no O(s²)
    residuals, float32 accumulation throughout. Grouped (GQA) k/v are
    handled natively: dq reads each kv head through the group index
    map, and the dk/dv grid enumerates every (group member, query
    block) pair so the per-kv-head scratch accumulates the whole
    group's contributions before one write."""
    b, s, h, d = q.shape
    t = k.shape[1]
    hk = k.shape[2]
    bq, bk = _blocks(s, t, block_q, block_k, prefix)
    qf, kf, vf, kv_index, group = _gqa_layout(q, k, v)
    gf = g.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    of = out.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    # δ_i = Σ_d dO_i·O_i — cheap elementwise reduction; XLA fuses it.
    # Same (bh, 1, s) row layout as lse (see _flash_fwd_res_pallas).
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    -1)[:, None, :]

    common = dict(causal=causal, scale=_scale(q), block_q=bq, block_k=bk,
                  prefix=prefix)
    nq, nk = s // bq, t // bk
    qspec = pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0))
    kspec = _kv_spec(bq, bk, d, kv_index, causal, prefix)
    rowspec = pl.BlockSpec((1, 1, bq), lambda bh, qi, ki: (bh, 0, qi))

    _count_cells(b * h, nq, nk, bq, bk, causal, prefix)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(b * h, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq" if prefix is None else "eva_remote_bwd_dq",
    )(qf, kf, vf, gf, lse, delta)

    # dk/dv: grid (b*hk, nk, group*nq) — ki owns the accumulation, the
    # third axis walks the group's query heads g-major so the scratch
    # gathers all of them; index maps send q/g/lse/delta at group
    # member g's flat query head, at the query block a dead cell finds
    # resident (the same cells as dq's, walked by column: counted again).
    def q_head(bh, gq):
        return (bh // hk) * h + (bh % hk) * group + gq // nq

    def q_block(ki, gq):
        return _resident_qi(gq % nq, ki, nq, bq, bk, causal, prefix)

    qspec2 = pl.BlockSpec(
        (1, bq, d), lambda bh, ki, gq: (q_head(bh, gq), q_block(ki, gq), 0))
    kspec2 = pl.BlockSpec((1, bk, d), lambda bh, ki, gq: (bh, ki, 0))
    rowspec2 = pl.BlockSpec(
        (1, 1, bq), lambda bh, ki, gq: (q_head(bh, gq), 0, q_block(ki, gq)))
    _count_cells(b * h, nq, nk, bq, bk, causal, prefix)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, **common),
        grid=(b * hk, nk, group * nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((b * hk, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * hk, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv" if prefix is None else "eva_remote_bwd_dkv",
    )(qf, kf, vf, gf, lse, delta)

    unflat_q = lambda x: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)  # noqa: E731
    unflat_kv = lambda x: x.reshape(b, hk, t, d).transpose(0, 2, 1, 3)  # noqa: E731
    return unflat_q(dq), unflat_kv(dk), unflat_kv(dv)


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             prefix=None):
    """Forward flash attention that also returns the per-row log-sum-exp.

    ``(out, lse)`` with ``out`` shaped like ``q`` and ``lse`` ``(b, h, s)``
    float32. Attention over a *subset* of keys composes exactly from
    (out, lse) pairs (:func:`merge_attention_chunks`) — the primitive ring
    attention builds on: each ring step runs this kernel on the visiting
    kv chunk and merges. Forward-only (no vjp is registered here); ring
    attention supplies its own backward via :func:`flash_chunk_bwd`.

    ``prefix=(q_per, k_per)`` masks at the level of groups: query row
    ``i`` sees key ``j`` iff ``j // k_per < i // q_per`` (EVA's chunk
    summaries of earlier windows, :mod:`mpi_tpu.ops.eva_attention`). A
    row that sees nothing comes back as zeros with ``lse`` ~ NEG_INF,
    which :func:`merge_attention_chunks` gives no weight."""
    itp = _should_interpret() if interpret is None else interpret
    b, s, h, d = q.shape
    out, lse = _flash_fwd_res_pallas(q, k, v, causal, block_q, block_k, itp,
                                     prefix)
    return out, lse.reshape(b, h, s)


def merge_attention_chunks(o1, lse1, o2, lse2):
    """Combine two attention results over disjoint key sets.

    ``o``: (b, s, h, d) normalized outputs; ``lse``: (b, h, s) float32.
    Returns the merged (o, lse). Rows that attended nothing anywhere
    (lse ~ NEG_INF on both sides) stay zero, matching the masked-fold
    convention."""
    lse_m = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse_m).transpose(0, 2, 1)[..., None]
    w2 = jnp.exp(lse2 - lse_m).transpose(0, 2, 1)[..., None]
    o = o1.astype(jnp.float32) * w1 + o2.astype(jnp.float32) * w2
    return o.astype(o1.dtype), lse_m


def flash_chunk_bwd(q, k, v, out, lse, g, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None, prefix=None):
    """FA-2 backward for ONE (query-chunk, kv-chunk) pair against the
    *global* softmax: ``out``/``lse`` are the full-attention result rows
    (after every chunk was merged), so the rebuilt probabilities
    ``exp(qk - lse)`` are the true global ones and the returned
    ``(dq, dk, dv)`` are this pair's exact additive contributions. Ring
    attention calls this once per ring step; EVA once for the window's
    own keys and once, under ``prefix``, for the summaries."""
    itp = _should_interpret() if interpret is None else interpret
    b, s, h, _ = q.shape
    return _flash_bwd_pallas(q, k, v, out, lse.reshape(b * h, 1, s), g,
                             causal, block_q, block_k, itp, prefix)


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    itp = _should_interpret() if interpret is None else interpret
    out, lse = _flash_fwd_res_pallas(q, k, v, causal, block_q, block_k, itp)
    # Named for a block under ``remat`` (models/transformer.py,
    # ``_REMAT_KEEPS``): held, they spare the backward this kernel's second
    # run. Outside a checkpoint a name is the identity.
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    itp = _should_interpret() if interpret is None else interpret
    return _flash_bwd_pallas(q, k, v, out, lse, g, causal, block_q,
                             block_k, itp)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
