"""EVA attention: exact softmax over the query's own window, one pooled
summary per chunk of every earlier window, one joint softmax over both.

"Efficient Attention via Control Variates" (Zheng, Yuan, Wang, Kong, ICLR
2023, arXiv:2302.04542) in the deterministic form EvaByte's released
modelling code uses. Per head, with ``d`` the head size, ``s = d**-0.5``,
windows of ``W`` positions and chunks of ``C`` (``W % C == 0``), ``q, k``
already rotated, ``phi, mu`` two learned vectors of ``d`` a head:

  * chunk ``c`` holds positions ``cC .. (c+1)C - 1``; its pooling weights
    are ``a_cj = softmax_j(s * k_j . phi)`` over its own positions, its
    summary key ``K_c = sum_j a_cj k_j + mu``, its summary value
    ``V_c = sum_j a_cj v_j`` (:func:`eva_summaries`);
  * query ``i`` of window ``w = i // W`` sees the keys ``wW <= j <= i`` of
    its own window and the summaries ``c < w * W/C`` of every chunk of
    every earlier window, none of its own window's;
  * ``o_i = (sum_j e^{s q_i.k_j} v_j + sum_c e^{s q_i.K_c} V_c) / Z_i``
    with ``Z_i`` the sum of the same exponentials: one softmax over both.

So with ``seq <= W`` this is causal softmax attention, and with ``C = 1``
and ``mu = 0`` every summary is its token and it is full causal attention.

Two forms of the same math, same ``(batch, seq, heads, head_dim)`` layout
as :mod:`mpi_tpu.ops.attention`:

  * ``impl="jnp"`` — materialised float32 scores over the ``seq + seq/C``
    keys; the oracle for tests and tiny models;
  * ``impl="flash"`` (the default) — no ``(heads, seq, keys)`` tensor ever
    reaches HBM. The windows are folded into the batch and run through the
    causal flash kernels (:func:`flash_attention_with_lse`); a second pass
    of the same kernels reads the ``seq/C`` summaries under the window-level
    ``prefix`` mask; :func:`merge_attention_chunks` joins the two by their
    log-sum-exps. The backward sends the merged output and log-sum-exp into
    both passes (:func:`flash_chunk_bwd`), so each rebuilds the joint
    softmax's probabilities, and adds the two ``dq``. The pooling is plain
    ``jnp`` on either side of the kernels and differentiated by JAX.

Device ops carry ``jax.named_scope("eva")`` with ``eva.summarize``,
``eva.local``, ``eva.remote`` and ``eva.merge`` inside it
(docs/OBSERVABILITY.md); the summaries' Pallas calls are named
``eva_remote_fwd`` / ``eva_remote_bwd``, the window's keep ``flash_fwd`` /
``flash_bwd``. At the EvaByte cell's shapes each pass's backward is that one
fused kernel; over its VMEM budget it would be the split pair,
``*_bwd_dq`` / ``*_bwd_dkv`` (``ops/attention.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .attention import (NEG_INF, _scale, flash_attention_with_lse,
                        flash_chunk_bwd, merge_attention_chunks)

__all__ = ["eva_attention", "eva_summaries"]


def _check(q, window: int, chunk: int):
    s = q.shape[1]
    if window % chunk:
        raise ValueError(
            f"mpi_tpu: eva window {window} is not a multiple of the chunk "
            f"{chunk}")
    if s % chunk or (s > window and s % window):
        raise ValueError(
            f"mpi_tpu: eva attention needs a sequence of whole chunks and, "
            f"past one window, of whole windows: got seq {s} with window "
            f"{window}, chunk {chunk}")


def eva_summaries(k, v, phi, mu, chunk: int):
    """``(K, V)``, one row a chunk: ``k, v`` ``(b, s, h, d)`` ->
    ``(b, s / chunk, h, d)``; ``phi, mu`` ``(h, d)``. Pooling weights in
    float32, the summaries in ``k``'s dtype."""
    b, s, h, d = k.shape
    kc = k.reshape(b, s // chunk, chunk, h, d)
    vc = v.reshape(b, s // chunk, chunk, h, d)
    score = jnp.einsum("bnchd,hd->bnch", kc.astype(jnp.float32),
                       phi.astype(jnp.float32)) * _scale(k)
    a = jax.nn.softmax(score, axis=2)
    pooled_k = jnp.einsum("bnch,bnchd->bnhd", a, kc.astype(jnp.float32))
    pooled_v = jnp.einsum("bnch,bnchd->bnhd", a, vc.astype(jnp.float32))
    return ((pooled_k + mu.astype(jnp.float32)).astype(k.dtype),
            pooled_v.astype(v.dtype))


def _eva_jnp(q, k, v, ks, vs, window: int, chunk: int):
    """Materialised scores over the ``s`` tokens and ``s / chunk``
    summaries, one softmax over both."""
    s, n = q.shape[1], ks.shape[1]
    row = jnp.arange(s)[:, None]
    col = jnp.arange(s)[None, :]
    local = (col // window == row // window) & (col <= row)
    remote = jnp.arange(n)[None, :] // (window // chunk) < row // window
    logits = jnp.concatenate(
        [jnp.einsum("bshk,bthk->bhst", q, k),
         jnp.einsum("bshk,bnhk->bhsn", q, ks)], axis=-1) * _scale(q)
    mask = jnp.concatenate([local, remote], axis=-1)
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhst,bthk->bshk", probs.astype(q.dtype),
                      jnp.concatenate([v, vs], axis=1))


def _fold(x, window: int):
    """Windows into the batch: ``(b, s, h, d) -> (b * s/W, W, h, d)``."""
    b, s, h, d = x.shape
    return x.reshape(b * (s // window), window, h, d)


def _fold_rows(lse, window: int):
    """The same for per-row statistics: ``(b, h, s) -> (b * s/W, h, W)``."""
    b, h, s = lse.shape
    return lse.reshape(b, h, s // window, window).transpose(
        0, 2, 1, 3).reshape(b * (s // window), h, window)


def _unfold_rows(lse, batch: int):
    bw, h, window = lse.shape
    return lse.reshape(batch, bw // batch, h, window).transpose(
        0, 2, 1, 3).reshape(batch, h, (bw // batch) * window)


def _prefix(window: int, chunk: int):
    return (window, window // chunk)


# (block_q, block_k) of the two passes, shrunk by the kernels to divide a
# short window. Chosen on a v5e for each pass alone, forward + backward,
# bfloat16, 32 heads of 128 (PERF.md, PR 32; PR 29 had chosen the same two
# from the whole op). ms a call, block_q down, block_k 128 / 256 / 512 /
# 1024 across:
#
#   the window's keys, causal,         the 1,024 summaries of 16,384 rows,
#   8 windows of 2,048 in the batch    prefix mask (2048, 128)
#    256   49.82  35.49  23.48  19.91    256   20.65  16.45  11.85  10.98
#    512   47.44  28.16  18.65  16.66    512   18.56  12.63   9.80   9.38
#   1024   40.43  28.84  19.40  16.22   1024   14.17  11.46   9.16   9.31
_LOCAL_BLOCKS = dict(block_q=1024, block_k=1024)
_REMOTE_BLOCKS = dict(block_q=1024, block_k=512)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _eva_flash(q, k, v, ks, vs, window: int, chunk: int,
               interpret: Optional[bool]):
    return _eva_flash_fwd(q, k, v, ks, vs, window, chunk, interpret)[0]


def _eva_flash_fwd(q, k, v, ks, vs, window, chunk, interpret):
    b, s = q.shape[:2]
    with jax.named_scope("eva.local"):
        out, lse = flash_attention_with_lse(
            _fold(q, window), _fold(k, window), _fold(v, window), True,
            interpret=interpret, **_LOCAL_BLOCKS)
        out, lse = out.reshape(q.shape), _unfold_rows(lse, b)
    if s > window:
        with jax.named_scope("eva.remote"):
            out_r, lse_r = flash_attention_with_lse(
                q, ks, vs, False, interpret=interpret,
                prefix=_prefix(window, chunk), **_REMOTE_BLOCKS)
        with jax.named_scope("eva.merge"):
            out, lse = merge_attention_chunks(out, lse, out_r, lse_r)
    # Named for a block under ``remat`` (models/transformer.py,
    # ``_REMAT_KEEPS``): held, they spare the backward both forward passes
    # and the merge. Outside a checkpoint a name is the identity.
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, ks, vs, out, lse)


def _eva_flash_bwd(window, chunk, interpret, res, g):
    q, k, v, ks, vs, out, lse = res
    s = q.shape[1]
    with jax.named_scope("eva.local"):
        dq, dk, dv = flash_chunk_bwd(
            _fold(q, window), _fold(k, window), _fold(v, window),
            _fold(out, window), _fold_rows(lse, window), _fold(g, window),
            True, interpret=interpret, **_LOCAL_BLOCKS)
        dq, dk, dv = (x.reshape(q.shape) for x in (dq, dk, dv))
    if s <= window:
        return dq, dk, dv, jnp.zeros_like(ks), jnp.zeros_like(vs)
    with jax.named_scope("eva.remote"):
        dq_r, dks, dvs = flash_chunk_bwd(
            q, ks, vs, out, lse, g, False, interpret=interpret,
            prefix=_prefix(window, chunk), **_REMOTE_BLOCKS)
    with jax.named_scope("eva.merge"):
        dq = dq + dq_r
    return dq, dk, dv, dks, dvs


_eva_flash.defvjp(_eva_flash_fwd, _eva_flash_bwd)


def eva_attention(q, k, v, phi, mu, window: int, chunk: int,
                  impl: str = "flash",
                  interpret: Optional[bool] = None):
    """EVA attention of ``q, k, v`` ``(b, s, h, d)`` with the pooling
    vectors ``phi, mu`` ``(h, d)``; see the module docstring. ``seq`` is a
    multiple of ``chunk`` and, past one window, of ``window``.
    ``interpret=None`` runs the kernels in interpreter mode off the TPU."""
    _check(q, window, chunk)
    window = min(window, q.shape[1])
    with jax.named_scope("eva"):
        with jax.named_scope("eva.summarize"):
            ks, vs = eva_summaries(k, v, phi, mu, chunk)
            ks = checkpoint_name(ks, "eva_ks")
            vs = checkpoint_name(vs, "eva_vs")
        if impl == "jnp":
            return _eva_jnp(q, k, v, ks, vs, window, chunk)
        if impl != "flash":
            raise ValueError(
                f"mpi_tpu: unknown eva impl {impl!r}: expected flash|jnp")
        return _eva_flash(q, k, v, ks, vs, window, chunk, interpret)
