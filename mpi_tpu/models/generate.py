"""Autoregressive generation with a KV cache — the flagship's inference path.

tpu-first decode: the cache is a preallocated ``(layers, batch, max_seq,
heads, head_dim)`` pair updated in place with ``dynamic_update_slice`` (no
shape growth — one compiled step serves every position), the per-step
attention is one masked dot against the full cache (MXU-shaped, masked by
position), and the whole generation loop is a single ``lax.scan`` under
``jit`` — no host round-trips per token. Prefill computes the prompt's
cache in one batched forward pass.

No reference analogue (btracey/mpi has no models, SURVEY.md §2) — this is
framework-completeness work: train (`make_train_step`) and serve
(`generate`) cover the model lifecycle.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .quant import embed_lookup, logits_matmul
from .transformer import TransformerConfig, _ffn, _layernorm, apply_rope

__all__ = ["prefill", "decode_step", "generate"]


def _proj_qkv(x, blk, cfg, n_valid):
    """q/k/v projections for tokens starting at absolute position
    ``n_valid``; under rope, q and k are rotated by their positions HERE
    — k enters the cache already rotated, so cached entries never need
    re-rotation as decode advances."""
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, blk["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, blk["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, blk["wv"].astype(dtype))
    if cfg.rope:
        pos = n_valid + jnp.arange(x.shape[1], dtype=jnp.int32)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


def _attend_cached(q, k_cache, v_cache, n_valid, cfg):
    """q: (b, s_q, h, hd) attends to cache positions [0, n_valid + s_q)
    with causal offsets; cache: (b, max_seq, kv_heads, hd).

    GQA stays *grouped* through the contraction — queries reshape to
    (b, s, kv, group, hd) and each kv head is read once per step rather
    than materialised group x larger, so decode keeps GQA's bandwidth
    and peak-memory win (the point of the smaller cache)."""
    b, s_q, h, hd = q.shape
    if cfg.decode_attention not in ("dense", "flash"):
        # Same loud-unknown stance as attention_impl: silently falling
        # back would hide a misconfiguration on the hot path.
        raise ValueError(
            f"mpi_tpu: unknown decode_attention "
            f"{cfg.decode_attention!r}: expected dense|flash")
    if s_q == 1 and cfg.decode_attention == "flash":
        # One-query steps take the fused Pallas path: a single VMEM
        # pass over the cache with online softmax, GQA-native.
        from ..ops.decode_attention import flash_decode_attention

        out = flash_decode_attention(q[:, 0], k_cache, v_cache,
                                     jnp.asarray(n_valid, jnp.int32))
        return out[:, None]
    kv = cfg.kv_heads
    group = h // kv
    qg = q.reshape(b, s_q, kv, group, hd)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    logits = jnp.einsum("bsKgk,btKk->bKgst", qg, k_cache) * scale
    t = k_cache.shape[1]
    # query i sits at absolute position n_valid + i; it may see cache
    # columns 0 .. n_valid + i.
    rows = n_valid + lax.broadcasted_iota(jnp.int32, (s_q, t), 0)
    cols = lax.broadcasted_iota(jnp.int32, (s_q, t), 1)
    logits = jnp.where((cols <= rows)[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    ctx = jnp.einsum("bKgst,btKk->bsKgk", probs.astype(q.dtype), v_cache)
    return ctx.reshape(b, s_q, h, hd)


def _forward_cached(params, tokens, cache, n_valid, cfg: TransformerConfig):
    """Run ``tokens`` (b, s) starting at absolute position ``n_valid``,
    writing their k/v into the cache. Returns (logits, new_cache)."""
    b, s = tokens.shape
    _check_cfg(cfg)
    x = embed_lookup(params["embed"], tokens, cfg.dtype)
    if not cfg.rope:
        pos_emb = lax.dynamic_slice_in_dim(
            params["pos"].astype(cfg.dtype), n_valid, s, axis=0)
        x = x + pos_emb[None]
    new_cache = []
    for i, blk in enumerate(params["blocks"]):
        h = _layernorm(x, blk["ln1"]["scale"].astype(x.dtype),
                       blk["ln1"]["bias"].astype(x.dtype))
        q, k, v = _proj_qkv(h, blk, cfg, n_valid)
        k_cache = lax.dynamic_update_slice_in_dim(
            cache[i][0], k, n_valid, axis=1)
        v_cache = lax.dynamic_update_slice_in_dim(
            cache[i][1], v, n_valid, axis=1)
        new_cache.append((k_cache, v_cache))
        ctx = _attend_cached(q, k_cache, v_cache, n_valid, cfg)
        x = x + jnp.einsum("bshk,hkd->bsd", ctx, blk["wo"].astype(x.dtype))
        h = _layernorm(x, blk["ln2"]["scale"].astype(x.dtype),
                       blk["ln2"]["bias"].astype(x.dtype))
        y, _ = _ffn(h, blk, cfg, mesh=None)  # aux loss is a train concern
        x = x + y
    x = _layernorm(x, params["final_ln"]["scale"].astype(x.dtype),
                   params["final_ln"]["bias"].astype(x.dtype))
    logits = logits_matmul(x, params["embed"])
    return logits, new_cache


def _check_cfg(cfg: TransformerConfig) -> None:
    """Refuse, by name, what the cached forward pass below cannot do."""
    if cfg.attention_impl == "eva":
        raise NotImplementedError(
            "mpi_tpu: generate cannot decode attention_impl='eva': it "
            "needs a cache of the current window's keys and values beside "
            "the chunk summaries (K_c, V_c) of every earlier window, and "
            "this cache holds one key and value per position; train with "
            "make_train_step, score with forward")
    beyond = cfg.beyond_classic_block()
    if beyond:
        raise NotImplementedError(
            f"mpi_tpu: generate does not handle {', '.join(beyond)}: its "
            f"cached forward pass norms, sums and projects the classic "
            f"block's way")


def _empty_cache(cfg: TransformerConfig, batch: int):
    # kv_heads, not n_heads: GQA shrinks the cache by the group factor.
    shape = (batch, cfg.max_seq, cfg.kv_heads, cfg.head_dim)
    return [(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
            for _ in range(cfg.n_layers)]


def prefill(params, prompt: jax.Array, cfg: TransformerConfig):
    """Batched prompt pass. Returns (last_logits (b, vocab), cache)."""
    cache = _empty_cache(cfg, prompt.shape[0])
    logits, cache = _forward_cached(params, prompt, cache, 0, cfg)
    return logits[:, -1], cache


def decode_step(params, token: jax.Array, cache, n_valid,
                cfg: TransformerConfig):
    """One incremental step: ``token`` (b,) at absolute position
    ``n_valid``. Returns (logits (b, vocab), new_cache)."""
    logits, cache = _forward_cached(params, token[:, None], cache,
                                    n_valid, cfg)
    return logits[:, 0], cache


def generate(params, prompt: jax.Array, cfg: TransformerConfig,
             max_new_tokens: int, temperature: float = 0.0,
             key: Optional[jax.Array] = None) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` (b, s).

    ``temperature == 0`` is greedy argmax; otherwise samples from the
    tempered softmax (requires ``key``). The decode loop is one
    ``lax.scan`` — jit-compatible end to end. Returns (b, max_new_tokens).
    """
    if prompt.shape[1] + max_new_tokens > cfg.max_seq:
        raise ValueError(
            f"mpi_tpu: prompt {prompt.shape[1]} + {max_new_tokens} new "
            f"tokens exceeds max_seq {cfg.max_seq}")
    if temperature > 0 and key is None:
        raise ValueError("mpi_tpu: sampling (temperature > 0) needs a key")
    last_logits, cache = prefill(params, prompt, cfg)
    if key is None:
        key = jax.random.PRNGKey(0)  # unused in greedy mode

    def pick(logits, k):
        if temperature > 0:
            return jax.random.categorical(k, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    def step(carry, k):
        logits, cache, n_valid = carry
        tok = pick(logits, k)
        new_logits, cache = decode_step(params, tok, cache, n_valid, cfg)
        return (new_logits, cache, n_valid + 1), tok

    keys = jax.random.split(key, max_new_tokens)
    (_, _, _), toks = lax.scan(
        step, (last_logits, cache, jnp.int32(prompt.shape[1])), keys)
    return toks.T  # (b, max_new_tokens)
