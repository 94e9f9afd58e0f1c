"""Plain reference: the decoder-only language model's next-token loss.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision:
no kernel, no cache, no sharding, no code of the program under test. It
reads the program's parameter tree (``embed``, ``pos``, ``final_ln``,
``blocks[i]`` with ``ln1 ln2 wq wk wv wo w1 w2``) and follows the
published block the configurations name: pre-norm residual blocks,
LayerNorm with bias (eps 1e-5), causal softmax attention with grouped
k/v heads, rotary embedding on half-split pairs (the GPT-NeoX layout the
StarCoder2 and Hugging Face implementations use) or a learned position
table, a two-matrix tanh-GELU feed-forward, and logits through the tied
embedding. Departures from the sources are the configuration files'
``changed`` lists (no biases on the linear layers).

Attention is computed one k/v group at a time, so that at seq 4096 the
float32 scores never take more than ``heads/kv_heads x s x s`` at once.
Each block and each group is a ``jax.checkpoint``: nothing changes in the
values, and ``jax.grad`` of the loss (the gradient oracle) keeps one
group's scores at a time instead of every layer's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def _layernorm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"].astype(_F32) \
        + p["bias"].astype(_F32)


def _rope(x, theta):
    """x: (s, heads, hd). Rotate the pair (i, i + hd/2) by pos * theta^(-2i/hd)."""
    s, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = jnp.arange(s, dtype=_F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(h, blk, model):
    s = h.shape[0]
    wq, wk, wv, wo = (blk[n].astype(_F32) for n in ("wq", "wk", "wv", "wo"))
    q = jnp.einsum("sd,dhk->shk", h, wq)
    k = jnp.einsum("sd,dhk->shk", h, wk)
    v = jnp.einsum("sd,dhk->shk", h, wv)
    if model.get("rope"):
        theta = model.get("rope_theta", 10000.0)
        q, k = _rope(q, theta), _rope(k, theta)
    heads, hd = q.shape[1], q.shape[2]
    kv = k.shape[1]
    group = heads // kv
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one_group(args):
        qg, kg, vg = args            # (group, s, hd), (s, hd), (s, hd)
        scores = jnp.einsum("gqk,tk->gqt", qg, kg) / math.sqrt(hd)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("gqt,tk->gqk", jax.nn.softmax(scores, -1), vg)

    qg = q.transpose(1, 0, 2).reshape(kv, group, s, hd)
    ctx = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2)))
    ctx = ctx.reshape(heads, s, hd).transpose(1, 0, 2)
    return jnp.einsum("shk,hkd->sd", ctx, wo)


def sequence_loss(params, tokens, model):
    """Mean next-token cross-entropy of ONE sequence ``tokens`` (s + 1,)."""

    @jax.checkpoint
    def block(x, blk):
        x = x + _attention(_layernorm(x, blk["ln1"]), blk, model)
        h = _layernorm(x, blk["ln2"])
        h = jax.nn.gelu(h @ blk["w1"].astype(_F32), approximate=True)
        return x + h @ blk["w2"].astype(_F32)

    with jax.default_matmul_precision("highest"):
        inp, tgt = tokens[:-1], tokens[1:]
        emb = params["embed"].astype(_F32)
        x = emb[inp]
        if not model.get("rope"):
            x = x + params["pos"].astype(_F32)[:inp.shape[0]]
        for blk in params["blocks"]:
            x = block(x, blk)
        logits = _layernorm(x, params["final_ln"]) @ emb.T
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, tgt[:, None], -1).mean()
