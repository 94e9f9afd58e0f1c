"""Plain reference: the next-token loss of an ``lfm2_moe`` layer stack
(gated short convs, causal attention with per-head q/k norm and rope,
dense SwiGLU FFNs, sigmoid-routed SwiGLU experts chosen by score plus a
bias, no shared expert), one sub-layer a block.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision:
no kernel, no tiles, no dispatch buffer, no sharding, no code of the
program under test. It reads the program's parameter tree (``embed``,
``head`` where the embedding is untied, ``final_ln.scale`` and
``blocks[i]`` with ``ln1.scale`` and, by the block's letter in
``model["layer_pattern"]``: ``C`` ``in_proj conv_w out_proj``; ``*`` ``wq
wk wv wo q_norm.scale k_norm.scale``; ``F`` ``w1 w2 w3``; ``E`` ``router
router_bias w_gate w_up w_down``) and the configuration's ``model``
(``n_heads``, ``n_kv_heads``, ``rope_theta``, ``moe_top_k``,
``moe_expert_offset``, ``moe_routed_scale``, ``tie_embeddings``; the
norms' eps is the published ``norm_eps`` 1e-5).

The equations, for one sequence; every block is ``x += f(rms(x) (1 + g))``
with ``rms(x) = x / sqrt(mean(x^2) + eps)``:

  * ``C`` (``Lfm2ShortConv``): ``[B | C | u] = h W_in``; ``v = B * u``;
    **token by token** ``c_t = sum_j w[j] v_{t-k+1+j}`` a channel, zero
    before the sequence, no bias; ``out = (C * c) W_out``.
  * ``*``: ``q = rms_64(h W_q) (1 + g_q)``, ``k = rms_64(h W_k) (1 + g_k)``
    over each head's values, then rope (the halves of a head rotated by
    ``t theta^(-i / half)``), ``softmax(q k^T / sqrt(hd) + causal) v``
    with grouped k/v heads, then ``W_o``.
  * ``F``: ``W_2 (silu(W_1 h) * W_3 h)``.
  * ``E``: ``s = sigmoid(h W_r)`` over all ``n_experts``; the experts of
    the ``top_k`` largest of ``s + b`` (``b`` the selection bias, which
    chooses and does not weigh); weights ``s_k / (sum_k s_k + 1e-20) *
    scale``; ``y = sum_k w_k W_down_k (silu(W_gate_k h) * W_up_k h)``, the
    sum **over the held experts only**: the tree holds experts ``offset ..
    offset + held - 1`` of the layer and what the others would add is left
    out, as in the program.
  * output: ``logits = W_head (rms(x) (1 + g_f))`` (``W_head`` the
    embedding where tied) over the vocabulary the tree holds; the mean
    cross-entropy of position ``t`` against token ``t + 1``.

Source: the model's ``config.json`` (``lfm2_moe``) and LiquidAI's released
``lfm2_moe`` modelling code, written down without network access.
Departures (the configuration file's ``changed`` and ``assumed`` say the
same): a norm's weight is stored as ``1 + g``; the weights' normaliser is
``+ 1e-20`` where the source adds 1e-6 (a relative difference under 1e-6
at four scores of about a half); everything is float32 here, where the
model keeps float32 only in the conv's gates and taps, the router and the
logits.

Only the order of the work is arranged for memory, never its values: the
conv is a ``lax.scan`` over time whose blocks of ``_TIME_BLOCK`` steps
are ``jax.checkpoint``ed, attention takes ``_QUERY_ROWS`` queries at a
time, the FFNs ``_FFN_ROWS`` positions, each piece a ``jax.checkpoint``
under ``lax.map``, and every block is one, so that ``jax.value_and_grad``
of this loss for one block fits beside the optimizer state at 8,192
tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_EPS = 1e-5
_TIME_BLOCK = 128
_QUERY_ROWS = 512
_FFN_ROWS = 2048


def _rms(x, p):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + _EPS) \
        * (1.0 + p["scale"].astype(_F32))


def _pieces(x, rows):
    """``(s, ...)`` -> ``(s / rows, rows, ...)`` (``s`` itself where
    ``rows`` does not divide it)."""
    s = x.shape[0]
    rows = rows if s % rows == 0 else s
    return x.reshape(s // rows, rows, *x.shape[1:])


def short_conv(v, w):
    """The causal depthwise conv above, one token at a time: ``v`` ``(s,
    ch)``, ``w`` ``(k, ch)``; the state is the last ``k - 1`` inputs."""
    k = w.shape[0]

    def step(last, v_t):                       # last (k - 1, ch)
        window = jnp.concatenate([last, v_t[None]], 0)
        return window[1:], (window * w).sum(0)

    @jax.checkpoint
    def some_steps(last, inp):
        return jax.lax.scan(step, last, inp)

    s = v.shape[0]
    block = _TIME_BLOCK if s % _TIME_BLOCK == 0 else s
    _, c = jax.lax.scan(some_steps, jnp.zeros((k - 1, v.shape[1]), _F32),
                        v.reshape(s // block, block, -1))
    return c.reshape(v.shape)


def _conv(h, blk, model):
    d = h.shape[1]
    bcu = h @ blk["in_proj"].astype(_F32)
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    return (c * short_conv(b * u, blk["conv_w"].astype(_F32))) \
        @ blk["out_proj"].astype(_F32)


def _rope(x, theta):
    """``x`` ``(s, heads, hd)``: the two halves of each head rotated."""
    s, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    angle = jnp.arange(s, dtype=_F32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(h, blk, model):
    s = h.shape[0]
    q = jnp.einsum("sd,dhk->shk", h, blk["wq"].astype(_F32))
    k = jnp.einsum("sd,dhk->shk", h, blk["wk"].astype(_F32))
    v = jnp.einsum("sd,dhk->shk", h, blk["wv"].astype(_F32))
    theta = model["rope_theta"]
    q = _rope(_rms(q, blk["q_norm"]), theta)
    k = _rope(_rms(k, blk["k_norm"]), theta)
    heads, hd = q.shape[1:]
    per = heads // k.shape[1]
    k, v = jnp.repeat(k, per, 1), jnp.repeat(v, per, 1)
    at = jnp.arange(s)

    @jax.checkpoint
    def some_queries(args):
        rows, q_rows = args                              # (r,), (r, H, hd)
        scores = jnp.einsum("qhd,thd->hqt", q_rows, k) * hd ** -0.5
        scores = jnp.where(at[None, None, :] <= rows[None, :, None], scores,
                           -jnp.inf)
        return jnp.einsum("hqt,thd->qhd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(some_queries, (_pieces(at, _QUERY_ROWS),
                                     _pieces(q, _QUERY_ROWS)))
    return jnp.einsum("shk,hkd->sd", ctx.reshape(s, heads, hd),
                      blk["wo"].astype(_F32))


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _dense(h, blk, model):
    w1, w2, w3 = (blk[n].astype(_F32) for n in ("w1", "w2", "w3"))

    @jax.checkpoint
    def some_rows(hr):
        return _swiglu(hr, w1, w3, w2)

    return jax.lax.map(some_rows, _pieces(h, _FFN_ROWS)).reshape(h.shape)


def _scores(h, blk):
    """``s`` and ``s + b``: what weighs and what selects."""
    s = jax.nn.sigmoid(h @ blk["router"].astype(_F32))
    return s, s + blk["router_bias"].astype(_F32)


def _experts(h, blk, model, chosen=None):
    top_k, offset = model["moe_top_k"], model.get("moe_expert_offset", 0)
    scale = model.get("moe_routed_scale", 1.0)
    w_gate, w_up, w_down = (blk[n].astype(_F32)
                            for n in ("w_gate", "w_up", "w_down"))

    @jax.checkpoint
    def some_rows(args):
        hr, idx = args
        scores, select = _scores(hr, blk)                # (r, n_experts)
        if chosen is None:
            idx = jax.lax.top_k(select, top_k)[1]
        picked = jnp.take_along_axis(scores, idx, -1)
        weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
        y = jnp.zeros_like(hr)
        for e in range(w_up.shape[0]):                   # the held experts
            gate = jnp.where(idx == offset + e, weight, 0.0).sum(-1)
            y = y + gate[:, None] * _swiglu(hr, w_gate[e], w_up[e],
                                            w_down[e])
        return y

    idx = jnp.zeros((h.shape[0], top_k), jnp.int32) if chosen is None \
        else chosen
    return jax.lax.map(some_rows, (_pieces(h, _FFN_ROWS),
                                   _pieces(idx, _FFN_ROWS))).reshape(h.shape)


def choices_outside_top_k(h, chosen, blk, model):
    """Of the choices ``chosen`` ``(s, top_k)`` that a program made for the
    router's input ``h`` ``(s, d)``, the share that is not among the
    ``top_k`` largest float32 values of ``s + b`` for that same input."""
    with jax.default_matmul_precision("highest"):
        select = _scores(h.astype(_F32), blk)[1]
    mine = jax.lax.top_k(select, model["moe_top_k"])[1]
    return (~(chosen[:, :, None] == mine[:, None, :]).any(-1)).mean()


_LAYER = {"C": _conv, "*": _attention, "F": _dense, "E": _experts}


def sequence_loss(params, tokens, model, routing=None):
    """The loss above for ONE sequence ``tokens`` (s + 1,). ``routing``,
    where given, holds for every ``E`` block in order the experts
    ``(s, top_k)`` each position is to use in place of this reference's
    own ``top_k`` largest of ``s + b``: the scores, the weights and
    everything else stay its own."""
    def block_of(kind):
        @jax.checkpoint
        def block(x, blk, *chosen):
            return x + _LAYER[kind](_rms(x, blk["ln1"]), blk, model, *chosen)
        return block

    given = iter(() if routing is None else routing)
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(_F32)[tokens[:-1]]
        for blk, kind in zip(params["blocks"], model["layer_pattern"]):
            chosen = (next(given),) if kind == "E" and routing is not None \
                else ()
            x = block_of(kind)(x, blk, *chosen)
        head = params["embed"] if model.get("tie_embeddings", True) \
            else params["head"]
        logits = _rms(x, params["final_ln"]) @ head.astype(_F32).T
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, tokens[1:, None], -1).mean()
