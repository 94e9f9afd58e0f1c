"""Plain reference: the next-token loss of a ``nemotron_h`` layer stack
(Mamba-2 mixers, causal attention, sigmoid-routed relu^2 experts beside a
shared expert), one sub-layer a block.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision:
no kernel, no chunk algebra, no dispatch buffer, no sharding, no code of
the program under test. It reads the program's parameter tree (``embed``,
``head``, ``final_ln.scale`` and ``blocks[i]`` with ``ln1.scale`` and, by
the block's letter in ``model["layer_pattern"]``: ``M`` ``in_proj conv_w
conv_b dt_bias A_log D ssm_norm out_proj``; ``*`` ``wq wk wv wo``; ``E``
``router w_up w_down shared_up shared_down``) and the configuration's
``model`` (``n_heads``, ``n_kv_heads``, ``ssm_heads`` H, ``ssm_head_dim``
P, ``ssm_groups`` G, ``ssm_state`` N, ``ssm_conv``, ``n_experts``,
``moe_top_k``, ``moe_expert_offset``, ``moe_routed_scale``; the norms' eps
is the published ``layer_norm_epsilon`` 1e-5).

The equations, for one sequence; every block is ``x += f(rms(x) (1 + g))``
with ``rms(x) = x / sqrt(mean(x^2) + eps)``:

  * ``M``: ``[z | xBC | dt] = h W_in``; ``xBC = silu(conv(xBC) + b)`` with
    ``conv(u)_t = sum_j w[j] u_{t-3+j}`` a channel (zero before the
    sequence); ``[x | B | C] = xBC`` as ``(H, P)``, ``(G, N)``, ``(G, N)``,
    head ``j`` reading group ``j // (H / G)``; ``dt = softplus(dt +
    dt_bias)``; ``A = -exp(A_log)``; **token by token**
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
    D x_t``; ``y = GroupRMS(y * silu(z)) * w`` over ``G`` groups of
    ``H P / G`` channels (the gate before the norm); ``out = y W_out``.
  * ``*``: ``softmax(q k^T / sqrt(hd) + causal) v`` with grouped k/v heads,
    no bias and **no position term**, then ``W_o``.
  * ``E``: ``s = sigmoid(h W_r)`` over all ``n_experts``; the ``top_k``
    largest (``e_score_correction_bias`` is zero here); weights ``s_k /
    (sum_k s_k + 1e-20) * scale``; ``y = sum_k w_k W_down_k relu(W_up_k
    h)^2 + W_sd relu(W_su h)^2``, the sum **over the held experts only**:
    the tree holds experts ``offset .. offset + held - 1`` of the layer
    and what the others would add is left out, as in the program.
  * output: ``logits = W_head (rms(x) (1 + g_f))`` over the vocabulary the
    tree holds; the mean cross-entropy of position ``t`` against token
    ``t + 1``.

Source: the model's ``config.json`` (``nemotron_h``) and "Transformers are
SSMs" (Dao and Gu, arXiv:2405.21060) for the mixer, written down without
network access. Departures (the configuration file's ``changed`` and
``assumed`` say the same): a norm's weight is stored as ``1 + g``; the
routing bias buffer is zero and has no leaf; ``time_step_limit`` is
``(0, inf)`` and clips nothing; everything is float32 here, where the
model keeps float32 only in the scan's state and decays, the router and
the logits.

Only the order of the work is arranged for memory, never its values: the
recurrence is a ``lax.scan`` over time whose blocks of ``_TIME_BLOCK``
steps are ``jax.checkpoint``ed, attention takes ``_QUERY_ROWS`` queries at
a time, the experts ``_FFN_ROWS`` positions, each piece a
``jax.checkpoint`` under ``lax.map``, and every block is one, so that
``jax.value_and_grad`` of this loss for one block fits beside the
optimizer state at 8,192 tokens.
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


def selective_scan(x, dt, A, B, C, D):
    """The recurrence above, one token at a time: ``x`` ``(s, H, P)``,
    ``dt`` ``(s, H)``, ``A``, ``D`` ``(H,)``, ``B``, ``C`` ``(s, G, N)``;
    returns ``(s, H, P)``."""
    s, heads, p = x.shape
    groups, n = B.shape[1:]
    per = heads // groups

    def step(state, inp):                     # state (H, P, N)
        x_t, dt_t, b_t, c_t = inp
        b_h, c_h = jnp.repeat(b_t, per, 0), jnp.repeat(c_t, per, 0)  # (H, N)
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_h) + D[:, None] * x_t

    @jax.checkpoint
    def some_steps(state, inp):
        return jax.lax.scan(step, state, inp)

    block = _TIME_BLOCK if s % _TIME_BLOCK == 0 else s
    inp = jax.tree.map(lambda a: a.reshape(s // block, block, *a.shape[1:]),
                       (x, dt, B, C))
    _, y = jax.lax.scan(some_steps, jnp.zeros((heads, p, n), _F32), inp)
    return y.reshape(s, heads, p)


def _scan_inputs(h, blk, model):
    """The gate ``z`` and ``(x, dt, A, B, C, D)`` of the recurrence."""
    heads, p = model["ssm_heads"], model["ssm_head_dim"]
    groups, n = model["ssm_groups"], model["ssm_state"]
    d_inner, s = heads * p, h.shape[0]
    conv_dim = d_inner + 2 * groups * n
    proj = h @ blk["in_proj"].astype(_F32)
    z, xbc, dt = (proj[:, :d_inner], proj[:, d_inner:d_inner + conv_dim],
                  proj[:, d_inner + conv_dim:])
    w = blk["conv_w"].astype(_F32)                       # (k, conv_dim)
    k = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, conv_dim), _F32), xbc], 0)
    xbc = jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(k))
                      + blk["conv_b"].astype(_F32))
    return (z, xbc[:, :d_inner].reshape(s, heads, p),
            jax.nn.softplus(dt + blk["dt_bias"].astype(_F32)),
            -jnp.exp(blk["A_log"].astype(_F32)),
            xbc[:, d_inner:d_inner + groups * n].reshape(s, groups, n),
            xbc[:, d_inner + groups * n:].reshape(s, groups, n),
            blk["D"].astype(_F32))


def _mamba(h, blk, model):
    z, *recurrence = _scan_inputs(h, blk, model)
    groups, s = model["ssm_groups"], h.shape[0]
    y = selective_scan(*recurrence)
    y = (y.reshape(s, -1) * jax.nn.silu(z)).reshape(s, groups, -1)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + _EPS)
    return (y.reshape(s, -1) * blk["ssm_norm"].astype(_F32)) \
        @ blk["out_proj"].astype(_F32)


def _attention(h, blk, model):
    s = h.shape[0]
    q = jnp.einsum("sd,dhk->shk", h, blk["wq"].astype(_F32))
    k = jnp.einsum("sd,dhk->shk", h, blk["wk"].astype(_F32))
    v = jnp.einsum("sd,dhk->shk", h, blk["wv"].astype(_F32))
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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _experts(h, blk, model, chosen=None):
    top_k, offset = model["moe_top_k"], model.get("moe_expert_offset", 0)
    scale = model.get("moe_routed_scale", 1.0)
    router = blk["router"].astype(_F32)
    w_up, w_down = blk["w_up"].astype(_F32), blk["w_down"].astype(_F32)
    s_up = blk["shared_up"].astype(_F32)
    s_down = blk["shared_down"].astype(_F32)

    @jax.checkpoint
    def some_rows(args):
        hr, idx = args
        scores = jax.nn.sigmoid(hr @ router)             # (r, n_experts)
        if chosen is None:
            idx = jax.lax.top_k(scores, top_k)[1]
        picked = jnp.take_along_axis(scores, idx, -1)
        weight = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
        y = _relu2(hr @ s_up) @ s_down
        for e in range(w_up.shape[0]):                   # the held experts
            gate = jnp.where(idx == offset + e, weight, 0.0).sum(-1)
            y = y + gate[:, None] * (_relu2(hr @ w_up[e]) @ w_down[e])
        return y

    idx = jnp.zeros((h.shape[0], top_k), jnp.int32) if chosen is None \
        else chosen
    return jax.lax.map(some_rows, (_pieces(h, _FFN_ROWS),
                                   _pieces(idx, _FFN_ROWS))).reshape(h.shape)


def choices_outside_top_k(h, chosen, blk, model):
    """Of the choices ``chosen`` ``(s, top_k)`` that a program made for the
    router's input ``h`` ``(s, d)``, the share that is not among the
    ``top_k`` largest float32 scores of that same input."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(h.astype(_F32) @ blk["router"].astype(_F32))
    mine = jax.lax.top_k(scores, model["moe_top_k"])[1]
    return (~(chosen[:, :, None] == mine[:, None, :]).any(-1)).mean()


def scan_inputs(tokens, params, model, at):
    """``(x, dt, A, B, C, D)`` as they enter the recurrence of the ``M``
    block ``at`` for the sequence ``tokens`` (s + 1,): what
    :func:`selective_scan` takes."""
    assert model["layer_pattern"][at] == "M", (at, model["layer_pattern"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(_F32)[tokens[:-1]]
        for blk, kind in zip(params["blocks"][:at],
                             model["layer_pattern"]):
            x = x + _LAYER[kind](_rms(x, blk["ln1"]), blk, model)
        blk = params["blocks"][at]
        return _scan_inputs(_rms(x, blk["ln1"]), blk, model)[1:]


_LAYER = {"M": _mamba, "*": _attention, "E": _experts}


def sequence_loss(params, tokens, model, routing=None):
    """The loss above for ONE sequence ``tokens`` (s + 1,). ``routing``,
    where given, holds for every ``E`` block in order the experts
    ``(s, top_k)`` each position is to use in place of this reference's
    own ``top_k`` largest: the scores, the weights and everything else
    stay its own."""
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
        logits = _rms(x, params["final_ln"]) @ params["head"].astype(_F32).T
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, tokens[1:, None], -1).mean()
