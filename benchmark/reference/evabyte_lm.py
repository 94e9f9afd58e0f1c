"""Plain reference: EvaByte's multi-byte prediction loss.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision:
no kernel, no cache, no sharding, no code of the program under test. It
reads the program's parameter tree (``embed``, ``head``, ``final_ln.scale``
and ``blocks[i]`` with ``ln1.scale ln2.scale wq wk wv wo w1 w3 w2 eva_phi
eva_mu``; ``w1`` is the gate, ``w3`` the up and ``w2`` the down matrix) and
the configuration's ``model`` (``n_heads``, ``eva_window`` W, ``eva_chunk``
C, ``rope_theta``, ``n_pred_heads`` P, ``vocab``; the norms' eps is the
published ``rms_norm_eps`` 1e-5).

The equations (per head; ``d`` the head size, ``s = d**-0.5``; ``q_i, k_j,
v_j`` after the projections and, for q and k, after rope on half-split
pairs; ``phi, mu`` two learned vectors of ``d`` a head and layer):

  * chunk ``c`` holds positions ``cC .. (c+1)C - 1``; its pooling weights
    are ``a_cj = softmax_j(s k_j.phi)`` over those positions, its summary
    key ``K_c = sum_j a_cj k_j + mu``, its summary value
    ``V_c = sum_j a_cj v_j``;
  * query ``i`` lies in window ``w = i // W``; it sees the keys
    ``L(i) = {j : wW <= j <= i}`` and the summaries
    ``R(i) = {c : c < w W/C}``: every chunk of every earlier window, none
    of its own;
  * ``Z_i = sum_L e^{s q_i.k_j} + sum_R e^{s q_i.K_c}``,
    ``o_i = (sum_L e^{s q_i.k_j} v_j + sum_R e^{s q_i.K_c} V_c) / Z_i``:
    one softmax over both sets;
  * block: ``x += Wo EVA(rope(Wq n1), rope(Wk n1), Wv n1)`` with
    ``n1 = rms(x) (1 + g1)``; ``x += Wd (silu(Wg n2) * (Wu n2))`` with
    ``n2 = rms(x) (1 + g2)``; ``rms(x) = x / sqrt(mean(x^2) + eps)``; no
    biases;
  * output: ``logits[t, p] = Wout_p (rms(x_t) (1 + gf))``, p = 0..P-1;
    loss = mean over p of the mean, over the positions t for which byte
    ``t + 1 + p`` exists, of the cross-entropy of ``logits[t, p]`` against
    that byte.

Source: "Efficient Attention via Control Variates" (Zheng, Yuan, Wang,
Kong, ICLR 2023, arXiv:2302.04542) in the deterministic form of EvaByte's
released modelling code, written down without network access. Departures
and assumptions (the configuration file's ``assumed`` and ``changed`` say
the same): the pooling is deterministic with learned ``phi`` and ``mu``
(no random features); windows are aligned blocks, not sliding; the P heads
weigh equally in the loss; everything is float32 here, where the model
keeps only the residual sum and the logits in float32.

Only the order of the work is arranged for memory, never its values: the
heads are taken ``_HEADS_AT_ONCE`` at a time and, inside, one window at a
time (a window's float32 scores are ``W x (W + seq/C)`` a head), the FFN
``_FFN_ROWS`` positions at a time, each piece a ``jax.checkpoint`` under
``lax.map``, so that ``jax.value_and_grad`` of this loss for one block fits
beside the optimizer state at 16,384 bytes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HEADS_AT_ONCE = 4
_FFN_ROWS = 2048


def _rms(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * (1.0 + p["scale"].astype(_F32))


def _rope(x, theta):
    """x: (s, heads, hd). Rotate the pair (i, i + hd/2) by pos * theta^(-2i/hd)."""
    s, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = jnp.arange(s, dtype=_F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def eva_attention(q, k, v, phi, mu, window, chunk):
    """The attention of the equations above for some heads of one
    sequence: ``q, k, v`` ``(s, heads, hd)`` after rope, ``phi, mu``
    ``(heads, hd)``; returns ``(s, heads, hd)``."""
    s, heads, hd = q.shape
    window = min(window, s)
    if s % window or window % chunk:
        raise ValueError(f"seq {s} is not whole windows of {window} made of "
                         f"whole chunks of {chunk}")
    scale = hd ** -0.5
    n_w, per_w = s // window, window // chunk
    kc = k.reshape(s // chunk, chunk, heads, hd)
    vc = v.reshape(s // chunk, chunk, heads, hd)
    a = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi) * scale, axis=1)
    ks = jnp.einsum("nch,nchd->nhd", a, kc) + mu[None]
    vs = jnp.einsum("nch,nchd->nhd", a, vc)
    causal = jnp.tril(jnp.ones((window, window), bool))
    chunk_window = jnp.arange(s // chunk) // per_w

    @jax.checkpoint
    def one_window(args):
        w, qw, kw, vw = args                      # (window, heads, hd)
        local = jnp.einsum("qhd,thd->hqt", qw, kw) * scale
        local = jnp.where(causal[None], local, -jnp.inf)
        remote = jnp.einsum("qhd,nhd->hqn", qw, ks) * scale
        remote = jnp.where((chunk_window < w)[None, None, :], remote,
                           -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([local, remote], -1), -1)
        return jnp.einsum("hqt,thd->qhd", p, jnp.concatenate([vw, vs], 0))

    by_window = lambda x: x.reshape(n_w, window, heads, hd)  # noqa: E731
    out = jax.lax.map(one_window, (jnp.arange(n_w), by_window(q),
                                   by_window(k), by_window(v)))
    return out.reshape(s, heads, hd)


def _attention(h, blk, model):
    heads = model["n_heads"]
    at_once = _HEADS_AT_ONCE if heads % _HEADS_AT_ONCE == 0 else heads
    groups = heads // at_once
    theta = model.get("rope_theta", 10000.0)

    def grouped(w):          # (d, heads, hd) -> (groups, d, at_once, hd)
        d, _, hd = w.shape
        return w.astype(_F32).reshape(d, groups, at_once, hd).transpose(
            1, 0, 2, 3)

    def vec(p):              # (heads, hd) -> (groups, at_once, hd)
        return p.astype(_F32).reshape(groups, at_once, -1)

    @jax.checkpoint
    def some_heads(args):
        wq, wk, wv, phi, mu = args
        q = _rope(jnp.einsum("sd,dhk->shk", h, wq), theta)
        k = _rope(jnp.einsum("sd,dhk->shk", h, wk), theta)
        v = jnp.einsum("sd,dhk->shk", h, wv)
        return eva_attention(q, k, v, phi, mu, model["eva_window"],
                             model["eva_chunk"])

    ctx = jax.lax.map(some_heads, (
        grouped(blk["wq"]), grouped(blk["wk"]), grouped(blk["wv"]),
        vec(blk["eva_phi"]), vec(blk["eva_mu"])))    # (groups, s, at_once, hd)
    s = h.shape[0]
    ctx = ctx.transpose(1, 0, 2, 3).reshape(s, heads, -1)
    return jnp.einsum("shk,hkd->sd", ctx, blk["wo"].astype(_F32))


def _ffn(x, blk, eps):
    """``x + Wd(silu(Wg n2) * (Wu n2))``, some rows at a time."""
    s, d = x.shape
    rows = _FFN_ROWS if s % _FFN_ROWS == 0 else s
    wg, wu, wd = (blk[n].astype(_F32) for n in ("w1", "w3", "w2"))

    @jax.checkpoint
    def some_rows(xr):
        n2 = _rms(xr, blk["ln2"], eps)
        return xr + (jax.nn.silu(n2 @ wg) * (n2 @ wu)) @ wd

    return jax.lax.map(some_rows, x.reshape(s // rows, rows, d)).reshape(s, d)


def multi_byte_loss(logits, tokens):
    """``logits`` ``(s, P, vocab)`` for the inputs ``tokens[:s]``; head p
    at position t is scored against ``tokens[t + 1 + p]`` where that
    exists; the mean of the heads' means."""
    s, heads, _ = logits.shape
    logp = jax.nn.log_softmax(logits, -1)
    total = 0.0
    for p in range(heads):
        n = s - p                          # positions t with t + 1 + p <= s
        tgt = tokens[1 + p:1 + p + n]
        total = total - jnp.take_along_axis(
            logp[:n, p], tgt[:, None], -1).mean()
    return total / heads


def sequence_loss(params, tokens, model):
    """The loss above for ONE sequence ``tokens`` (s + 1,)."""
    eps = 1e-5

    @jax.checkpoint
    def attn_part(x, blk):
        return x + _attention(_rms(x, blk["ln1"], eps), blk, model)

    @jax.checkpoint
    def block(x, blk):
        return _ffn(attn_part(x, blk), blk, eps)

    with jax.default_matmul_precision("highest"):
        inp = tokens[:-1]
        x = params["embed"].astype(_F32)[inp]
        for blk in params["blocks"]:
            x = block(x, blk)
        h = _rms(x, params["final_ln"], eps)
        logits = h @ params["head"].astype(_F32).T
        return multi_byte_loss(
            logits.reshape(inp.shape[0], model["n_pred_heads"],
                           model["vocab"]), tokens)
