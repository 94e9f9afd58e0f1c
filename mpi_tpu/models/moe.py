"""Mixture-of-Experts FFN — expert parallelism over the ``ep`` mesh axis.

GShard-style top-1 routed MoE with static shapes (XLA needs them): each
token picks its highest-probability expert, experts process fixed-capacity
token buffers, and overflow tokens fall through the residual connection.
Expert weights carry a leading expert axis sharded ``P('ep', ...)``; the
dispatched token buffers are constrained to the same axis, so GSPMD
inserts the all-to-all exchanges that carry tokens to their experts over
ICI — the standard tpu-native MoE dataflow (no reference analogue:
btracey/mpi has no ML code, SURVEY.md §2).

Everything in :func:`moe_ffn` is einsum/one-hot arithmetic — MXU-friendly,
fully differentiable, no data-dependent shapes.

:func:`routed_share_ffn` is the other routed layer: one device's share of
an expert-parallel layer whose experts outnumber the devices. It scores
every expert of the layer, is told which contiguous share it holds, drops
no (token, expert) pair whatever the load, and adds a shared expert where
it has one. The pairs that land on held experts are sorted by expert and
laid out in tiles of rows that belong to one expert each; the expert
products are two (relu²) or three (SwiGLU) matmuls a tile, in one loop
over the occupied tiles. A tile writes its rows to its own contiguous
rows of that layout, and after the loop a gather returns them, weighted,
to their tokens (a short loop adds a token's later held pairs); the
backward loop does the same with the rows of the input's gradient.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import trace

__all__ = ["init_moe_params", "moe_specs", "moe_ffn",
           "init_routed_share_params", "routed_share_specs",
           "routed_share_ffn", "route_top_k", "floor_tiles"]


def _dense(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def init_moe_params(key: jax.Array, d_model: int, d_ff: int,
                    n_experts: int, dtype: Any) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": _dense(k1, (d_model, n_experts), d_model, dtype),
        "w1e": _dense(k2, (n_experts, d_model, d_ff), d_model, dtype),
        "w2e": _dense(k3, (n_experts, d_ff, d_model), d_ff, dtype),
    }


def moe_specs() -> Dict[str, P]:
    """PartitionSpecs for :func:`init_moe_params`'s tree: experts over
    ``ep``, the FFN hidden dim over ``tp`` (Megatron split inside each
    expert); the router is small and replicated."""
    return {
        "router": P(),
        "w1e": P("ep", None, "tp"),
        "w2e": P("ep", "tp", None),
    }


def moe_ffn(x: jax.Array, params: Dict[str, Any], n_experts: int,
            capacity_factor: float = 1.25,
            mesh: Optional[Mesh] = None,
            top_k: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed expert FFN (GShard-style; ``top_k=1`` is Switch).

    ``x``: (batch, seq, d_model). Returns ``(y, aux)`` where ``y`` has
    x's shape (fully-overflowed tokens produce zeros — the caller's
    residual stream carries them through) and ``aux`` is the
    load-balancing loss (Shazeer et al.:
    ``E * sum_e fraction_first_choice_e * mean_prob_e``, minimised at
    uniform routing; computed on first choices for any k).

    Tokens are routed within *groups* (one group per batch row, the
    GShard/Switch recipe): the dispatch one-hots are (groups, seq, E, C)
    with per-group capacity, so memory stays linear in the global token
    count instead of quadratic, and group = batch row keeps routing
    aligned with the dp sharding (no cross-device cumsum).

    Capacity handling for ``k > 1`` follows GShard: per-expert buffers
    hold ``ceil(k * seq / E * capacity_factor)`` tokens, and slots are
    claimed choice-major — every token's first choice outranks any
    token's second choice — so congestion drops k-th choices first.
    Gates are the raw router probabilities of the surviving choices
    (matching the k=1 behavior; a dropped choice contributes zero and
    its share rides the residual).
    """
    b, s, d = x.shape
    e = n_experts
    if not 1 <= top_k <= e:
        raise ValueError(
            f"mpi_tpu: moe top_k={top_k} must be in [1, n_experts={e}]")
    capacity = max(1, int(math.ceil(top_k * s / e * capacity_factor)))

    logits = jnp.einsum("gnd,de->gne", x, params["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topk_probs, topk_idx = lax.top_k(probs, top_k)       # (G, N, K)
    onehot_k = jax.nn.one_hot(topk_idx, e, dtype=jnp.int32)  # (G, N, K, E)

    # Slot positions, choice-major priority: order all first choices in
    # token order, then all second choices, ... (exclusive int cumsum —
    # deterministic, exact). pos[(g, n, k)] = slot index within the
    # chosen expert's group-g buffer.
    ordered = onehot_k.transpose(0, 2, 1, 3).reshape(b, top_k * s, e)
    pos_flat = jnp.cumsum(ordered, axis=1) - ordered
    pos = jnp.einsum("gme,gme->gm", pos_flat, ordered)
    pos = pos.reshape(b, top_k, s).transpose(0, 2, 1)    # (G, N, K)
    kept = pos < capacity                                # (G, N, K)
    gates = jnp.where(kept, topk_probs, 0.0)

    # dispatch[g, n, e', c] = 1 iff token (g, n) sits in slot c of
    # expert e''s group-g buffer (via any of its k choices — top_k gives
    # distinct experts, so slots never collide).
    slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # (G, N, K, C)
    sel = (onehot_k * kept[..., None]).astype(jnp.float32)   # (G, N, K, E)
    dispatch = jnp.einsum("gnke,gnkc->gnec", sel, slot)
    combine = jnp.einsum("gnke,gnkc,gnk->gnec", sel, slot, gates)

    xin = jnp.einsum("gnec,gnd->gecd", dispatch.astype(x.dtype), x)
    buf_sharding = None
    if mesh is not None and "ep" in mesh.axis_names:
        from .transformer import sanitize_spec

        # Commit the expert buffers to the ep axis: GSPMD materialises the
        # token all-to-all here (tokens travel to their expert's device).
        buf_sharding = NamedSharding(
            mesh, sanitize_spec(P("dp", "ep", None, None), mesh))
        xin = lax.with_sharding_constraint(xin, buf_sharding)
    h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", xin,
                               params["w1e"].astype(x.dtype)))
    y_e = jnp.einsum("gecf,efd->gecd", h, params["w2e"].astype(x.dtype))
    if buf_sharding is not None:
        y_e = lax.with_sharding_constraint(y_e, buf_sharding)
    y = jnp.einsum("gnec,gecd->gnd", combine.astype(x.dtype), y_e)

    # Load-balance aux: fraction of first-choice tokens per expert x mean
    # router prob (first choices for any k — the standard GShard form).
    frac = jnp.mean(onehot_k[:, :, 0, :].astype(jnp.float32), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac * mean_prob)
    return y, aux.astype(jnp.float32)


# --------------------------------------------------------------------------
# One device's share of a sigmoid-routed layer, without a dropped pair
# --------------------------------------------------------------------------

def init_routed_share_params(key: jax.Array, d_model: int, d_ff: int,
                             d_shared: int, n_experts: int, held: int,
                             dtype: Any, gated: bool = False,
                             select_bias: bool = False,
                             bias_std: float = 0.0) -> Dict[str, Any]:
    """The router over all ``n_experts``, ``held`` experts of width
    ``d_ff`` (two matrices, or with ``gated`` a third, ``w_gate``: SwiGLU)
    and the shared expert of width ``d_shared`` (none where it is 0). With
    ``select_bias`` the router's selection bias ``router_bias``
    ``(n_experts,)`` float32, zero or drawn N(0, ``bias_std``^2): it
    decides which experts a token uses, never their weights, and no step
    updates it (``make_train_parts``)."""
    keys = jax.random.split(key, 5)
    out = {
        "router": _dense(keys[0], (d_model, n_experts), d_model, dtype),
        "w_up": _dense(keys[1], (held, d_model, d_ff), d_model, dtype),
        "w_down": _dense(keys[2], (held, d_ff, d_model), d_ff, dtype),
    }
    if d_shared:
        out["shared_up"] = _dense(keys[3], (d_model, d_shared), d_model,
                                  dtype)
        out["shared_down"] = _dense(keys[4], (d_shared, d_model), d_shared,
                                    dtype)
    if gated:   # folded in, so that the two-matrix draw is what it was
        out["w_gate"] = _dense(jax.random.fold_in(key, 5),
                               (held, d_model, d_ff), d_model, dtype)
    if select_bias:
        out["router_bias"] = bias_std * jax.random.normal(
            jax.random.fold_in(key, 6), (n_experts,), jnp.float32) \
            if bias_std else jnp.zeros((n_experts,), jnp.float32)
    return out


def routed_share_specs(gated: bool = False, shared: bool = True,
                       select_bias: bool = False) -> Dict[str, P]:
    """Every leaf of :func:`init_routed_share_params`'s tree replicated:
    the share IS the device's part of the expert-parallel layer, and
    nothing of it is split further."""
    names = ["router", "w_up", "w_down"]
    names += ["shared_up", "shared_down"] if shared else []
    names += ["w_gate"] if gated else []
    names += ["router_bias"] if select_bias else []
    return {name: P() for name in names}


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _router_scores(x2, router):
    """``sigmoid(x W_r)`` over every expert of the layer, product and
    scores in float32 (at the highest precision: a TPU's default would
    round the operands to bfloat16)."""
    return jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x2.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))


# Rows of one tile of the dispatch loop: every tile belongs to one expert,
# so an expert's rows are padded to whole tiles.
_TILE = 512


def route_top_k(x2: jax.Array, router: jax.Array, top_k: int,
                scale: float = 1.0, bias: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """The routed layer's decisions for ``x2`` ``(tokens, d)``: over every
    expert of the layer the scores ``s = sigmoid(x W_r)``, the ``top_k``
    largest of ``s + bias`` (of ``s`` without a bias) as ``idx`` ``(tokens,
    top_k)``, and their weights ``s_k / (sum_k s_k + 1e-20) * scale`` in
    float32: the bias selects, the scores weigh."""
    scores = _router_scores(x2, router)
    if bias is None:
        chosen, idx = lax.top_k(scores, top_k)
    else:
        idx = lax.top_k(scores + bias.astype(jnp.float32), top_k)[1]
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scale


def _layout_rows(tokens: int, top_k: int, held: int, floor: int) -> int:
    """Rows of the tile layout: every tile the loop can run, so no pair is
    dropped whatever the load (a token puts at most ``min(top_k, held)``
    pairs on the share, and each expert pads its last tile)."""
    return max(floor, -(-tokens * min(top_k, held) // _TILE) + held) * _TILE


def _tile_layout(key, order, held: int, top_k: int, rows: int):
    """The pairs laid out in tiles of one held expert each: ``key`` a
    pair's place in the share (``held`` for an absent expert), ``order``
    the pairs sorted stably by it. Returns the occupied tiles, ``rows_of(t)
    -> (e, pair, token, live)`` for tile ``t`` (its expert, and a row's
    pair, token and whether the row holds a pair: a tile past the occupied
    ones holds none) and ``slot`` ``(pairs,)``: the row of the layout,
    ``t * _TILE + i``, that holds a pair, or ``rows`` for a pair on an
    absent expert."""
    hits = key[:, None] == jnp.arange(held)[None, :]
    sizes = hits.sum(0, dtype=jnp.int32)
    first = jnp.cumsum(sizes) - sizes              # in ``order``
    tiles = -(-sizes // _TILE)                     # whole tiles an expert
    tile_end = jnp.cumsum(tiles)

    def rows_of(t):
        e = jnp.minimum(jnp.sum(tile_end <= t), held - 1)   # tiles ended
        within = (t - (tile_end[e] - tiles[e])) * _TILE + jnp.arange(_TILE)
        live = (t < tile_end[-1]) & (within < sizes[e])
        pair = order[jnp.clip(first[e] + within, 0, order.size - 1)]
        return e, pair, pair // top_k, live

    # A pair's row: its expert's first row plus its rank among the
    # expert's pairs, which the stable sort keeps in pair order (the count
    # of the same key before it). Masked sums, not gathers by ``key``: a
    # gather of 8 values at every pair is many ops once compiled.
    start = (tile_end - tiles) * _TILE - 1
    slot = jnp.sum(jnp.where(hits, start + jnp.cumsum(hits, 0,
                                                      dtype=jnp.int32), 0),
                   -1)
    return tile_end[-1], rows_of, jnp.where(key < held, slot, rows)


def _layout_buffer(rows: int, row_shape, dtype, after):
    """The layout's ``rows + 1`` rows of ``row_shape``; the last, the one
    an absent pair's slot names, is zero. The loop writes every row a slot
    names before it is read, so the rest is left as it is allocated. The
    zero row waits for ``after``, the layer's own input: a buffer that
    depends on nothing could be allocated at the start of the step, every
    layer's at once."""
    zero, _ = lax.optimization_barrier(
        (jnp.zeros((1, *row_shape), dtype), after))
    return lax.dynamic_update_slice_in_dim(
        lax.empty((rows + 1, *row_shape), dtype), zero, rows, 0)


def _expert_rows(x, experts, e):
    """Rows ``x`` through held expert ``e``: ``W_down relu(W_up x)^2`` for
    ``experts`` = ``(w_up, w_down)``, ``W_down (silu(W_gate x) * W_up x)``
    for ``(w_up, w_down, w_gate)``."""
    if len(experts) == 2:
        w_up, w_down = experts
        return jnp.dot(_relu2(jnp.dot(x, w_up[e])), w_down[e])
    w_up, w_down, w_gate = experts
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate[e])) * jnp.dot(x, w_up[e]),
                   w_down[e])


def _combine(buf, slot, weight, out_dtype):
    """``y[tok] = sum_k weight[tok, k] buf[slot[tok, k]]`` in float32, cast
    to ``out_dtype``: ``buf`` the layout's rows, its last row zero;
    ``slot`` and ``weight`` ``(tokens, top_k)``, a slot naming the zero
    row where the pair's expert is absent.

    A row gather costs about the same for any row (about 50 ns a row on a
    TPU v5e), the zero one too, and most slots name it. So one gather takes
    each token's first pair on a held expert (the zero row where it has
    none), and the token's later held pairs, few, are added in tiles of
    ``_TILE`` by a loop whose trip count is read from their number. With
    tracing on, each combine built adds 1 to ``moe.combine.gathers`` (at
    trace time)."""
    trace.count("moe.combine.gathers")
    top_k = slot.shape[1]
    held = slot != buf.shape[0] - 1
    first = jnp.arange(top_k) == jnp.argmax(held, axis=1)[:, None]
    y = (buf[jnp.sum(jnp.where(first, slot, 0), 1)]
         * jnp.sum(jnp.where(first, weight, 0), 1, keepdims=True))
    later = (held & ~first).reshape(-1)
    # The n-th later pair is the one at which the running count passes n:
    # counted by compares, since a search loop in the loop compiles slowly.
    seen = jnp.cumsum(later, dtype=jnp.int32)
    step = min(_TILE, later.size)

    def body(i, y):
        nth = i * step + jnp.arange(step)
        pair = jnp.minimum(jnp.sum(seen <= nth[:, None], 1), later.size - 1)
        row_weight = jnp.where(nth < seen[-1], weight.reshape(-1)[pair], 0)
        return y.at[pair // top_k].add(
            buf[slot.reshape(-1)[pair]] * row_weight[:, None])

    return lax.fori_loop(0, -(-seen[-1] // step), body, y).astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _expert_tiles(x2, experts, pair_weight, key, order, top_k, floor):
    """``sum over a token's pairs of weight x expert_e(x)``
    (:func:`_expert_rows`) for the pairs on held experts: ``x2`` ``(tokens,
    d)``, ``experts`` the held experts' matrices, ``pair_weight``
    ``(tokens * top_k,)``, ``key`` a pair's held expert (``held`` where it
    is absent) and ``order`` the pairs sorted by it; returns ``(tokens,
    d)`` in ``x2``'s dtype.

    ONE loop over the tiles, with a trip count read from the load: a
    tile's rows are gathered, go through their expert's two or three
    matmuls, and are written as they come, unweighted, to the tile's own
    rows of the layout (:func:`_tile_layout`), so the work follows the
    pairs. The loop runs at least ``floor`` tiles (the tiles past the
    occupied ones hold no pair, and no slot names their rows). After it
    the rows go back to their tokens, weighted and summed in float32
    (:func:`_combine`). The backward pass is the same loop again: it
    recomputes a tile's hidden rows, adds its weight gradients into
    float32 sums in place, and writes its rows of ``d x`` and of the pair
    weights' gradient to its slots, which gathers after the loop return
    to the tokens and pairs."""
    return _expert_tiles_fwd(x2, experts, pair_weight, key, order, top_k,
                             floor)[0]


def _expert_tiles_fwd(x2, experts, pair_weight, key, order, top_k, floor):
    tokens, d = x2.shape
    held = experts[0].shape[0]
    rows = _layout_rows(tokens, top_k, held, floor)
    occupied, rows_of, slot = _tile_layout(key, order, held, top_k, rows)

    def body(t, buf):
        e, _, token, _ = rows_of(t)
        return lax.dynamic_update_slice_in_dim(
            buf, _expert_rows(x2[token], experts, e), t * _TILE, 0)

    buf = lax.fori_loop(0, jnp.maximum(occupied, floor), body,
                        _layout_buffer(rows, (d,), x2.dtype, x2))
    y = _combine(buf, slot.reshape(tokens, top_k),
                 pair_weight.reshape(tokens, top_k), x2.dtype)
    return y, (x2, experts, pair_weight, key, order, slot)


def _expert_tiles_bwd(top_k, floor, held_back, g):
    x2, experts, pair_weight, key, order, slot = held_back
    f32 = jnp.float32
    (tokens, d), held = x2.shape, experts[0].shape[0]
    rows = _layout_rows(tokens, top_k, held, floor)
    occupied, rows_of, _ = _tile_layout(key, order, held, top_k, rows)

    def relu2_body(t, sums):
        d_x, (d_up, d_down), d_weight = sums
        (w_up, w_down), (e, pair, token, live) = experts, rows_of(t)
        x, g_t = x2[token], g[token]
        weight = jnp.where(live, pair_weight[pair], 0)[:, None]
        r = jax.nn.relu(jnp.dot(x, w_up[e]))
        r2 = r * r
        back = jnp.dot(g_t, w_down[e].T)            # d out / d hidden
        d_weight = lax.dynamic_update_slice_in_dim(
            d_weight, (r2.astype(f32) * back.astype(f32)).sum(-1),
            t * _TILE, 0)
        d_down = d_down.at[e].add(jnp.dot(
            (r2 * weight.astype(r2.dtype)).T, g_t, preferred_element_type=f32))
        d_pre = back * (2 * r) * weight.astype(r.dtype)
        d_up = d_up.at[e].add(jnp.dot(x.T, d_pre, preferred_element_type=f32))
        d_x = lax.dynamic_update_slice_in_dim(
            d_x, jnp.dot(d_pre, w_up[e].T).astype(f32), t * _TILE, 0)
        return d_x, (d_up, d_down), d_weight

    def swiglu_body(t, sums):
        # The gate's derivative in float32: silu'(a) = s (1 + a (1 - s)).
        d_x, (d_up, d_down, d_gate), d_weight = sums
        (w_up, w_down, w_gate), (e, pair, token, live) = experts, rows_of(t)
        x, g_t = x2[token], g[token]
        weight = jnp.where(live, pair_weight[pair], 0)[:, None]
        a, u = jnp.dot(x, w_gate[e]), jnp.dot(x, w_up[e])
        hidden = jax.nn.silu(a) * u
        back = jnp.dot(g_t, w_down[e].T)            # d out / d hidden
        d_weight = lax.dynamic_update_slice_in_dim(
            d_weight, (hidden.astype(f32) * back.astype(f32)).sum(-1),
            t * _TILE, 0)
        d_down = d_down.at[e].add(jnp.dot(
            (hidden * weight.astype(hidden.dtype)).T, g_t,
            preferred_element_type=f32))
        a32, d_hidden = a.astype(f32), back.astype(f32) * weight
        sig = jax.nn.sigmoid(a32)
        d_u = (d_hidden * a32 * sig).astype(x.dtype)
        d_a = (d_hidden * u.astype(f32) * sig * (1 + a32 * (1 - sig))
               ).astype(x.dtype)
        d_up = d_up.at[e].add(jnp.dot(x.T, d_u, preferred_element_type=f32))
        d_gate = d_gate.at[e].add(
            jnp.dot(x.T, d_a, preferred_element_type=f32))
        d_x = lax.dynamic_update_slice_in_dim(
            d_x, jnp.dot(d_u, w_up[e].T, preferred_element_type=f32)
            + jnp.dot(d_a, w_gate[e].T, preferred_element_type=f32),
            t * _TILE, 0)
        return d_x, (d_up, d_down, d_gate), d_weight

    d_rows, d_experts, d_slots = lax.fori_loop(
        0, jnp.maximum(occupied, floor),
        relu2_body if len(experts) == 2 else swiglu_body,
        (_layout_buffer(rows, (d,), f32, g),
         tuple(jnp.zeros(w.shape, f32) for w in experts),
         _layout_buffer(rows, (), f32, g)))
    d_x = _combine(d_rows, slot.reshape(tokens, top_k),
                   jnp.ones((tokens, top_k), f32), x2.dtype)
    return (d_x,
            tuple(d.astype(w.dtype) for d, w in zip(d_experts, experts)),
            d_slots[slot].astype(pair_weight.dtype), None, None)


_expert_tiles.defvjp(_expert_tiles_fwd, _expert_tiles_bwd)


def floor_tiles(tokens: int, top_k: int, held: int, n_experts: int) -> int:
    """Tiles the dispatch loop always runs: those of a buffer of twice the
    pairs that uniform routing sends to the held experts. With weights as
    drawn a router is far from uniform (over 640 readings on the chip a
    share's load lay between 0.31 and 2.58 times the uniform one, median
    0.98: PERF.md, PR 36); up to this many tiles a step's time does not
    depend on the draw, past it the loop goes on over the occupied tiles
    and no pair is dropped."""
    return -(-2 * tokens * top_k * held // (n_experts * _TILE))


def routed_share_ffn(x: jax.Array, params: Dict[str, Any], n_experts: int,
                     top_k: int, offset: int = 0,
                     scale: float = 1.0) -> jax.Array:
    """``x`` ``(batch, seq, d)`` -> ``sum_k w_k expert_k(x) + shared(x)``
    over the experts ``offset .. offset + held - 1`` that ``params`` holds
    (``held = params["w_up"].shape[0]``); what the other experts of the
    layer would add is left out. What ``params`` holds decides the rest
    (:func:`init_routed_share_params`).

    Routing (:func:`route_top_k`): ``s = sigmoid(x W_r)`` over all
    ``n_experts``, product and scores in float32; the ``top_k`` largest
    of ``s`` (of ``s + router_bias`` where ``params`` has that leaf: the
    bias selects, and neither weighs nor gets a gradient); weights ``s_k
    / (sum_k s_k + 1e-20) * scale`` (the sum over all ``top_k`` chosen,
    held or not). An expert is ``W_down relu(W_up x)^2``, or ``W_down
    (silu(W_gate x) * W_up x)`` where ``params`` has ``w_gate``; the
    shared expert, where ``params`` has one, ``W_sd relu(W_su x)^2`` at
    its own width for every token.

    No pair that lands on a held expert is dropped, whatever the load. The
    pairs are sorted by expert (those of absent experts last) and laid out
    with each expert's rows padded to whole tiles of ``_TILE``, so that a
    tile's rows share one expert, and one loop runs over the occupied
    tiles (:func:`_expert_tiles`; at least :func:`floor_tiles` of them),
    writing each tile's rows to its own place in the layout; after the
    loop they go back, weighted, to their tokens (:func:`_combine`). Scopes
    ``moe.route``, ``moe.routed`` (sort, dispatch, expert products, the
    gather that combines) and ``moe.shared`` (none without a shared
    expert); with tracing on each call adds 1 to ``moe.layers`` and each
    combine built, forward or backward, 1 to ``moe.combine.gathers`` (at
    trace time)."""
    b, s, d = x.shape
    tokens, held = b * s, params["w_up"].shape[0]
    if not 1 <= top_k <= n_experts:
        raise ValueError(
            f"mpi_tpu: moe top_k={top_k} must be in [1, n_experts="
            f"{n_experts}]")
    if offset < 0 or offset + held > n_experts:
        raise ValueError(
            f"mpi_tpu: experts {offset}..{offset + held - 1} are not among "
            f"the layer's {n_experts}")
    floor = floor_tiles(tokens, top_k, held, n_experts)
    trace.count("moe.layers")
    x2 = x.reshape(tokens, d)

    with jax.named_scope("moe.route"):
        idx, weight = route_top_k(x2, params["router"], top_k, scale,
                                  params.get("router_bias"))
        local = idx - offset
        # A pair's key: its expert's place in the share, or ``held`` for
        # an absent expert, so that a sort puts the share's pairs first.
        key = jnp.where((local >= 0) & (local < held), local,
                        held).reshape(-1)

    with jax.named_scope("moe.routed"):
        order = jnp.argsort(key, stable=True)
        experts = tuple(params[name].astype(x.dtype) for name in (
            "w_up", "w_down", "w_gate") if name in params)
        routed = _expert_tiles(x2, experts, weight.reshape(-1), key, order,
                               top_k, floor)
    if "shared_up" not in params:
        return routed.reshape(b, s, d)

    with jax.named_scope("moe.shared"):
        # Named for ``checkpointed_block``, which does not hold it: the
        # table above ``_REMAT_KEEPS`` (models/transformer.py) says why.
        up = checkpoint_name(
            jnp.einsum("td,df->tf", x2, params["shared_up"].astype(x.dtype)),
            "moe_shared_up")
        shared = jnp.einsum("tf,fd->td", _relu2(up),
                            params["shared_down"].astype(x.dtype))
    return (routed + shared).reshape(b, s, d)
