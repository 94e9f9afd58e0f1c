"""The routed share's combine (``models/moe.py``): a tile writes its
expert's rows, unweighted, to its own contiguous rows of the tile layout,
and after the loop a gather returns them, weighted, to their tokens; the
backward loop does the same with its rows of the input's gradient and of
the pair weights' gradient. Both expert bodies (relu², as in the
nemotron_h layers, and SwiGLU, as in the lfm2_moe ones) under each load
the loop sees, against every pair through its expert summed in float32
and against the same pairs scatter-added into their tokens' rows. Small
sizes, seeded, CPU, float32.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tpu.models import moe
from mpi_tpu.models.moe import routed_share_ffn

D, FF, EXPERTS, TOP_K = 24, 16, 16, 4
PAIR_TILE, PAIR_TOKENS = 16, 48
# A load the loop sees -> (held experts, the loop's floor in tiles)
LOADS = {"under_floor": (4, 8), "over_floor": (4, 2), "no_pair": (4, 2),
         "top_k_over_held": (3, 1)}


def _relu2_rows(x, w_up, w_down):
    """Row ``p`` of ``x`` through its own ``w_up[p]``, ``w_down[p]``."""
    hidden = jnp.einsum("pd,pdf->pf", x, w_up)
    return jnp.einsum("pf,pfd->pd", jnp.square(jax.nn.relu(hidden)), w_down)


def _swiglu_rows(x, w_up, w_down, w_gate):
    """Row ``p`` of ``x`` through its own ``w_gate[p]``, ``w_up[p]``,
    ``w_down[p]``."""
    gate = jax.nn.silu(jnp.einsum("pd,pdf->pf", x, w_gate))
    return jnp.einsum("pf,pfd->pd", gate * jnp.einsum("pd,pdf->pf", x, w_up),
                      w_down)


# An expert body -> (its matrices, a row through them, the routed scale)
BODIES = {"relu2": (("w_up", "w_down"), _relu2_rows, 2.5),
          "swiglu": (("w_up", "w_down", "w_gate"), _swiglu_rows, 1.0)}


@pytest.fixture(scope="module")
def whole_layer():
    """Every expert of a layer of sixteen, with a gate for SwiGLU, and
    some tokens."""
    ks = jax.random.split(jax.random.PRNGKey(41), 5)
    dense = lambda k, shape: (                               # noqa: E731
        jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-2]))
    return {
        "router": dense(ks[0], (D, EXPERTS)),
        "w_up": dense(ks[1], (EXPERTS, D, FF)),
        "w_down": dense(ks[2], (EXPERTS, FF, D)),
        "w_gate": dense(ks[3], (EXPERTS, D, FF)),
    }, jax.random.normal(ks[4], (2, 40, D), jnp.float32)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _far(got, want):
    """Each leaf's relative distance; a leaf that should be zero is held
    to exactly zero (0 where it is)."""
    def far(g, w):
        if not np.any(np.asarray(w)):
            return 0.0 if not np.any(np.asarray(g)) else np.inf
        return _rel(g, w)
    return jax.tree.map(far, got, want)


def _keys(load, held, seed=41):
    """Each pair's held expert (``held`` where it is absent), ``(tokens *
    TOP_K,)``: uniform choices (``under_floor``); held expert 1 first for
    every token (``over_floor``); only absent experts (``no_pair``);
    every held expert and absent ones besides (``top_k_over_held``)."""
    rng = np.random.default_rng(seed)
    every = np.arange(EXPERTS)

    def one_token():
        if load == "no_pair":
            return rng.choice(every[held:], TOP_K, replace=False)
        if load == "over_floor":
            return np.r_[1, rng.choice(np.delete(every, 1), TOP_K - 1,
                                       replace=False)]
        if load == "top_k_over_held":
            return np.r_[rng.permutation(held),
                         rng.choice(every[held:], TOP_K - held,
                                    replace=False)]
        return rng.choice(every, TOP_K, replace=False)

    idx = np.stack([one_token() for _ in range(PAIR_TOKENS)])
    return jnp.asarray(np.where(idx < held, idx, held).reshape(-1))


def _per_pair(rows, x2, experts, pair_weight, key, scatter=False):
    """Every pair through its expert (``rows``), weighted, summed in
    float32 over a token's pairs; with ``scatter`` each pair is added into
    its token's row instead, by one scatter-add."""
    held = experts[0].shape[0]
    e = jnp.minimum(key, held - 1)
    out = rows(jnp.repeat(x2, TOP_K, axis=0),
               *(w[e] for w in experts)).astype(jnp.float32)
    out = out * jnp.where(key < held, pair_weight, 0)[:, None]
    if scatter:
        return jnp.zeros(x2.shape, jnp.float32).at[
            jnp.arange(key.size) // TOP_K].add(out)
    return out.reshape(-1, TOP_K, x2.shape[1]).sum(1)


def _tile_loop_against_per_pair(layer, body, load, seed):
    """The distances of ``_expert_tiles``' value and its gradients with
    respect to the input rows, every expert matrix and the pair weights
    from the per-pair sum (``False``) and the scatter-add (``True``)."""
    names, rows, _ = BODIES[body]
    held, floor = LOADS[load]
    key = _keys(load, held)
    order = jnp.argsort(key, stable=True)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x2 = jax.random.normal(ks[0], (PAIR_TOKENS, D), jnp.float32)
    pair_weight = jax.random.uniform(ks[1], key.shape, jnp.float32)
    weigh = jax.random.normal(ks[2], x2.shape, jnp.float32)
    experts = tuple(layer[n][:held] for n in names)

    def system(x2, experts, pair_weight):
        return jnp.sum(moe._expert_tiles(x2, experts, pair_weight, key, order,
                                         TOP_K, floor) * weigh)

    got = jax.value_and_grad(system, argnums=(0, 1, 2))(
        x2, experts, pair_weight)
    far = {}
    for scatter in (False, True):
        def plain(x2, experts, pair_weight):
            return jnp.sum(_per_pair(rows, x2, experts, pair_weight, key,
                                     scatter) * weigh)

        want = jax.value_and_grad(plain, argnums=(0, 1, 2))(
            x2, experts, pair_weight)
        far[scatter] = max(jax.tree.leaves(_far(got, want)))
    return got, far


@pytest.mark.parametrize("load", sorted(LOADS))
def test_slots_name_the_rows_the_loop_writes(load, monkeypatch):
    """Every held pair has a row of its own among the occupied tiles'
    rows, the row its tile writes; an absent pair names the zero row past
    the layout, which holds every tile the loop can run."""
    monkeypatch.setattr(moe, "_TILE", PAIR_TILE)
    held, floor = LOADS[load]
    key = _keys(load, held)
    rows = moe._layout_rows(PAIR_TOKENS, TOP_K, held, floor)
    occupied, rows_of, slot = moe._tile_layout(
        key, jnp.argsort(key, stable=True), held, TOP_K, rows)
    occupied, slot, key = int(occupied), np.asarray(slot), np.asarray(key)
    on = key < held
    assert {"under_floor": 0 < occupied < floor, "over_floor":
            occupied > floor, "no_pair": occupied == 0,
            "top_k_over_held": TOP_K > held and occupied > floor}[load]
    assert max(occupied, floor) * PAIR_TILE <= rows
    assert np.all(slot[~on] == rows)
    assert len(set(slot[on])) == on.sum()
    assert np.all(slot[on] < occupied * PAIR_TILE)
    for t in range(occupied):
        e, pair, _, live = (np.asarray(a) for a in rows_of(t))
        assert np.array_equal(slot[pair[live]],
                              t * PAIR_TILE + np.flatnonzero(live))
        assert np.all(key[pair[live]] == e)


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_tile_loop_and_its_gradient_equal_a_per_pair_sum(
        whole_layer, body, load, monkeypatch):
    """The tile loop, forward and backward, under each load: its output
    and its gradients with respect to the input rows, every expert matrix
    and the pair weights, against every pair through its expert summed in
    float32, and against the same pairs added into their tokens' rows one
    by one."""
    monkeypatch.setattr(moe, "_TILE", PAIR_TILE)
    _, far = _tile_loop_against_per_pair(whole_layer[0], body, load, 411)
    assert max(far.values()) < 1e-5, far


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("body", sorted(BODIES))
def test_rows_the_loop_leaves_unwritten_are_never_read(
        whole_layer, body, load, monkeypatch):
    """The layout's buffers are left as allocated, which on a TPU is
    whatever memory held: filled here with NaN but for the zero row, the
    loop's output and every gradient are finite and equal the per-pair
    sum, so nothing reads a row that no tile wrote (a padding pair of the
    later-pair loop multiplies a row by 0, and 0 x NaN is NaN)."""
    monkeypatch.setattr(moe, "_TILE", PAIR_TILE)

    def unwritten(rows, row_shape, dtype, after):
        return jnp.full((rows + 1, *row_shape), jnp.nan, dtype).at[rows].set(0)

    monkeypatch.setattr(moe, "_layout_buffer", unwritten)
    got, far = _tile_loop_against_per_pair(whole_layer[0], body, load, 412)
    assert all(np.isfinite(np.asarray(leaf)).all()
               for leaf in jax.tree.leaves(got))
    assert max(far.values()) < 1e-5, far


@pytest.mark.parametrize("how, held, floor", [
    ("one", 4, 20), ("all", 4, 1), ("none", 4, 2), ("all", 3, 1)],
    ids=["under_floor", "over_floor", "no_pair", "top_k_over_held"])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_share_and_its_gradient_equal_a_per_pair_sum(
        whole_layer, body, how, held, floor, monkeypatch):
    """``routed_share_ffn`` with no shared expert, a selection bias
    steering each load (one held expert in every token's choice, every
    held expert, none) and the loop's floor set: its output and its
    gradients with respect to the input, the router (through the pair
    weights) and every expert matrix, against the per-pair sum on the same
    choices."""
    monkeypatch.setattr(moe, "_TILE", PAIR_TILE)
    monkeypatch.setattr(moe, "floor_tiles", lambda *_: floor)
    names, rows, scale = BODIES[body]
    layer, x = whole_layer
    offset = 4
    bias = np.zeros(EXPERTS, np.float32)
    here = slice(offset + 1, offset + 2) if how == "one" else slice(
        offset, offset + held)
    bias[here] = -10.0 if how == "none" else 10.0
    share = {n: layer[n][offset:offset + held] for n in names}
    share.update(router=layer["router"], router_bias=jnp.asarray(bias))
    weigh = jax.random.normal(jax.random.PRNGKey(413), x.shape, jnp.float32)

    def keys(p, x):
        idx, weight = moe.route_top_k(x.reshape(-1, D), p["router"], TOP_K,
                                      scale, p["router_bias"])
        local = idx - offset
        return jnp.where((local >= 0) & (local < held), local,
                         held).reshape(-1), weight.reshape(-1)

    key = keys(share, x)[0]
    occupied = int(moe._tile_layout(
        key, jnp.argsort(key, stable=True), held, TOP_K,
        moe._layout_rows(key.size // TOP_K, TOP_K, held, floor))[0])
    assert {"one": 0 < occupied < floor, "none": occupied == 0}.get(
        how, occupied > floor)

    def system(p, x):
        return jnp.sum(routed_share_ffn(x, p, EXPERTS, TOP_K, offset=offset,
                                        scale=scale) * weigh)

    def plain(p, x):
        key, weight = keys(p, x)
        return jnp.sum(_per_pair(rows, x.reshape(-1, D),
                                 tuple(p[n] for n in names), weight,
                                 key).reshape(x.shape) * weigh)

    got = jax.value_and_grad(system, argnums=(0, 1))(share, x)
    want = jax.value_and_grad(plain, argnums=(0, 1))(share, x)
    far = _far(got, want)
    assert max(jax.tree.leaves(far)) < 1e-5, far


@pytest.mark.parametrize("grad, built", [(False, 1), (True, 2)],
                         ids=["forward", "forward_and_backward"])
@pytest.mark.parametrize("body", sorted(BODIES))
def test_each_combine_built_is_counted(whole_layer, traced, body, grad,
                                       built):
    """``moe.combine.gathers``: 1 for the forward combine of a traced
    layer, 1 more where its backward is traced too."""
    names, _, scale = BODIES[body]
    layer, x = whole_layer
    share = {n: layer[n][4:8] for n in names}
    share["router"] = layer["router"]

    def f(p, x):
        return jnp.sum(routed_share_ffn(x, p, EXPERTS, TOP_K, offset=4,
                                        scale=scale))

    jax.make_jaxpr(jax.grad(f, argnums=(0, 1)) if grad else f)(share, x)
    assert traced.counters()["moe.combine.gathers"] == built
