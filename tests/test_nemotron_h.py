"""A ``nemotron_h`` layer stack on the normal train path (ISSUE 36): a
block stack driven by ``layer_pattern``, the Mamba-2 mixer, one device's
share of a sigmoid-routed expert layer that drops no pair, attention with
a head size of its own and no position term: each against the benchmark's
plain reference (``benchmark/reference/nemotron_h_lm.py``), which shares
no code with ``mpi_tpu``. Small sizes, seeded, CPU, float32.
"""

import dataclasses
import importlib.util
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tpu.models import (TransformerConfig, make_mesh_nd, make_train_step,
                            moe)
from mpi_tpu.models.mamba2 import mamba2_mixer
from mpi_tpu.models.moe import floor_tiles, routed_share_ffn
from mpi_tpu.models.transformer import (forward, init_params, loss_fn,
                                        routed_choices,
                                        param_specs)

ROOT = Path(__file__).resolve().parent.parent
SEQ = 32
MODEL = dict(
    vocab=64, d_model=48, n_heads=4, n_kv_heads=2, attn_head_dim=16, d_ff=24,
    n_layers=9, layer_pattern="MEMEM*EME", norm="rmsnorm_unit_offset",
    ffn="relu2", tie_embeddings=False, position_table=False,
    attention_impl="dense", ssm_heads=8, ssm_head_dim=8, ssm_groups=2,
    ssm_state=16, ssm_conv=4, ssm_chunk=8, n_experts=16, moe_top_k=3,
    moe_experts_held=4, moe_expert_offset=4, moe_shared_d_ff=40,
    moe_routed_scale=2.5, moe_aux_coef=0.0)


@pytest.fixture(scope="module")
def reference():
    path = ROOT / "benchmark" / "reference" / "nemotron_h_lm.py"
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _cfg(**over):
    return TransformerConfig(**dict(MODEL, max_seq=SEQ + 1, **over))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _leaf_errors(got, want):
    far = jax.tree.map(_rel, got, want)
    return {jax.tree_util.keystr(path): e
            for path, e in jax.tree.leaves_with_path(far)}


@pytest.fixture(scope="module")
def drawn():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(36), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(37), (2, SEQ + 1), 0,
                                cfg.vocab)
    return cfg, params, tokens


# --------------------------------------------------------------------------
# The stack
# --------------------------------------------------------------------------

def test_each_block_holds_one_norm_and_its_own_leaves(drawn):
    cfg, params, _ = drawn
    by_kind = {
        "M": {"ln1", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "ssm_norm", "out_proj"},
        "*": {"ln1", "wq", "wk", "wv", "wo"},
        "E": {"ln1", "router", "w_up", "w_down", "shared_up", "shared_down"}}
    assert isinstance(params["blocks"], list)
    for kind, blk in zip(cfg.layer_pattern, params["blocks"]):
        assert set(blk) == by_kind[kind]
    assert "pos" not in params and "head" in params
    attn = params["blocks"][5]
    assert attn["wq"].shape == (48, 4, 16) and attn["wk"].shape == (48, 2, 16)
    assert attn["wo"].shape == (4, 16, 48)           # 4 x 16 = 64 != 48
    mixer = params["blocks"][0]
    assert mixer["in_proj"].shape == (48, 64 + (64 + 2 * 2 * 16) + 8)
    assert mixer["conv_w"].shape == (4, 128)
    experts = params["blocks"][1]
    assert experts["router"].shape == (48, 16)       # every expert scored
    assert experts["w_up"].shape == (4, 48, 24)      # four held
    assert experts["shared_up"].shape == (48, 40)
    specs = param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(
            s, jax.sharding.PartitionSpec))


def test_initial_values_are_the_assumed_ones(drawn):
    _, params, _ = drawn
    mixer = params["blocks"][0]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(mixer["dt_bias"], np.float64)))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001
    assert np.all(np.asarray(mixer["D"]) == 1)
    assert np.all(np.asarray(mixer["ssm_norm"]) == 1)
    assert np.all(np.asarray(mixer["conv_b"]) == 0)
    assert abs(float(np.std(mixer["in_proj"])) * math.sqrt(48) - 1) < 0.05


def test_loss_and_every_leafs_gradient_equal_the_references(drawn, reference):
    cfg, params, tokens = drawn
    one = tokens[:1]
    got = jax.value_and_grad(loss_fn)(params, one, cfg)
    want = jax.value_and_grad(
        lambda p: reference.sequence_loss(p, one[0], MODEL))(params)
    assert abs(float(got[0]) - float(want[0])) < 1e-5
    errors = _leaf_errors(got[1], want[1])
    assert len(errors) == 4 * 9 + 5 + 4 * 6 + 3
    assert max(errors.values()) < 1e-4, errors


def test_routed_choices_are_what_the_layers_decide(drawn, reference):
    """For every ``E`` block the router's input and the experts chosen: the
    reference, told to use them, gives the loss it gives by itself, and
    none of them lies outside its own top ``k`` of the same input."""
    cfg, params, tokens = drawn
    one = tokens[:1]
    choices = routed_choices(params, one[:, :-1], cfg)
    assert len(choices) == cfg.layer_pattern.count("E")
    for h, idx in choices:
        assert h.shape == (SEQ, cfg.d_model)
        assert idx.shape == (SEQ, cfg.moe_top_k) and idx.dtype == jnp.int32
        assert int(idx.min()) >= 0 and int(idx.max()) < cfg.n_experts
    routed = [blk for blk, kind in zip(params["blocks"], cfg.layer_pattern)
              if kind == "E"]
    for (h, idx), blk in zip(choices, routed):
        assert float(reference.choices_outside_top_k(h, idx, blk,
                                                     MODEL)) == 0.0
    own = reference.sequence_loss(params, one[0], MODEL)
    told = reference.sequence_loss(params, one[0], MODEL,
                                   routing=[idx for _, idx in choices])
    assert abs(float(own) - float(told)) < 1e-6
    with pytest.raises(ValueError, match="needs a layer_pattern"):
        routed_choices(params, one[:, :-1], TransformerConfig())


def test_the_reference_follows_the_choices_it_is_given(drawn, reference):
    """Other experts than its own give another loss and another gradient
    of the router, and such choices count as outside its top ``k``."""
    cfg, params, tokens = drawn
    one = tokens[0]
    choices = routed_choices(params, one[None, :-1], cfg)
    shifted = [(idx + 1) % cfg.n_experts for _, idx in choices]
    own = jax.value_and_grad(
        lambda p: reference.sequence_loss(p, one, MODEL))(params)
    told = jax.value_and_grad(lambda p: reference.sequence_loss(
        p, one, MODEL, routing=shifted))(params)
    assert abs(float(own[0]) - float(told[0])) > 1e-4
    assert _rel(told[1]["blocks"][1]["router"],
                own[1]["blocks"][1]["router"]) > 0.1
    (h, idx), blk = choices[0], params["blocks"][1]
    outside = float(reference.choices_outside_top_k(
        h, (idx + 1) % cfg.n_experts, blk, MODEL))
    assert 0.1 < outside <= 1.0
    one_wrong = idx.at[0, 0].set(
        int(jnp.setdiff1d(jnp.arange(cfg.n_experts), idx[0])[0]))
    assert float(reference.choices_outside_top_k(
        h, one_wrong, blk, MODEL)) == pytest.approx(
            1 / (SEQ * cfg.moe_top_k))


@pytest.mark.parametrize("at", [0, 2, 7])
def test_scan_inputs_are_what_enters_the_recurrence(drawn, reference, at):
    """``scan_inputs`` of an ``M`` block, through the program's chunked
    scan, is the reference's own recurrence on them."""
    from mpi_tpu.ops.ssd import ssd_scan

    cfg, params, tokens = drawn
    x, dt, A, B, C, D = reference.scan_inputs(tokens[0], params, MODEL, at)
    assert x.shape == (SEQ, cfg.ssm_heads, cfg.ssm_head_dim)
    assert dt.shape == (SEQ, cfg.ssm_heads) and float(dt.min()) > 0
    assert B.shape == C.shape == (SEQ, cfg.ssm_groups, cfg.ssm_state)
    want = reference.selective_scan(x, dt, A, B, C, D)
    got = ssd_scan(x[None], dt[None], A, B[None], C[None], D,
                   cfg.ssm_chunk)[0]
    assert _rel(got, want) < 1e-5


def test_scan_inputs_of_another_kind_of_block_are_refused(drawn, reference):
    _, params, tokens = drawn
    with pytest.raises(AssertionError):
        reference.scan_inputs(tokens[0], params, MODEL, 1)


def test_remat_changes_no_value(drawn):
    cfg, params, tokens = drawn
    plain = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    remat = jax.value_and_grad(loss_fn)(params, tokens, _cfg(remat=True))
    assert abs(float(plain[0]) - float(remat[0])) < 1e-6
    assert max(_leaf_errors(remat[1], plain[1]).values()) < 1e-5


def test_one_train_step_moves_every_leaf_by_the_references_gradient(
        drawn, reference):
    """Through ``make_train_step`` on a one-device mesh with plain SGD, so
    that ``(before - after) / learning rate`` is the gradient the step
    used: the mean over the batch of the reference's gradients."""
    cfg, params, tokens = drawn
    rate = 0.5
    init_state, step = make_train_step(cfg, mesh=make_mesh_nd(1),
                                       learning_rate=rate, optimizer="sgd")
    state = init_state(jax.random.PRNGKey(36))
    before = jax.tree.map(np.asarray, state["params"])
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(before), jax.tree.leaves(params)))
    state, loss = step(state, tokens)
    per_seq = [jax.value_and_grad(
        lambda p, t=t: reference.sequence_loss(p, t, MODEL))(params)
        for t in tokens]
    want_loss = np.mean([float(v) for v, _ in per_seq])
    want_grad = jax.tree.map(lambda *g: sum(g) / len(g),
                             *[g for _, g in per_seq])
    assert abs(float(loss) - want_loss) < 1e-5
    used = jax.tree.map(lambda a, b: (a - np.asarray(b)) / rate, before,
                        state["params"])
    assert max(_leaf_errors(used, want_grad).values()) < 2e-3


def test_a_dp_mesh_runs_the_scan_per_shard_and_changes_no_value(drawn):
    """The scan's kernels are Mosaic calls that GSPMD cannot partition: on
    a dp mesh the mixer hands the scan each device's rows of the batch
    under ``shard_map`` (the compile for four chips is held in
    test_tpu_compile.py). Loss and every gradient, the replicated ``A_log``
    and ``D`` with their sum over the shards, equal the unsharded ones."""
    cfg, params, _ = drawn
    tokens = jax.random.randint(jax.random.PRNGKey(38), (4, SEQ + 1), 0,
                                cfg.vocab)
    mesh = make_mesh_nd(2, axes=("dp", "tp"), devices=jax.devices()[:2])
    sharded = jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg, mesh))
    jaxpr = str(jax.make_jaxpr(sharded)(params, tokens))
    assert jaxpr.count("shard_map") >= cfg.layer_pattern.count("M")
    got, want = jax.jit(sharded)(params, tokens), jax.value_and_grad(
        loss_fn)(params, tokens, cfg)
    assert abs(float(got[0]) - float(want[0])) < 1e-5
    assert max(_leaf_errors(got[1], want[1]).values()) < 2e-5


def test_a_mesh_that_would_split_a_layer_is_refused_by_name(drawn):
    cfg, params, tokens = drawn
    mesh = make_mesh_nd(2, axes=("dp", "tp"), devices=jax.devices()[:2])
    assert dict(mesh.shape) == {"dp": 2, "tp": 1}
    assert np.isfinite(float(loss_fn(params, tokens, cfg, mesh)))
    for axis in ("tp", "ep", "sp"):
        split = make_mesh_nd(2, axes=(axis,), devices=jax.devices()[:2])
        with pytest.raises(ValueError, match=f"{axis}=2.*not split over"):
            loss_fn(params, tokens, cfg, split)


@pytest.mark.parametrize("over, said", [
    (dict(layer_pattern="MEMEM*EMX"), "letters of M"),
    (dict(layer_pattern="MEM"), "n_layers=9 letters"),
    (dict(ssm_heads=0), "an M layer needs ssm_heads"),
    (dict(ssm_heads=9), "whole groups of ssm_groups=2"),
    (dict(ffn="gelu"), "experts of an E layer are relu2 .* or swiglu"),
    (dict(ffn="swiglu"), "beside swiglu experts moe_shared_d_ff must be 0"),
    (dict(moe_expert_offset=14),
     "moe_expert_offset=14 \\+ moe_experts_held=4"),
    (dict(moe_shared_d_ff=-1), "moe_shared_d_ff >= 0 \\(got -1"),
    (dict(rope=True), "position_table=False means no position term"),
])
def test_a_pattern_the_stack_cannot_run_is_refused(over, said):
    with pytest.raises(ValueError, match=said):
        _cfg(**over)


def test_ragged_sequence_is_refused_by_the_mixer(drawn):
    cfg, params, tokens = drawn
    with pytest.raises(ValueError, match="chunks of 8: seq 30"):
        forward(params, tokens[:, :30], cfg)


def test_generate_and_the_pipeline_refuse_the_new_fields_by_name(drawn):
    from mpi_tpu.models import generate
    from mpi_tpu.models.pipeline_lm import _check_cfg

    cfg, params, tokens = drawn
    named = ("attn_head_dim=16", "position_table=False",
             "layer_pattern='MEMEM*EME'", "ffn='relu2'")
    assert set(named) <= set(cfg.beyond_classic_block())
    for name in named:
        with pytest.raises(NotImplementedError, match=re.escape(name)):
            generate(params, tokens[:, :4], cfg, max_new_tokens=2)
    with pytest.raises(ValueError, match="layer_pattern='MMMMM"):
        _check_cfg(_cfg(n_experts=0, layer_pattern="MMMMM*MMM"), 1)
    relu2 = TransformerConfig(ffn="relu2")
    assert relu2.beyond_classic_block() == ("ffn='relu2'",)


def test_relu2_is_a_dense_ffn_of_the_classic_block_too():
    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=1,
                            d_ff=24, max_seq=9, ffn="relu2")
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert set(params["blocks"][0]) >= {"w1", "w2", "ln2"}
    gelu = dataclasses.replace(cfg, ffn="gelu")
    tokens = jnp.arange(9)[None] % 32
    assert float(loss_fn(params, tokens, cfg)) != float(
        loss_fn(params, tokens, gelu))


def test_counters_hold_their_counts(drawn, traced):
    cfg, params, tokens = drawn

    def counted():
        jax.make_jaxpr(lambda p, t: loss_fn(p, t, cfg))(params, tokens)
        return {k: v for k, v in traced.counters().items()
                if k.startswith(("ssm.", "ssd.", "moe."))}

    traced.disable()
    assert counted() == {}
    traced.enable()
    assert counted() == {
        "ssm.layers": 4,
        "ssd.scans.program": 4,         # a state of 16 fills no register
        "moe.layers": 4,
        "moe.combine.gathers": 4}       # a forward pass: no backward built


def test_floor_tiles_hold_twice_the_pairs_of_uniform_routing():
    """The tiles the dispatch loop always runs, in whole tiles of 512."""
    assert moe._TILE == 512
    assert floor_tiles(16384, 6, 8, 128) == 2 * 6144 // 512        # the cell
    assert floor_tiles(8192, 6, 8, 128) == 2 * 3072 // 512
    assert floor_tiles(1000, 6, 8, 128) == 2                       # 750 up
    assert floor_tiles(16, 6, 8, 128) == 1


# --------------------------------------------------------------------------
# The mixer
# --------------------------------------------------------------------------

def test_mixer_equals_the_references(drawn, reference):
    cfg, params, _ = drawn
    blk = params["blocks"][0]
    h = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, cfg.d_model),
                          jnp.float32)
    weigh = jax.random.normal(jax.random.PRNGKey(2), h.shape, jnp.float32)

    def system(blk, h):
        return jnp.sum(mamba2_mixer(h, blk, cfg) * weigh)

    def plain(blk, h):
        return jnp.sum(jnp.stack(
            [reference._mamba(row, blk, MODEL) for row in h]) * weigh)

    got = jax.value_and_grad(system, argnums=(0, 1))(blk, h)
    want = jax.value_and_grad(plain, argnums=(0, 1))(blk, h)
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    assert max(_leaf_errors(got[1], want[1]).values()) < 1e-4


def test_mixer_scopes_are_in_the_lowered_text(drawn):
    cfg, params, tokens = drawn
    text = jax.jit(lambda p, t: loss_fn(p, t, cfg)).lower(
        params, tokens).as_text(debug_info=True)
    for scope in ("attn/ssm/ssm.in_proj", "attn/ssm/ssm.conv",
                  "attn/ssm/ssm.scan", "attn/ssm/ssm.norm",
                  "attn/ssm/ssm.out_proj", "ffn/moe.route", "ffn/moe.routed",
                  "ffn/moe.shared"):
        assert scope in text, scope


# --------------------------------------------------------------------------
# The routed share
# --------------------------------------------------------------------------

D, FF, SHARED, EXPERTS, TOP_K = 24, 16, 20, 16, 3


@pytest.fixture(scope="module")
def whole_layer():
    """Every expert of a layer of sixteen, and some tokens."""
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    dense = lambda k, shape: (                               # noqa: E731
        jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-2]))
    return {
        "router": dense(ks[0], (D, EXPERTS)),
        "w_up": dense(ks[1], (EXPERTS, D, FF)),
        "w_down": dense(ks[2], (EXPERTS, FF, D)),
        "shared_up": dense(ks[3], (D, SHARED)),
        "shared_down": dense(ks[4], (SHARED, D)),
    }, jax.random.normal(ks[5], (2, 40, D), jnp.float32)


def _share(layer, offset, held, shared=True):
    out = dict(layer, w_up=layer["w_up"][offset:offset + held],
               w_down=layer["w_down"][offset:offset + held])
    if not shared:
        out["shared_up"] = jnp.zeros_like(layer["shared_up"])
    return out


def _plain(reference, layer, x, offset):
    model = dict(moe_top_k=TOP_K, moe_expert_offset=offset,
                 moe_routed_scale=2.5)
    return jnp.stack([reference._experts(row, layer, model) for row in x])


def test_the_shares_add_up_to_the_uncut_layer(whole_layer, reference):
    """The routed parts of all four shares of four experts, plus the
    shared expert once, are what the reference gives for the whole layer
    (all sixteen held)."""
    layer, x = whole_layer
    want = _plain(reference, layer, x, 0)
    routed = [routed_share_ffn(x, _share(layer, off, 4, shared=False),
                               EXPERTS, TOP_K, offset=off, scale=2.5)
              for off in range(0, EXPERTS, 4)]
    with_shared = routed_share_ffn(x, _share(layer, 0, 4), EXPERTS, TOP_K,
                                   offset=0, scale=2.5)
    shared_once = with_shared - routed[0]
    assert _rel(sum(routed) + shared_once, want) < 1e-5
    assert _rel(routed[0], want) > 0.1        # one share is not the layer
    # and the uncut layer through the same code, every expert held
    assert _rel(routed_share_ffn(x, layer, EXPERTS, TOP_K, scale=2.5),
                want) < 1e-5


def _steered(layer, offset, held, how):
    """Tokens and a router under which every token chooses held expert
    ``offset + 1`` (``one``), every choice of every token is a held expert
    (``all``), or no token chooses a held expert (``none``)."""
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (2, 40, D),
                                  jnp.float32)) + 0.1
    router = np.array(layer["router"])
    here = slice(offset, offset + held)
    if how == "one":
        router[:, offset + 1] = 0.3
    elif how == "all":      # scores apart and short of saturation
        router[:, here] = (np.abs(router[:, here]) + 0.5) / 8
        rest = np.ones(EXPERTS, bool)
        rest[here] = False
        router[:, rest] = -(np.abs(router[:, rest]) + 0.5) / 8
    else:
        router[:, here] = -0.3
    return x, dict(layer, router=jnp.asarray(router))


@pytest.mark.parametrize("tile", [512, 16])
@pytest.mark.parametrize("how", ["one", "all", "none"])
def test_no_pair_on_a_held_expert_is_dropped(whole_layer, reference, how,
                                             tile, monkeypatch):
    """Whatever the load: with tiles of 512 rows (the 0 to 240 pairs lie
    inside the tiles the loop always runs, or one tile an expert past
    them) and with tiles of 16, where the loop goes many tiles past."""
    monkeypatch.setattr(moe, "_TILE", tile)
    layer, _ = whole_layer
    offset, held = 4, 4
    x, layer = _steered(layer, offset, held, how)
    share = _share(layer, offset, held)
    scores = jax.nn.sigmoid(x.reshape(-1, D) @ layer["router"])
    chosen = np.asarray(jax.lax.top_k(scores, TOP_K)[1])
    here = (chosen >= offset) & (chosen < offset + held)
    assert {"one": np.all((chosen == offset + 1).sum(1) == 1),
            "all": here.all(), "none": not here.any()}[how]
    weigh = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)

    def system(p, x):
        return jnp.sum(routed_share_ffn(x, p, EXPERTS, TOP_K, offset=offset,
                                        scale=2.5) * weigh)

    def plain(p, x):
        return jnp.sum(_plain(reference, p, x, offset) * weigh)

    got = jax.value_and_grad(system, argnums=(0, 1))(share, x)
    want = jax.value_and_grad(plain, argnums=(0, 1))(share, x)
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    errors = _leaf_errors(got[1], want[1])
    if how == "none":   # nothing reaches the held experts: zero, not NaN
        for name in ("router", "w_up", "w_down"):
            assert not np.any(np.asarray(got[1][0][name]))
            assert not np.any(np.asarray(want[1][0][name]))
            errors.pop(f"[0]['{name}']")
    assert max(errors.values()) < 1e-4, errors


def test_a_share_outside_the_layer_is_refused(whole_layer):
    layer, x = whole_layer
    with pytest.raises(ValueError, match="experts 14..17 are not among"):
        routed_share_ffn(x, _share(layer, 0, 4), EXPERTS, TOP_K, offset=14)
    with pytest.raises(ValueError, match="top_k=17"):
        routed_share_ffn(x, layer, EXPERTS, 17)


# --------------------------------------------------------------------------
# What was there draws what it drew
# --------------------------------------------------------------------------

# Sum of |leaf| over the tree (float64) and the loss of two sequences, from
# the parent of PR 36 (commit 33c8153) with the same keys, on the CPU.
RECORDED = {
    "classic": (dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                     max_seq=17),
                24, 2673.293344448396, 4.858981609344482),
    "starcoder_like": (dict(vocab=64, d_model=32, n_heads=4, n_kv_heads=2,
                            n_layers=2, d_ff=64, max_seq=17, rope=True,
                            rope_theta=999999.0, attention_impl="flash"),
                       23, 2306.5717929787616, 4.899692535400391),
    "evabyte_like": (dict(vocab=32, d_model=32, n_heads=4, n_layers=2,
                          d_ff=48, max_seq=33, rope=True,
                          norm="rmsnorm_unit_offset", ffn="swiglu",
                          tie_embeddings=False, n_pred_heads=4,
                          residual_dtype="float32", attention_impl="eva",
                          eva_window=16, eva_chunk=4, remat=True),
                     25, 3219.92304251966, 3.9513347148895264),
}


def _parents_draw(key, cfg):
    """``init_params`` as the parent of PR 36 had it, for the leaves these
    three configurations have: the same keys in the same order."""
    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(
            cfg.param_dtype)

    def norm():
        d = cfg.d_model
        if cfg.norm == "layernorm":
            return {"scale": jnp.ones((d,), cfg.param_dtype),
                    "bias": jnp.zeros((d,), cfg.param_dtype)}
        return {"scale": jnp.zeros((d,), cfg.param_dtype)}

    keys = jax.random.split(key, 2 + cfg.n_layers)
    d, f, h = cfg.d_model, cfg.d_ff, cfg.n_heads
    hd, kv = d // h, cfg.kv_heads
    params = {"embed": dense(keys[0], (cfg.vocab, d), d), "final_ln": norm(),
              "blocks": []}
    if not cfg.tie_embeddings:
        params["head"] = dense(jax.random.fold_in(keys[0], 1),
                               (cfg.n_pred_heads * cfg.vocab, d), d)
    if not cfg.rope:
        params["pos"] = dense(keys[1], (cfg.max_seq, d), d)
    for i in range(cfg.n_layers):
        ks = jax.random.split(keys[2 + i], 6)
        blk = {"ln1": norm(), "ln2": norm(),
               "wq": dense(ks[0], (d, h, hd), d),
               "wk": dense(ks[1], (d, kv, hd), d),
               "wv": dense(ks[2], (d, kv, hd), d),
               "wo": dense(ks[3], (h, hd, d), d),
               "w1": dense(ks[4], (d, f), d), "w2": dense(ks[5], (f, d), f)}
        if cfg.ffn == "swiglu":
            blk["w3"] = dense(jax.random.fold_in(keys[2 + i], 6), (d, f), d)
        if cfg.attention_impl == "eva":
            blk["eva_phi"] = jax.random.normal(
                jax.random.fold_in(keys[2 + i], 7), (h, hd)).astype(
                    cfg.param_dtype)
            blk["eva_mu"] = jax.random.normal(
                jax.random.fold_in(keys[2 + i], 8), (h, hd)).astype(
                    cfg.param_dtype)
        params["blocks"].append(blk)
    return params


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_a_configuration_without_the_new_fields_draws_what_it_drew(name):
    kw, leaves, total, loss = RECORDED[name]
    cfg = TransformerConfig(**kw)
    assert cfg.layer_pattern is None and cfg.head_dim == 8
    params = init_params(jax.random.PRNGKey(7), cfg)
    before = _parents_draw(jax.random.PRNGKey(7), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(before)
    assert len(jax.tree.leaves(params)) == leaves
    for (path, a), b in zip(jax.tree.leaves_with_path(params),
                            jax.tree.leaves(before)):
        assert a.dtype == b.dtype and np.array_equal(a, b), path
    got = sum(np.abs(np.asarray(x, np.float64)).sum()
              for x in jax.tree.leaves(params))
    assert abs(got - total) < 1e-6 * total
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, cfg.max_seq), 0,
                                cfg.vocab)
    assert abs(float(loss_fn(params, tokens, cfg)) - loss) < 1e-5
