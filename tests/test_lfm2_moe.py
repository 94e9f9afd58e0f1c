"""An ``lfm2_moe`` layer stack on the normal train path (ISSUE 40): the
pattern letters ``C`` (a gated short conv) and ``F`` (a dense FFN of its
own width), routed experts that are SwiGLU, have no shared expert and are
chosen by score plus a selection bias, and a norm over each head's q and
k: each against the benchmark's plain reference
(``benchmark/reference/lfm2_moe_lm.py``), which shares no code with
``mpi_tpu``. Small sizes, seeded, CPU, float32.
"""

import dataclasses
import importlib.util
import json
import math
import re
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tpu.models import (TransformerConfig, make_mesh_nd, make_train_step,
                            moe)
from mpi_tpu.models.mamba2 import _causal_conv
from mpi_tpu.models.moe import floor_tiles, routed_share_ffn
from mpi_tpu.models.short_conv import short_conv_mixer
from mpi_tpu.models.transformer import (_attention, init_params, loss_fn,
                                        param_specs, routed_choices)

ROOT = Path(__file__).resolve().parent.parent
SEQ = 32
MODEL = dict(
    vocab=64, d_model=32, n_heads=4, n_kv_heads=2, d_ff=16, dense_d_ff=40,
    n_layers=8, layer_pattern="CF*ECECE", norm="rmsnorm_unit_offset",
    ffn="swiglu", tie_embeddings=True, rope=True, rope_theta=1e6,
    qk_norm=True, attention_impl="dense", n_experts=16,
    moe_top_k=4, moe_experts_held=4, moe_expert_offset=4, moe_shared_d_ff=0,
    moe_routed_scale=1.0, moe_router_bias=True, moe_aux_coef=0.0)


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load(ROOT / "benchmark" / "reference" / "lfm2_moe_lm.py")


def _cfg(**over):
    return TransformerConfig(**dict(MODEL, max_seq=SEQ + 1, **over))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _leaf_errors(got, want):
    far = jax.tree.map(_rel, got, want)
    return {jax.tree_util.keystr(path): e
            for path, e in jax.tree.leaves_with_path(far)}


def _stirred(params, seed=40):
    """``params`` with every norm's weight and every selection bias drawn
    away from their starting values, so that each one weighs and that the
    bias chooses other experts than the scores alone would (a bias of
    about the scores' own spread)."""
    def stir(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(name.encode()) % 997)
        if "scale" in name:
            return 0.3 * jax.random.normal(key, x.shape, x.dtype)
        if "router_bias" in name:
            return 0.2 * jax.random.normal(key, x.shape, x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(stir, params)


@pytest.fixture(scope="module")
def drawn():
    cfg = _cfg()
    params = _stirred(init_params(jax.random.PRNGKey(40), cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(41), (2, SEQ + 1), 0,
                                cfg.vocab)
    return cfg, params, tokens


# --------------------------------------------------------------------------
# The stack
# --------------------------------------------------------------------------

def test_each_block_holds_one_norm_and_its_own_leaves(drawn):
    cfg, params, _ = drawn
    by_kind = {
        "C": {"ln1", "in_proj", "conv_w", "out_proj"},
        "F": {"ln1", "w1", "w2", "w3"},
        "*": {"ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm"},
        "E": {"ln1", "router", "router_bias", "w_gate", "w_up", "w_down"}}
    for kind, blk in zip(cfg.layer_pattern, params["blocks"]):
        assert set(blk) == by_kind[kind]
    assert "pos" not in params and "head" not in params     # rope, tied
    conv, dense, attn, experts = params["blocks"][:4]
    assert conv["in_proj"].shape == (32, 96) and conv["conv_w"].shape == (
        3, 32) and conv["out_proj"].shape == (32, 32)
    assert dense["w1"].shape == dense["w3"].shape == (32, 40)  # dense_d_ff
    assert attn["q_norm"]["scale"].shape == (8,)              # head_dim
    assert experts["router"].shape == (32, 16)       # every expert scored
    assert experts["router_bias"].shape == (16,)
    assert experts["router_bias"].dtype == jnp.float32
    assert experts["w_gate"].shape == experts["w_up"].shape == (4, 32, 16)
    assert experts["w_down"].shape == (4, 16, 32)
    specs = param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(
            s, jax.sharding.PartitionSpec))


def test_initial_values_are_the_assumed_ones():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(40), cfg)
    conv, _, attn, experts = params["blocks"][:4]
    assert not np.any(np.asarray(experts["router_bias"]))
    assert not np.any(np.asarray(attn["q_norm"]["scale"]))
    assert abs(float(np.std(conv["conv_w"])) * math.sqrt(3) - 1) < 0.25
    assert abs(float(np.std(conv["in_proj"])) * math.sqrt(32) - 1) < 0.05


def test_a_drawn_selection_bias_is_seeded_and_moves_no_other_draw():
    key = jax.random.PRNGKey(40)
    zero, drawn, again = (init_params(key, _cfg(moe_router_bias_std=std))
                          for std in (0.0, 0.01, 0.01))
    biases = np.concatenate([np.asarray(b["router_bias"])
                             for b in drawn["blocks"] if "router_bias" in b])
    assert biases.size == 16 * 3 and 0.005 < biases.std() < 0.02
    assert jax.tree.all(jax.tree.map(np.array_equal, drawn, again))
    for (path, a), b in zip(jax.tree.leaves_with_path(drawn),
                            jax.tree.leaves(zero)):
        if "router_bias" not in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(a, b)


def test_loss_and_every_leafs_gradient_equal_the_references(drawn, reference):
    """With a selection bias that is not zero, so that what selects and
    what weighs are different numbers."""
    cfg, params, tokens = drawn
    one = tokens[:1]
    got = jax.value_and_grad(loss_fn)(params, one, cfg)
    want = jax.value_and_grad(
        lambda p: reference.sequence_loss(p, one[0], MODEL))(params)
    assert abs(float(got[0]) - float(want[0])) < 1e-5
    errors = _leaf_errors(got[1], want[1])
    biases = [k for k in errors if "router_bias" in k]
    assert len(biases) == 3
    for name in biases:     # it chooses, and nothing flows back into it
        errors.pop(name)
    assert len(errors) == 3 * 4 + 4 + 7 + 3 * 5 + 2
    assert max(errors.values()) < 1e-4, errors
    for blk in got[1]["blocks"]:
        if "router_bias" in blk:
            assert not np.any(np.asarray(blk["router_bias"]))


def test_the_bias_chooses_other_experts_than_the_scores(drawn):
    cfg, params, tokens = drawn
    h, idx = routed_choices(params, tokens[:1, :-1], cfg)[0]
    blk = params["blocks"][3]
    scores = jax.nn.sigmoid(h @ blk["router"])
    by_scores = jax.lax.top_k(scores, cfg.moe_top_k)[1]
    by_both = jax.lax.top_k(scores + blk["router_bias"], cfg.moe_top_k)[1]
    assert np.array_equal(np.sort(idx, -1), np.sort(by_both, -1))
    assert not np.array_equal(np.sort(idx, -1), np.sort(by_scores, -1))


def test_routed_choices_are_what_the_layers_decide(drawn, reference):
    """For every ``E`` block the router's input and the experts chosen by
    score plus bias: none lies outside the reference's float32 top ``k``
    of ``s + b`` for the same input, and the reference, told to use them,
    gives the loss it gives by itself."""
    cfg, params, tokens = drawn
    one = tokens[:1]
    choices = routed_choices(params, one[:, :-1], cfg)
    assert len(choices) == cfg.layer_pattern.count("E")
    routed = [blk for blk, kind in zip(params["blocks"], cfg.layer_pattern)
              if kind == "E"]
    for (h, idx), blk in zip(choices, routed):
        assert h.shape == (SEQ, cfg.d_model)
        assert idx.shape == (SEQ, cfg.moe_top_k) and idx.dtype == jnp.int32
        assert float(reference.choices_outside_top_k(h, idx, blk,
                                                     MODEL)) == 0.0
    own = reference.sequence_loss(params, one[0], MODEL)
    told = reference.sequence_loss(params, one[0], MODEL,
                                   routing=[idx for _, idx in choices])
    assert abs(float(own) - float(told)) < 1e-6


def test_the_reference_follows_the_choices_it_is_given(drawn, reference):
    cfg, params, tokens = drawn
    one = tokens[0]
    choices = routed_choices(params, one[None, :-1], cfg)
    shifted = [(idx + 1) % cfg.n_experts for _, idx in choices]
    own = jax.value_and_grad(
        lambda p: reference.sequence_loss(p, one, MODEL))(params)
    told = jax.value_and_grad(lambda p: reference.sequence_loss(
        p, one, MODEL, routing=shifted))(params)
    assert abs(float(own[0]) - float(told[0])) > 1e-4
    assert _rel(told[1]["blocks"][3]["router"],
                own[1]["blocks"][3]["router"]) > 0.1
    (h, idx), blk = choices[0], params["blocks"][3]
    outside = float(reference.choices_outside_top_k(
        h, (idx + 1) % cfg.n_experts, blk, MODEL))
    assert 0.1 < outside <= 1.0


def test_a_step_leaves_the_selection_bias_as_it_was(drawn):
    """AdamW through ``make_train_step``: every other leaf moves, the bias
    (a gradient of zero, and a decay that would pull it) does not."""
    cfg, params, tokens = drawn
    init_state, step = make_train_step(cfg, mesh=make_mesh_nd(1),
                                       learning_rate=1e-2)
    state = init_state(jax.random.PRNGKey(0))
    state = dict(state, params=params)
    before = jax.tree.map(np.asarray, params)
    state, loss = step(state, tokens)
    assert np.isfinite(float(loss))
    for was, now in zip(before["blocks"], state["params"]["blocks"]):
        for name in was:
            moved = not np.array_equal(jax.tree.leaves(was[name])[0],
                                       jax.tree.leaves(now[name])[0])
            assert moved == (name != "router_bias"), name


def test_one_train_step_moves_every_leaf_by_the_references_gradient(
        drawn, reference):
    """Plain SGD on a one-device mesh: ``(before - after) / rate`` is the
    gradient the step used, the mean over the batch of the reference's."""
    cfg, params, tokens = drawn
    rate = 0.5
    init_state, step = make_train_step(cfg, mesh=make_mesh_nd(1),
                                       learning_rate=rate, optimizer="sgd")
    state = dict(init_state(jax.random.PRNGKey(0)), params=params)
    before = jax.tree.map(np.asarray, params)
    state, loss = step(state, tokens)
    per_seq = [jax.value_and_grad(
        lambda p, t=t: reference.sequence_loss(p, t, MODEL))(params)
        for t in tokens]
    assert abs(float(loss) - np.mean([float(v) for v, _ in per_seq])) < 1e-5
    want = jax.tree.map(lambda *g: sum(g) / len(g), *[g for _, g in per_seq])
    used = jax.tree.map(lambda a, b: (a - np.asarray(b)) / rate, before,
                        state["params"])
    errors = {k: e for k, e in _leaf_errors(used, want).items()
              if "router_bias" not in k}
    assert max(errors.values()) < 2e-3, errors


def test_remat_changes_no_value(drawn):
    cfg, params, tokens = drawn
    plain = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    remat = jax.value_and_grad(loss_fn)(params, tokens, _cfg(remat=True))
    assert abs(float(plain[0]) - float(remat[0])) < 1e-6
    errors = {k: e for k, e in _leaf_errors(remat[1], plain[1]).items()
              if "router_bias" not in k}
    assert max(errors.values()) < 1e-5


def test_a_dp_mesh_changes_no_value(drawn):
    cfg, params, _ = drawn
    tokens = jax.random.randint(jax.random.PRNGKey(42), (4, SEQ + 1), 0,
                                cfg.vocab)
    mesh = make_mesh_nd(2, axes=("dp", "tp"), devices=jax.devices()[:2])
    got = jax.jit(jax.value_and_grad(
        lambda p, t: loss_fn(p, t, cfg, mesh)))(params, tokens)
    want = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    assert abs(float(got[0]) - float(want[0])) < 1e-5
    errors = {k: e for k, e in _leaf_errors(got[1], want[1]).items()
              if "router_bias" not in k}
    assert max(errors.values()) < 2e-5


@pytest.mark.parametrize("axis", ["tp", "ep", "sp"])
@pytest.mark.parametrize("pattern", ["CCCCCCCC", "FFFFFFFF"])
def test_a_mesh_that_would_split_a_layer_is_refused_by_name(
        drawn, axis, pattern):
    cfg, params, tokens = drawn
    cfg = _cfg(layer_pattern=pattern)
    params = init_params(jax.random.PRNGKey(0), cfg)
    split = make_mesh_nd(2, axes=(axis,), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=(
            f"{axis}=2: .*short conv \\(C\\).*dense FFN \\(F\\).*not "
            f"split over")):
        loss_fn(params, tokens, cfg, split)


@pytest.mark.parametrize("over, said", [
    (dict(layer_pattern="CF*ECECX"),
     "letters of M \\(Mamba-2\\), C \\(gated short conv\\), \\* "
     "\\(attention\\), E \\(routed experts\\), F \\(dense FFN\\)"),
    (dict(dense_d_ff=0), "an F layer needs its width dense_d_ff \\(got 0"),
    (dict(moe_shared_d_ff=8),
     "beside swiglu experts moe_shared_d_ff must be 0 \\(got 8"),
    (dict(ffn="gelu"), "experts of an E layer are relu2 .* or swiglu"),
    (dict(moe_router_bias=False, moe_router_bias_std=0.01),
     "moe_router_bias_std=0.01 draws a selection bias that "
     "moe_router_bias=False leaves out"),
])
def test_a_pattern_the_stack_cannot_run_is_refused(over, said):
    with pytest.raises(ValueError, match=said):
        _cfg(**over)


def test_generate_and_the_pipeline_refuse_the_new_fields_by_name(drawn):
    from mpi_tpu.models import generate
    from mpi_tpu.models.pipeline_lm import _check_cfg

    cfg, params, tokens = drawn
    named = ("qk_norm=True", "moe_router_bias=True", "dense_d_ff=40",
             "layer_pattern='CF*ECECE'", "ffn='swiglu'")
    assert set(named) <= set(cfg.beyond_classic_block())
    for name in named:
        with pytest.raises(NotImplementedError, match=re.escape(name)):
            generate(params, tokens[:, :4], cfg, max_new_tokens=2)
    classic_qk = TransformerConfig(qk_norm=True)
    assert classic_qk.beyond_classic_block() == ("qk_norm=True",)
    with pytest.raises(ValueError, match="qk_norm=True"):
        _check_cfg(classic_qk, 1)
    with pytest.raises(ValueError, match="layer_pattern='CFCF'"):
        _check_cfg(_cfg(n_experts=0, n_layers=4, layer_pattern="CFCF",
                        qk_norm=False, moe_router_bias=False), 1)


def test_counters_hold_their_counts(drawn, traced):
    cfg, params, tokens = drawn

    def counted():
        jax.make_jaxpr(lambda p, t: loss_fn(p, t, cfg))(params, tokens)
        return {k: v for k, v in traced.counters().items()
                if k.startswith(("shortconv.", "ssm.", "moe."))}

    traced.disable()
    assert counted() == {}
    traced.enable()
    assert counted() == {"shortconv.layers": 3, "moe.layers": 3,
                         "moe.combine.gathers": 3}


def test_scopes_are_in_the_lowered_text(drawn):
    cfg, params, tokens = drawn
    text = jax.jit(lambda p, t: loss_fn(p, t, cfg)).lower(
        params, tokens).as_text(debug_info=True)
    for scope in ("attn/shortconv/shortconv.in_proj",
                  "attn/shortconv/shortconv.conv",
                  "attn/shortconv/shortconv.out_proj", "ffn/moe.route",
                  "ffn/moe.routed"):
        assert scope in text, scope
    assert "moe.shared" not in text          # no shared expert, no scope


def test_floor_tiles_run_twice_the_uniform_pairs_at_the_cell():
    """At the cell's 16,384 tokens, 4 of 64 experts a token and 8 held,
    uniform routing sends 8,192 pairs to the share: the loop's floor is
    32 tiles of 512 rows, twice that, so the executed products follow the
    pairs within 2x at uniform routing."""
    pairs = 16384 * 4 * 8 // 64
    assert pairs == 8192
    assert floor_tiles(16384, 4, 8, 64) * moe._TILE == 2 * pairs


# --------------------------------------------------------------------------
# The short conv
# --------------------------------------------------------------------------

def test_the_short_conv_is_its_token_by_token_definition(drawn, reference):
    """The conv alone against a loop over positions written here, and the
    reference's recurrence against the same loop."""
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 12), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 12), jnp.float32)
    x = np.asarray(u, np.float64)
    want = np.zeros_like(x)
    for t in range(SEQ):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += np.asarray(w[j], np.float64) * x[:, t - 2 + j]
    assert _rel(_causal_conv(u, w), want) < 1e-6
    assert _rel(jnp.stack([reference.short_conv(row, w) for row in u]),
                want) < 1e-6


def test_the_mixer_equals_the_references(drawn, reference):
    cfg, params, _ = drawn
    blk = params["blocks"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, cfg.d_model),
                          jnp.float32)
    weigh = jax.random.normal(jax.random.PRNGKey(4), h.shape, jnp.float32)

    def system(blk, h):
        return jnp.sum(short_conv_mixer(h, blk) * weigh)

    def plain(blk, h):
        return jnp.sum(jnp.stack(
            [reference._conv(row, blk, MODEL) for row in h]) * weigh)

    got = jax.value_and_grad(system, argnums=(0, 1))(blk, h)
    want = jax.value_and_grad(plain, argnums=(0, 1))(blk, h)
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    assert max(_leaf_errors(got[1], want[1]).values()) < 1e-4


def test_the_conv_with_a_bias_is_what_the_mamba2_mixer_had():
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 8, 4), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(6), (4, 4), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(7), (4,), jnp.float32)
    with_bias = _causal_conv(u, w, b)
    assert with_bias.dtype == jnp.float32
    np.testing.assert_array_equal(with_bias, _causal_conv(u, w) + b)


# --------------------------------------------------------------------------
# q/k norm
# --------------------------------------------------------------------------

def _attention_case(reference, qk_norm):
    cfg = _cfg(qk_norm=qk_norm, layer_pattern="*" * 8)
    blk = init_params(jax.random.PRNGKey(43), cfg)["blocks"][0]
    blk = _stirred(blk, seed=44)
    x = jax.random.normal(jax.random.PRNGKey(45), (1, SEQ, cfg.d_model))
    return cfg, blk, x


def test_qk_norm_on_is_the_references_attention(reference):
    cfg, blk, x = _attention_case(reference, True)
    with jax.default_matmul_precision("highest"):
        want = reference._attention(x[0], blk, MODEL)
    assert _rel(_attention(x, blk, cfg)[0], want) < 1e-5


def test_qk_norm_off_is_todays_attention(reference):
    """Off: no leaf and no norm in the program, and the values of the
    attention the block had before the field (projections, rope, softmax,
    ``W_o``); on, with weights of one, other values."""
    cfg, blk, x = _attention_case(reference, False)
    assert set(blk) == {"ln1", "wq", "wk", "wv", "wo"}
    jaxpr = str(jax.make_jaxpr(lambda x: _attention(x, blk, cfg))(x))
    assert "rsqrt" not in jaxpr
    with jax.default_matmul_precision("highest"):
        want = _todays_attention(x[0], blk, cfg, reference)
    assert _rel(_attention(x, blk, cfg)[0], want) < 1e-5
    unit = dict(blk, q_norm={"scale": jnp.zeros(8)},
                k_norm={"scale": jnp.zeros(8)})
    on = dataclasses.replace(cfg, qk_norm=True)
    assert _rel(_attention(x, unit, on)[0], want) > 1e-3


def _todays_attention(x, blk, cfg, reference):
    """``_attention`` as it was before ``qk_norm``, written out."""
    q = jnp.einsum("sd,dhk->shk", x, blk["wq"])
    k = jnp.einsum("sd,dhk->shk", x, blk["wk"])
    v = jnp.einsum("sd,dhk->shk", x, blk["wv"])
    q, k = (reference._rope(a, cfg.rope_theta) for a in (q, k))
    per = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, per, 1), jnp.repeat(v, per, 1)
    s, hd = x.shape[0], q.shape[-1]
    scores = jnp.einsum("qhd,thd->hqt", q, k) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    ctx = jnp.einsum("hqt,thd->qhd", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("shk,hkd->sd", ctx, blk["wo"])


# --------------------------------------------------------------------------
# The flash kernels at head_dim 64
# --------------------------------------------------------------------------

@pytest.mark.parametrize("heads", [(4, 4), (4, 1)], ids=["mha", "gqa"])
def test_flash_kernels_at_head_dim_64_equal_dense_attention(heads):
    """Forward and backward, in interpret mode, with the head size of the
    cell (its 32 query heads on 8 k/v heads are grouped as here)."""
    from mpi_tpu.ops import dense_attention, flash_attention
    from mpi_tpu.models.transformer import repeat_kv_heads

    h, hk = heads
    ks = jax.random.split(jax.random.PRNGKey(46), 4)
    q = jax.random.normal(ks[0], (2, 128, h, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, hk, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, hk, 64), jnp.float32)
    g = jax.random.normal(ks[3], q.shape, jnp.float32)
    cfg = TransformerConfig(n_heads=h, n_kv_heads=hk, d_model=64 * h)

    def dense(q, k, v):
        return dense_attention(q, *repeat_kv_heads(k, v, cfg), causal=True)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, 32, 64, True)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=2e-5,
                               atol=2e-5)
    want = jax.grad(lambda *a: jnp.vdot(dense(*a), g), (0, 1, 2))(q, k, v)
    got = jax.grad(lambda *a: jnp.vdot(flash(*a), g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# The routed share
# --------------------------------------------------------------------------

D, FF, EXPERTS, TOP_K = 24, 16, 16, 4


@pytest.fixture(scope="module")
def whole_layer():
    """Every expert of a layer of sixteen, SwiGLU, a selection bias, no
    shared expert; and some tokens."""
    ks = jax.random.split(jax.random.PRNGKey(47), 6)
    dense = lambda k, shape: (                               # noqa: E731
        jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-2]))
    return {
        "router": dense(ks[0], (D, EXPERTS)),
        "router_bias": 0.2 * jax.random.normal(ks[1], (EXPERTS,)),
        "w_gate": dense(ks[2], (EXPERTS, D, FF)),
        "w_up": dense(ks[3], (EXPERTS, D, FF)),
        "w_down": dense(ks[4], (EXPERTS, FF, D)),
    }, jax.random.normal(ks[5], (2, 40, D), jnp.float32)


def _share(layer, offset, held):
    return dict(layer, **{n: layer[n][offset:offset + held]
                          for n in ("w_gate", "w_up", "w_down")})


def _plain(reference, layer, x, offset):
    model = dict(moe_top_k=TOP_K, moe_expert_offset=offset,
                 moe_routed_scale=1.0)
    return jnp.stack([reference._experts(row, layer, model) for row in x])


def test_the_shares_add_up_to_the_uncut_layer(whole_layer, reference):
    """The parts of all four shares of four SwiGLU experts, with no shared
    expert to count once, are what the reference gives for the whole
    layer; and the uncut layer through the same code too."""
    layer, x = whole_layer
    want = _plain(reference, layer, x, 0)
    parts = [routed_share_ffn(x, _share(layer, off, 4), EXPERTS, TOP_K,
                              offset=off)
             for off in range(0, EXPERTS, 4)]
    assert _rel(sum(parts), want) < 1e-5
    assert _rel(parts[0], want) > 0.1
    assert _rel(routed_share_ffn(x, layer, EXPERTS, TOP_K), want) < 1e-5


@pytest.mark.parametrize("tile", [512, 16])
def test_the_swiglu_share_and_its_gradient_equal_the_references(
        whole_layer, reference, tile, monkeypatch):
    """The hand-written backward loop of three-matrix experts: with tiles
    of 512 rows and of 16 (the loop then runs past its floor)."""
    monkeypatch.setattr(moe, "_TILE", tile)
    layer, x = whole_layer
    share = _share(layer, 4, 4)
    weigh = jax.random.normal(jax.random.PRNGKey(48), x.shape, jnp.float32)

    def system(p, x):
        return jnp.sum(routed_share_ffn(x, p, EXPERTS, TOP_K, offset=4)
                       * weigh)

    def plain(p, x):
        return jnp.sum(_plain(reference, p, x, 4) * weigh)

    got = jax.value_and_grad(system, argnums=(0, 1))(share, x)
    want = jax.value_and_grad(plain, argnums=(0, 1))(share, x)
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * abs(float(want[0]))
    errors = _leaf_errors(got[1], want[1])
    assert not np.any(np.asarray(got[1][0]["router_bias"]))
    errors.pop("[0]['router_bias']")
    assert max(errors.values()) < 1e-4, errors


# --------------------------------------------------------------------------
# The benchmark's comparison for this kind of cell
# --------------------------------------------------------------------------

BENCH = ROOT / "benchmark"


def _kind():
    return (_load(BENCH / "kinds" / "train_step_routed_noscan.py"),
            _load(BENCH / "kinds" / "train_step_routed.py"))


@pytest.fixture(scope="module")
def cell():
    conf = json.loads((BENCH / "configs" /
                       "_rehearsal-train_step_routed_noscan.json").read_text())
    seq = 64
    cfg = TransformerConfig(**dict(conf["model"], dtype=jnp.float32,
                                   max_seq=seq + 1))
    params = _stirred(init_params(jax.random.PRNGKey(3), cfg))
    one = jax.random.randint(jax.random.PRNGKey(4), (1, seq + 1), 0,
                             cfg.vocab)
    kind, routed = _kind()
    reference = _load(BENCH / conf["reference"])
    lines = []

    def compare(**limits):
        del lines[:]
        check = kind.Check(routed._limit, conf["learning_rate"])
        ok, numbers = check.compare(params, one, cfg, make_mesh_nd(1),
                                    dict(conf, **limits), reference,
                                    lines.append)
        return ok, numbers, "\n".join(lines)

    return compare


def test_the_program_as_it_is_lies_within_every_limit(cell):
    ok, numbers, said = cell()
    assert ok and said.endswith("over: none: ok")
    assert numbers["routing_outside_top_k"] == [0.0] * 8
    assert set(numbers["grad_rel_err"]) == {
        "conv_w", "in_proj", "ln1/scale", "out_proj"}
    assert max(numbers["grad_rel_err"].values()) < 1e-4
    assert abs(numbers["loss_system"] - numbers["loss_reference"]) < 1e-5


def _bf16_scores(x2, router):
    return jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x2.astype(jnp.bfloat16), router.astype(jnp.bfloat16))
    ).astype(jnp.float32)


def test_a_bfloat16_router_fails_the_routing_limit(cell, monkeypatch):
    monkeypatch.setattr(moe, "_router_scores", _bf16_scores)
    ok, numbers, said = cell()
    assert not ok and said.endswith("FAILED")
    assert max(numbers["routing_outside_top_k"]) > 1e-3


def test_the_first_steps_reference_is_optaxs_adamw():
    import optax

    kind, _ = _kind()
    p = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 8)),
         "s": jnp.zeros((8,))}
    g = jax.tree.map(lambda x: 1e-3 * jax.random.normal(
        jax.random.PRNGKey(1), x.shape), p)
    opt = optax.adamw(1e-3)         # mpi_tpu.models.make_optimizer's
    want = opt.update(g, opt.init(p), p)[0]
    got = jax.tree.map(lambda g, p: kind.adamw_first_step(g, p, 1e-3), g, p)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-12)


def _first_step(planted=None):
    """The rehearsal cell's comparison and its first step, a dp mesh of
    one, on a batch of two; ``planted(step)`` stands in for the step."""
    conf = json.loads((BENCH / "configs" /
                       "_rehearsal-train_step_routed_noscan.json").read_text())
    seq = 32
    cfg = TransformerConfig(**dict(conf["model"], dtype=jnp.float32,
                                   max_seq=seq + 1))
    mesh = make_mesh_nd(1)
    init_state, step = make_train_step(cfg, mesh=mesh,
                                       learning_rate=conf["learning_rate"])
    state = init_state(jax.random.key_data(jax.random.key(5)))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, seq + 1), 0,
                                cfg.vocab)
    kind, routed = _kind()
    lines = []
    check = kind.Check(routed._limit, conf["learning_rate"])
    ok, _ = check.compare(state["params"], tokens[:1], cfg, mesh, conf,
                          _load(BENCH / conf["reference"]), lines.append)
    checked = kind.FirstStepChecked(
        step if planted is None else planted(step), check)
    state, loss = checked(state, tokens)
    assert ok and bool(jnp.isfinite(loss)) and checked._check is None
    return check, lines[-1]


def test_the_first_step_is_float32_adamw_on_the_references_gradient():
    check, said = _first_step()
    assert check.update_ok and said.endswith("over: none: ok")
    errors = check.update_numbers["update_rel_err"]
    assert set(errors) == {"conv_w", "in_proj", "ln1/scale", "out_proj"}
    assert max(errors.values()) < 0.1
    # The first sequence's gradient alone would move the block elsewhere.
    assert min(check.update_numbers[
        "update_rel_err_one_sequence"].values()) > 0.5


@pytest.mark.parametrize("planted, low", [
    (lambda step: lambda state, tokens: (state, jnp.float32(0.0)), 0.99),
    (lambda step: lambda state, tokens: step(state, tokens[:1]), 0.5),
    (lambda step: lambda state, tokens: step(state, tokens[:, ::-1]), 0.5),
], ids=["state_left_as_it_was", "one_sequence_of_two", "tokens_reversed"])
def test_a_step_that_does_not_train_the_batch_fails_the_update_limit(
        planted, low):
    check, said = _first_step(planted)
    assert not check.update_ok and said.endswith("FAILED")
    assert min(check.update_numbers["update_rel_err"].values()) >= low


def test_the_kind_runs_the_routed_kinds_loop_with_its_own_compare():
    kind = (BENCH / "kinds" / "train_step_routed_noscan.py").read_text()
    assert 'ctx.load("kinds/train_step_routed.py")' in kind
    assert "routed.compare = " in kind and "for _ in range" not in kind
