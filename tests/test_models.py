"""Flagship-model tests: sharded training step on the virtual 8-device mesh.

The reference has no models (SURVEY.md §2) — these tests cover the *new*
SPMD showcase: forward determinism, tp/dp/sp-sharded training parity with
the unsharded single-device step, and the driver-contract entry points.
"""

import collections
import functools
import sys
from dataclasses import replace as dataclasses_replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tpu.models import (
    TransformerConfig,
    forward,
    init_params,
    make_mesh_nd,
    make_train_step,
)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


CFG = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, max_seq=32)


def _tokens(batch=4, seq=17, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, CFG.vocab, (batch, seq)),
        dtype=jnp.int32)


def test_forward_shape_and_determinism():
    params = init_params(jax.random.PRNGKey(0), CFG)
    toks = _tokens()[:, :-1]
    out1 = jax.jit(lambda p, t: forward(p, t, CFG))(params, toks)
    out2 = jax.jit(lambda p, t: forward(p, t, CFG))(params, toks)
    assert out1.shape == (4, 16, CFG.vocab)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_train_step_reduces_loss_single_device():
    init_state, step = make_train_step(CFG, mesh=None, learning_rate=1e-2)
    state = init_state(jax.random.PRNGKey(0))
    toks = _tokens()
    losses = []
    for _ in range(5):
        state, loss = step(state, toks)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_sharded_step_matches_unsharded():
    """dp=2 x sp=2 x tp=2 sharded step computes the same loss trajectory as
    the single-device step — the collectives GSPMD inserts are exact."""
    mesh = make_mesh_nd(8)
    toks = _tokens()

    init_u, step_u = make_train_step(CFG, mesh=None)
    su = init_u(jax.random.PRNGKey(0))
    init_s, step_s = make_train_step(CFG, mesh=mesh)
    ss = init_s(jax.random.PRNGKey(0))

    for _ in range(3):
        su, lu = step_u(su, toks)
        ss, ls = step_s(ss, toks)
        assert float(lu) == pytest.approx(float(ls), rel=2e-5)


def test_sharded_params_actually_sharded():
    mesh = make_mesh_nd(8)
    init_s, _ = make_train_step(CFG, mesh=mesh)
    state = init_s(jax.random.PRNGKey(0))
    w1 = state["params"]["blocks"][0]["w1"]
    # w1 is column-parallel over tp: 2 distinct shards along dim 1.
    assert len({s.index for s in w1.addressable_shards}) == 2


def test_make_mesh_nd_factoring():
    assert tuple(make_mesh_nd(8).shape.values()) == (2, 2, 2)
    assert tuple(make_mesh_nd(4).shape.values()) == (2, 2, 1)
    assert tuple(make_mesh_nd(2).shape.values()) == (2, 1, 1)
    assert tuple(make_mesh_nd(1).shape.values()) == (1, 1, 1)
    assert tuple(make_mesh_nd(6).shape.values()) == (2, 3, 1)


def test_graft_entry_single_chip():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] == 64
    assert np.isfinite(np.asarray(out)).all()


def test_graft_entry_dryrun_multichip():
    import __graft_entry__ as g

    g.dryrun_multichip(8)


@pytest.mark.parametrize("impl", ["flash", "blockwise"])
def test_attention_impls_match_dense_forward(impl):
    cfg = dataclasses_replace(CFG, attention_impl=impl)
    params = init_params(jax.random.PRNGKey(0), CFG)
    toks = _tokens()[:, :-1]
    want = jax.jit(lambda p, t: forward(p, t, CFG))(params, toks)
    got = jax.jit(lambda p, t: forward(p, t, cfg))(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_impl_in_sharded_model_runs_per_shard():
    """On a mesh the flash kernel runs per (dp, tp) shard inside
    shard_map (GSPMD cannot partition a Mosaic kernel — the v5e
    compiler's refusal, tests/test_tpu_compile.py); a mesh that also
    splits the sequence is refused rather than attended by halves."""
    cfg = dataclasses_replace(CFG, attention_impl="flash")
    params = init_params(jax.random.PRNGKey(0), CFG)
    toks = _tokens()[:, :-1]
    want = jax.jit(lambda p, t: forward(p, t, CFG))(params, toks)
    mesh = make_mesh_nd(4, axes=("dp", "tp"))
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh))(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="sp > 1"):
        forward(params, toks, cfg, make_mesh_nd(4))  # dp 2 x sp 2


def test_ring_attention_impl_in_sharded_model():
    cfg = dataclasses_replace(CFG, attention_impl="ring")
    mesh = make_mesh_nd(8)  # dp=2, sp=2, tp=2
    params = init_params(jax.random.PRNGKey(0), CFG)
    toks = _tokens()[:, :-1]
    want = jax.jit(lambda p, t: forward(p, t, CFG))(params, toks)
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh))(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ring_impl_training_step_runs_sharded():
    cfg = dataclasses_replace(CFG, attention_impl="ring")
    mesh = make_mesh_nd(8)
    init_state, step = make_train_step(cfg, mesh=mesh, learning_rate=1e-2)
    state = init_state(jax.random.PRNGKey(0))
    toks = _tokens()
    state, l0 = step(state, toks)
    state, l1 = step(state, toks)
    assert np.isfinite(float(l0)) and np.isfinite(float(l1))
    assert float(l1) < float(l0)


def test_ulysses_attention_impl_in_sharded_model():
    cfg = dataclasses_replace(CFG, attention_impl="ulysses")
    mesh = make_mesh_nd(8)  # dp=2, sp=2, tp=2
    params = init_params(jax.random.PRNGKey(0), CFG)
    toks = _tokens()[:, :-1]
    want = jax.jit(lambda p, t: forward(p, t, CFG))(params, toks)
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh))(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# Rematerialisation and gradient accumulation
# --------------------------------------------------------------------------

def _tiny(**kw):
    return TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                             d_ff=64, max_seq=32, **kw)


def _tokens(batch=4, seq=17, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, 64, (batch, seq)),
        jnp.int32)


_REMAT_IMPLS = ("eva", "flash", "dense")


def _remat_cfg(impl, **kw):
    """``_tiny`` with rope and a gated FFN, so every value a block under
    remat can be told to hold exists; for eva two windows of two chunks."""
    return _tiny(attention_impl=impl, rope=True, ffn="swiglu", eva_window=8,
                 eva_chunk=4, **kw)


def _pattern_cfg(pattern="M*EM", **kw):
    """A small ``layer_pattern`` stack: Mamba-2 mixers (``M``), attention
    through the flash kernels on grouped k/v heads of a size of their own
    (``*``) and a device's share of a routed expert layer beside a shared
    expert (``E``), so every value an ``M`` or ``E`` block names exists."""
    return TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, attn_head_dim=16,
        d_ff=24, n_layers=len(pattern), layer_pattern=pattern, max_seq=32,
        ffn="relu2", tie_embeddings=False, position_table=False,
        attention_impl="flash", ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
        ssm_state=16, ssm_conv=4, ssm_chunk=8, n_experts=8, moe_top_k=2,
        moe_experts_held=4, moe_expert_offset=2, moe_shared_d_ff=40,
        moe_aux_coef=0.0, **kw)


@pytest.mark.parametrize("make", [_tiny] + [
    functools.partial(_remat_cfg, impl) for impl in _REMAT_IMPLS],
    ids=("classic",) + _REMAT_IMPLS)
def test_remat_matches_plain_step(make):
    """remat=True recomputes activations in the backward, all but the
    named values it holds, which are the forward's own: it must leave
    the math untouched, identical loss and identical updated params."""
    results = []
    for remat in (False, True):
        init_state, step = make_train_step(make(remat=remat))
        state = init_state(jax.random.PRNGKey(0))
        state, loss = step(state, _tokens())
        results.append((float(loss), state["params"]))
    (l0, p0), (l1, p1) = results
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of every jaxpr inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _pallas_calls(jaxpr):
    """``{kernel name: calls}`` over a jaxpr and every jaxpr inside it."""
    return collections.Counter(
        eqn.params["name"] for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "pallas_call")


def _products(jaxpr):
    """``{output shape: dot_generals}``, likewise."""
    return collections.Counter(
        eqn.outvars[0].aval.shape for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "dot_general")


@pytest.mark.parametrize("impl, forward_kernels", [
    ("eva", ("flash_fwd", "eva_remote_fwd")), ("flash", ("flash_fwd",))])
def test_remat_runs_forward_kernels_once_a_layer(impl, forward_kernels):
    """The attention op's output and log-sum-exp are held, so the
    gradient of a block under remat has no second forward kernel; its
    backward is one fused kernel a pass (``flash_bwd``, and
    ``eva_remote_bwd`` for EVA's summaries)."""
    from mpi_tpu.models.transformer import loss_fn

    cfg = _remat_cfg(impl, remat=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(
        lambda p, t: loss_fn(p, t, cfg)))(params, _tokens()).jaxpr)
    backward_kernels = tuple(name.replace("_fwd", "_bwd")
                             for name in forward_kernels)
    assert set(calls) == set(forward_kernels + backward_kernels), dict(calls)
    for name in forward_kernels + backward_kernels:
        assert calls[name] == cfg.n_layers, (name, dict(calls))


def _named_bytes(cfg, batch, seq):
    """Bytes of each value a block names, by hand, float32 throughout:
    one ``{name: bytes}`` a block."""
    d, h, f = cfg.d_model, cfg.n_heads, cfg.d_ff
    token_wide = 4 * batch * seq
    if cfg.layer_pattern is not None:
        q, kv = h * cfg.attn_head_dim, cfg.n_kv_heads * cfg.attn_head_dim
        inner = cfg.ssm_heads * cfg.ssm_head_dim
        conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
        by_kind = {
            "M": {"ssm_in": token_wide * (inner + conv + cfg.ssm_heads),
                  "ssm_conv": token_wide * conv},
            "E": {"moe_shared_up": token_wide * cfg.moe_shared_d_ff},
            "*": {"attn_q": token_wide * q, "attn_k": token_wide * kv,
                  "attn_v": token_wide * kv, "attn_out": token_wide * q,
                  "attn_lse": token_wide * h}}
        return [by_kind[kind] for kind in cfg.layer_pattern]
    by_hand = {"attn_q": token_wide * d, "attn_k": token_wide * d,
               "attn_v": token_wide * d, "ffn_gate": token_wide * f,
               "ffn_up": token_wide * f}
    if cfg.attention_impl != "dense":
        by_hand.update(attn_out=token_wide * d, attn_lse=token_wide * h)
    if cfg.attention_impl == "eva":
        summaries = 4 * batch * (seq // cfg.eva_chunk) * d
        by_hand.update(eva_ks=summaries, eva_vs=summaries)
    return [by_hand] * cfg.n_layers


@pytest.mark.parametrize("keeps", ["as committed", "every name"])
@pytest.mark.parametrize("impl", _REMAT_IMPLS + ("pattern",))
def test_remat_counters_read_their_hand_count(impl, keeps, monkeypatch,
                                              traced):
    """``remat.blocks`` and ``remat.kept_bytes`` with tracing on; silent
    with it off. With every name kept, each named site is checked:
    those of the classic block by attention op and, in a ``layer_pattern``
    stack, those of an ``M``, an ``E`` and a ``*`` block."""
    from mpi_tpu.models import transformer

    cfg = (_pattern_cfg(remat=True) if impl == "pattern"
           else _remat_cfg(impl, remat=True))
    tok = _tokens()
    by_hand = _named_bytes(cfg, tok.shape[0], tok.shape[1] - 1)
    if keeps == "every name":
        monkeypatch.setattr(transformer, "_REMAT_KEEPS",
                            tuple(set().union(*by_hand)))
    want = sum(blk.get(name, 0) for blk in by_hand
               for name in transformer._REMAT_KEEPS)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def counted_while_tracing_the_gradient():
        jax.make_jaxpr(jax.grad(     # a new function: nothing cached
            lambda p, t: transformer.loss_fn(p, t, cfg)))(params, tok)
        return {k: v for k, v in traced.counters().items()
                if k.startswith("remat.")}

    traced.disable()
    assert counted_while_tracing_the_gradient() == {}
    traced.enable()
    assert counted_while_tracing_the_gradient() == {
        "remat.blocks": cfg.n_layers, "remat.kept_bytes": want}


@pytest.mark.parametrize("keeps, a_mixer", [("as committed", 1),
                                            ("nothing", 2)])
def test_remat_runs_the_in_projection_once_a_mixer(keeps, a_mixer,
                                                   monkeypatch):
    """An ``M`` block under remat holds its in-projection's product, so
    the gradient's jaxpr has it once a mixer: the backward does not run
    it again. With nothing held it does, which the count shows."""
    from mpi_tpu.models import transformer

    if keeps == "nothing":
        monkeypatch.setattr(transformer, "_REMAT_KEEPS", ())
    cfg = _pattern_cfg("MEME", remat=True)
    tok = _tokens()
    params = init_params(jax.random.PRNGKey(0), cfg)
    products = _products(jax.make_jaxpr(jax.grad(
        lambda p, t: transformer.loss_fn(p, t, cfg)))(params, tok).jaxpr)
    wide = params["blocks"][0]["in_proj"].shape[1]
    assert products[tok.shape[0], tok.shape[1] - 1, wide] == (
        a_mixer * cfg.layer_pattern.count("M")), dict(products)


def test_plain_block_is_returned_as_it_is():
    """Without remat the helper hands back the block itself: nothing
    wrapped, nothing counted, the program of a model without remat as
    it was."""
    from mpi_tpu.models.transformer import block_body, checkpointed_block

    block = checkpointed_block(_tiny(), None)
    assert block.func is block_body and block.keywords == {
        "cfg": _tiny(), "mesh": None}


def test_grad_accum_matches_full_batch():
    """grad_accum=k over the batch must produce the same mean loss and
    the same optimizer update as one full-batch step (equal microbatch
    sizes make mean-of-means exact)."""
    cfg = _tiny()
    tok = _tokens(batch=4)
    ref_init, ref_step = make_train_step(cfg)
    state = ref_init(jax.random.PRNGKey(0))
    ref_state, ref_loss = ref_step(state, tok)

    acc_init, acc_step = make_train_step(cfg, grad_accum=2)
    state2 = acc_init(jax.random.PRNGKey(0))
    acc_state, acc_loss = acc_step(state2, tok)

    np.testing.assert_allclose(float(ref_loss), float(acc_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ref_state["params"]),
                    jax.tree.leaves(acc_state["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_grad_accum_rejects_indivisible_batch():
    init_state, step = make_train_step(_tiny(), grad_accum=3)
    state = init_state(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="divisible"):
        step(state, _tokens(batch=4))


def test_remat_grad_accum_sharded_step():
    """Both features compose with a dp x tp mesh (long-context training
    shape: remat for memory, accumulation for global batch)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    cfg = _tiny(remat=True)
    init_state, step = make_train_step(cfg, mesh=mesh, grad_accum=2)
    state = init_state(jax.random.PRNGKey(0))
    tok = jax.device_put(_tokens(batch=4),
                         NamedSharding(mesh, P("dp", None)))
    state, loss1 = step(state, tok)
    state, loss2 = step(state, tok)
    assert np.isfinite(float(loss1)) and float(loss2) < float(loss1)


@pytest.mark.parametrize("optimizer", ["adamw", "adafactor", "sgd"])
def test_optimizer_choices_train(optimizer):
    init_state, step = make_train_step(_tiny(), optimizer=optimizer,
                                       learning_rate=1e-2)
    state = init_state(jax.random.PRNGKey(0))
    tok = _tokens()
    losses = []
    for _ in range(3):
        state, loss = step(state, tok)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_adafactor_state_smaller_than_adamw():
    """The point of adafactor: factored second moment, so optimizer
    state is a fraction of adamw's two full-size moments."""
    def opt_bytes(optimizer):
        init_state, _ = make_train_step(_tiny(), optimizer=optimizer)
        state = init_state(jax.random.PRNGKey(0))
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(state["opt"])
                   if hasattr(x, "size"))
    # ~0.5x even at this tiny size (factoring wins grow with dims).
    assert opt_bytes("adafactor") < 0.6 * opt_bytes("adamw")


def test_warmup_cosine_schedule_runs():
    from mpi_tpu.models import make_optimizer
    import optax

    opt = make_optimizer("adamw", 1e-3, warmup_steps=2, total_steps=10)
    assert isinstance(opt, optax.GradientTransformation)
    init_state, step = make_train_step(_tiny(), warmup_steps=2,
                                       total_steps=10)
    state = init_state(jax.random.PRNGKey(0))
    state, loss = step(state, _tokens())
    assert np.isfinite(float(loss))


def test_unknown_optimizer_rejected():
    from mpi_tpu.models import make_optimizer

    with pytest.raises(ValueError, match="optimizer"):
        make_optimizer("lamb")


# --------------------------------------------------------------------------
# RoPE and grouped-query attention
# --------------------------------------------------------------------------

def test_gqa_full_heads_equals_mha():
    """n_kv_heads == n_heads must be numerically identical to the MHA
    default (the repeat is a no-op and shapes coincide)."""
    toks = _tokens()
    p = init_params(jax.random.PRNGKey(0), _tiny())
    a = forward(p, toks, _tiny())
    b = forward(p, toks, _tiny(n_kv_heads=4))
    np.testing.assert_allclose(a, b, rtol=1e-6)


@pytest.mark.parametrize("kv", [1, 2])
def test_gqa_trains_and_shrinks_kv(kv):
    cfg = _tiny(n_kv_heads=kv)
    p = init_params(jax.random.PRNGKey(0), cfg)
    assert p["blocks"][0]["wk"].shape == (32, kv, 8)
    init_state, step = make_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    state, l1 = step(state, _tokens())
    state, l2 = step(state, _tokens())
    assert np.isfinite(float(l1)) and float(l2) < float(l1)


def test_rope_shift_invariance():
    """RoPE scores depend only on relative position: rotating q/k with
    positions p and p+C gives identical attention logits."""
    from mpi_tpu.models import apply_rope

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 8, 2, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 2, 8))
    p0 = jnp.arange(8, dtype=jnp.int32)
    s0 = jnp.einsum("bshk,bthk->bhst", apply_rope(q, p0),
                    apply_rope(k, p0))
    s1 = jnp.einsum("bshk,bthk->bhst", apply_rope(q, p0 + 100),
                    apply_rope(k, p0 + 100))
    np.testing.assert_allclose(s0, s1, rtol=1e-4, atol=1e-5)


def test_rope_model_trains_without_pos_table():
    cfg = _tiny(rope=True)
    p = init_params(jax.random.PRNGKey(0), cfg)
    assert "pos" not in p
    init_state, step = make_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    losses = []
    for _ in range(3):
        state, loss = step(state, _tokens())
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_rope_gqa_generate_matches_forward():
    """Prefill+decode with rope+GQA must agree with the full forward
    pass: greedy generation equals argmax of teacher-forced logits."""
    from mpi_tpu.models import generate

    cfg = _tiny(rope=True, n_kv_heads=2)
    p = init_params(jax.random.PRNGKey(1), cfg)
    prompt = _tokens(batch=2, seq=5, seed=3)
    toks = generate(p, prompt, cfg, max_new_tokens=4)
    # teacher-forced check of the first generated token
    logits = forward(p, prompt, cfg)
    np.testing.assert_array_equal(
        np.asarray(toks[:, 0]), np.asarray(jnp.argmax(logits[:, -1], -1)))
    assert toks.shape == (2, 4)


def test_rope_gqa_sharded_train_step():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    cfg = _tiny(rope=True, n_kv_heads=2)
    init_state, step = make_train_step(cfg, mesh=mesh)
    state = init_state(jax.random.PRNGKey(0))
    tok = jax.device_put(_tokens(batch=4),
                         NamedSharding(mesh, P("dp", None)))
    state, loss1 = step(state, tok)
    state, loss2 = step(state, tok)
    assert np.isfinite(float(loss1)) and float(loss2) < float(loss1)


def test_gqa_invalid_kv_heads_rejected():
    with pytest.raises(ValueError, match="n_kv_heads"):
        init_params(jax.random.PRNGKey(0), _tiny(n_kv_heads=3))


def test_gqa_tp_indivisible_rejected():
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:4]).reshape(1, 4)
    mesh = Mesh(devs, ("dp", "tp"))
    with pytest.raises(ValueError, match="tp"):
        make_train_step(_tiny(n_kv_heads=2), mesh=mesh)


def test_gqa_flash_impl_matches_dense_forward():
    """attention_impl='flash' with GQA uses the kernels' native grouped
    path (no repeat) and must match the dense impl's output."""
    cfg_d = _tiny(n_kv_heads=2)
    cfg_f = _tiny(n_kv_heads=2, attention_impl="flash")
    p = init_params(jax.random.PRNGKey(0), cfg_d)
    toks = _tokens()[:, :-1]
    want = forward(p, toks, cfg_d)
    got = forward(p, toks, cfg_f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_zero1_matches_plain_dp_and_shards_opt_state():
    # ZeRO-1 (parallel/zero.py): same step math as plain dp training up
    # to float reduction order; AdamW moments land dp-sharded.
    mesh = make_mesh_nd(8)  # dp=2, sp=2, tp=2
    toks = _tokens(batch=4, seq=17)

    init_p, step_p = make_train_step(CFG, mesh=mesh)
    init_z, step_z = make_train_step(CFG, mesh=mesh, zero1=True)
    sp_, sz = init_p(jax.random.PRNGKey(0)), init_z(jax.random.PRNGKey(0))

    # mu for w1 is (d_model, d_ff): tp on axis 1 (from the param spec),
    # dp claimed on axis 0 -> 4 distinct shard index patterns.
    mu_w1 = sz["opt"][0].mu["blocks"][0]["w1"]
    assert len({s.index for s in mu_w1.addressable_shards}) == 4

    for _ in range(3):
        sp_, lp = step_p(sp_, toks)
        sz, lz = step_z(sz, toks)
        assert float(lp) == pytest.approx(float(lz), rel=2e-4)

    # zero1 without a dp mesh axis is a loud error
    with pytest.raises(ValueError, match="dp"):
        make_train_step(CFG, mesh=None, zero1=True)


def test_fsdp_matches_plain_dp_and_shards_params():
    # ZeRO-3/FSDP (parallel/zero.py fsdp_specs): parameters AND
    # optimizer moments live dp-sharded; the step math matches plain dp
    # up to float reduction order.
    mesh = make_mesh_nd(8)  # dp=2, sp=2, tp=2
    toks = _tokens(batch=4, seq=17)

    init_p, step_p = make_train_step(CFG, mesh=mesh)
    init_f, step_f = make_train_step(CFG, mesh=mesh, fsdp=True)
    sp_, sf = init_p(jax.random.PRNGKey(0)), init_f(jax.random.PRNGKey(0))

    # w1 is (d_model, d_ff): tp on axis 1 (param spec), dp claimed on
    # axis 0 -> 4 distinct shard index patterns for the WEIGHT itself
    # (the zero1 test asserts this for the moments only).
    w1 = sf["params"]["blocks"][0]["w1"]
    assert len({s.index for s in w1.addressable_shards}) == 4
    mu_w1 = sf["opt"][0].mu["blocks"][0]["w1"]
    assert len({s.index for s in mu_w1.addressable_shards}) == 4
    # plain dp keeps weights replicated over dp (2 patterns: tp only)
    w1_p = sp_["params"]["blocks"][0]["w1"]
    assert len({s.index for s in w1_p.addressable_shards}) == 2

    for _ in range(3):
        sp_, lp = step_p(sp_, toks)
        sf, lf = step_f(sf, toks)
        assert float(lp) == pytest.approx(float(lf), rel=2e-4)
    # params stay sharded across steps (the constraint held)
    w1 = sf["params"]["blocks"][0]["w1"]
    assert len({s.index for s in w1.addressable_shards}) == 4

    with pytest.raises(ValueError, match="dp"):
        make_train_step(CFG, mesh=None, fsdp=True)
    with pytest.raises(ValueError, match="subsumes"):
        make_train_step(CFG, mesh=mesh, fsdp=True, zero1=True)


def test_fsdp_checkpoint_roundtrip_resumes_identically():
    """Save an FSDP-sharded state, restore onto the sharded template,
    keep training: the restored run's losses match the uninterrupted
    one exactly (layouts and step math both survive the roundtrip)."""
    import tempfile

    from mpi_tpu.utils import restore_checkpoint, save_checkpoint

    mesh = make_mesh_nd(8)
    toks = _tokens(batch=4, seq=17)
    init_f, step_f = make_train_step(CFG, mesh=mesh, fsdp=True)
    state = init_f(jax.random.PRNGKey(0))
    state, _ = step_f(state, toks)

    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, state, step=1)
        # Uninterrupted continuation...
        cont, l2a = step_f(state, toks)
        _, l3a = step_f(cont, toks)
        # ...vs restore-onto-fresh-template continuation.
        template = init_f(jax.random.PRNGKey(1))
        restored = restore_checkpoint(d, template)
        # restored params keep the fully-sharded layout
        w1 = restored["params"]["blocks"][0]["w1"]
        assert len({s.index for s in w1.addressable_shards}) == 4
        r2, l2b = step_f(restored, toks)
        _, l3b = step_f(r2, toks)
    assert float(l2a) == pytest.approx(float(l2b), rel=1e-5)
    assert float(l3a) == pytest.approx(float(l3b), rel=1e-5)


def test_fsdp_composes_with_moe_and_gqa_tp():
    """fsdp_specs claims a FREE axis only: expert weights keep their ep
    sharding, attention weights their tp sharding — and the step still
    matches the plain-dp run at each composition."""
    from jax.sharding import Mesh

    # MoE over dp x ep
    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    mesh_ep = Mesh(devs, ("dp", "ep"))
    cfg_moe = dataclasses_replace(CFG, n_experts=2, moe_top_k=2)
    toks = _tokens(batch=8, seq=17)
    init_p, step_p = make_train_step(cfg_moe, mesh=mesh_ep)
    init_f, step_f = make_train_step(cfg_moe, mesh=mesh_ep, fsdp=True)
    s_p, s_f = init_p(jax.random.PRNGKey(0)), init_f(jax.random.PRNGKey(0))
    for _ in range(2):
        s_p, lp = step_p(s_p, toks)
        s_f, lf = step_f(s_f, toks)
        assert float(lp) == pytest.approx(float(lf), rel=3e-4)

    # GQA under dp x sp x tp
    mesh = make_mesh_nd(8)
    cfg_gqa = dataclasses_replace(CFG, n_kv_heads=2)
    init_p, step_p = make_train_step(cfg_gqa, mesh=mesh)
    init_f, step_f = make_train_step(cfg_gqa, mesh=mesh, fsdp=True)
    s_p, s_f = init_p(jax.random.PRNGKey(0)), init_f(jax.random.PRNGKey(0))
    toks4 = _tokens(batch=4, seq=17)
    for _ in range(2):
        s_p, lp = step_p(s_p, toks4)
        s_f, lf = step_f(s_f, toks4)
        assert float(lp) == pytest.approx(float(lf), rel=3e-4)
    # wq is (d, h, hd) with tp on heads: fsdp claims axis 0 ->
    # tp x dp = 4 distinct shard patterns
    wq = s_f["params"]["blocks"][0]["wq"]
    assert len({s.index for s in wq.addressable_shards}) == 4
