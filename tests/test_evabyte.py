"""EvaByte on the normal train path (ISSUE 29): the EVA op in both its
forms, the block's new parts (RMSNorm with unit offset, the gated FFN,
the untied multi-byte head, the float32 residual stream) and the whole
model against the benchmark's plain reference
(``benchmark/reference/evabyte_lm.py``), which shares no code with
``mpi_tpu``. Small sizes, seeded, CPU; the kernel form runs the Pallas
kernels in interpret mode.
"""

import importlib.util
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tpu.models import TransformerConfig, make_train_step
from mpi_tpu.models.transformer import (forward, init_params, loss_fn,
                                        pred_heads_xent)
from mpi_tpu.ops import dense_attention, eva_attention, eva_summaries

ROOT = Path(__file__).resolve().parent.parent
WINDOW, CHUNK, SEQ = 32, 4, 128


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load(ROOT / "benchmark" / "reference" / "evabyte_lm.py")


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# --------------------------------------------------------------------------
# The op
# --------------------------------------------------------------------------

OP_ARGS = ("q", "k", "v", "phi", "mu")


@pytest.fixture(scope="module")
def op_case(reference):
    """Inputs, and per form: the output and the gradient of a fixed linear
    functional of it with respect to q, k, v, phi, mu."""
    b, h, d = 2, 2, 16
    ks = jax.random.split(jax.random.key(29), 6)
    q, k, v, w = (jax.random.normal(ks[i], (b, SEQ, h, d), jnp.float32)
                  for i in range(4))
    phi, mu = (jax.random.normal(ks[4 + i], (h, d), jnp.float32)
               for i in range(2))

    def ref_op(q, k, v, phi, mu):
        return jax.vmap(lambda q_, k_, v_: reference.eva_attention(
            q_, k_, v_, phi, mu, WINDOW, CHUNK))(q, k, v)

    forms = {"reference": ref_op}
    for impl in ("jnp", "flash"):
        forms[impl] = (lambda impl: lambda *a: eva_attention(
            *a, WINDOW, CHUNK, impl=impl))(impl)
    out = {}
    for name, fn in forms.items():
        val = fn(q, k, v, phi, mu)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                         argnums=tuple(range(5)))(q, k, v, phi, mu)
        out[name] = dict(zip(("out",) + OP_ARGS, (val,) + grads))
    return out


@pytest.mark.parametrize("what", ("out",) + OP_ARGS)
@pytest.mark.parametrize("impl", ("jnp", "flash"))
def test_op_matches_reference(op_case, impl, what):
    want = op_case["reference"][what]
    assert float(jnp.abs(want).max()) > 0.1     # nothing compared is ~0
    assert _rel(op_case[impl][what], want) < 2e-5


@pytest.mark.parametrize("impl", ("jnp", "flash", "reference"))
def test_one_window_is_causal_attention(reference, impl):
    """seq <= window: no summaries, plain causal softmax attention."""
    ks = jax.random.split(jax.random.key(1), 5)
    q, k, v = (jax.random.normal(ks[i], (2, WINDOW, 2, 16), jnp.float32)
               for i in range(3))
    phi, mu = (jax.random.normal(ks[3 + i], (2, 16)) for i in range(2))
    if impl == "reference":
        got = jax.vmap(lambda q_, k_, v_: reference.eva_attention(
            q_, k_, v_, phi, mu, 2 * WINDOW, CHUNK))(q, k, v)
    else:
        got = eva_attention(q, k, v, phi, mu, 2 * WINDOW, CHUNK, impl=impl)
    assert _rel(got, dense_attention(q, k, v, causal=True)) < 2e-6


@pytest.mark.parametrize("impl", ("jnp", "flash", "reference"))
def test_chunk_one_without_offset_is_full_causal_attention(reference, impl):
    """chunk 1 and mu = 0: every summary is its token, so window plus
    summaries are the whole causal prefix at every length."""
    ks = jax.random.split(jax.random.key(2), 4)
    q, k, v = (jax.random.normal(ks[i], (2, SEQ, 2, 16), jnp.float32)
               for i in range(3))
    phi, mu = jax.random.normal(ks[3], (2, 16)), jnp.zeros((2, 16))
    if impl == "reference":
        got = jax.vmap(lambda q_, k_, v_: reference.eva_attention(
            q_, k_, v_, phi, mu, WINDOW, 1))(q, k, v)
    else:
        got = eva_attention(q, k, v, phi, mu, WINDOW, 1, impl=impl)
    assert _rel(got, dense_attention(q, k, v, causal=True)) < 2e-6


def test_summaries_are_the_equations():
    """K_c = sum_j softmax_j(s k_j.phi) k_j + mu, V_c likewise without mu,
    written out for one chunk of one head."""
    ks = jax.random.split(jax.random.key(3), 4)
    k, v = (jax.random.normal(ks[i], (1, 8, 1, 16), jnp.float32)
            for i in range(2))
    phi, mu = (jax.random.normal(ks[2 + i], (1, 16)) for i in range(2))
    got_k, got_v = eva_summaries(k, v, phi, mu, 4)
    kc, vc = np.asarray(k[0, 4:8, 0]), np.asarray(v[0, 4:8, 0])
    e = np.exp(kc @ np.asarray(phi[0]) / math.sqrt(16))
    a = e / e.sum()
    np.testing.assert_allclose(got_k[0, 1, 0], a @ kc + np.asarray(mu[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_v[0, 1, 0], a @ vc, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seq, why", [(WINDOW + CHUNK, "window"),
                                      (WINDOW - 1, "chunk")])
@pytest.mark.parametrize("impl", ("jnp", "flash"))
def test_ragged_sequence_raises(impl, seq, why):
    x = jnp.zeros((1, seq, 1, 16))
    vec = jnp.zeros((1, 16))
    with pytest.raises(ValueError, match="whole"):
        eva_attention(x, x, x, vec, vec, WINDOW, CHUNK, impl=impl)


def test_window_must_be_whole_chunks():
    x, vec = jnp.zeros((1, 64, 1, 16)), jnp.zeros((1, 16))
    with pytest.raises(ValueError, match="multiple of the chunk"):
        eva_attention(x, x, x, vec, vec, 32, 5)


def test_remote_kernels_carry_eva_names():
    """The summaries' Pallas calls are named for a trace to find them."""
    x, vec = jnp.zeros((1, 64, 1, 16)), jnp.zeros((1, 16))
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(eva_attention(
        q, x, x, vec, vec, 32, 4))))(x))
    for name in ("eva_remote_fwd", "eva_remote_bwd_dq", "eva_remote_bwd_dkv",
                 "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text, name


def test_eva_scopes_in_lowered_program():
    x, vec = jnp.zeros((1, 64, 1, 16)), jnp.zeros((1, 16))
    text = jax.jit(jax.grad(lambda q, p: jnp.sum(eva_attention(
        q, x, x, p, vec, 32, 4)), argnums=(0, 1))).lower(x, vec).as_text(
            debug_info=True)

    def bare(part):          # transpose(jvp(eva)) -> eva
        while re.fullmatch(r"[\w\-]+\((.*)\)", part):
            part = re.fullmatch(r"[\w\-]+\((.*)\)", part).group(1)
        return part

    paths = {"/".join(bare(p) for p in name.split("/"))
             for name in re.findall(r'loc\("([^"]*)"', text)}
    for scope in ("eva.summarize", "eva.local", "eva.remote", "eva.merge"):
        forward = [p for p in paths if f"/eva/{scope}/" in p]
        assert forward, scope
    # the backward rule's ops carry the scopes too
    backward = {name for name in re.findall(r'loc\("([^"]*)"', text)
                if "transpose(jvp(eva))" in name}
    for scope in ("eva.local", "eva.remote", "eva.merge"):
        assert any(f"/{scope}/" in n for n in backward), scope


# --------------------------------------------------------------------------
# The model
# --------------------------------------------------------------------------

MODEL = dict(vocab=40, d_model=64, n_heads=4, d_ff=96, n_layers=2,
             rope=True, rope_theta=100000, norm="rmsnorm_unit_offset",
             ffn="swiglu", tie_embeddings=False,
             n_pred_heads=8, residual_dtype="float32", attention_impl="eva",
             eva_window=WINDOW, eva_chunk=CHUNK)


def _cfg(**over):
    model = dict(MODEL, **over)
    return TransformerConfig(**dict(model, dtype=jnp.float32,
                                    max_seq=SEQ + 1)), model


def _nonzero_norms(params, key):
    """Norm offsets away from their zero start, so that 1 + w matters."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.3 * jax.random.normal(k, x.shape, x.dtype) if x.ndim == 1
        else x for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def model_case(reference):
    cfg, model = _cfg()
    params = _nonzero_norms(init_params(jax.random.key(5), cfg),
                            jax.random.key(6))
    tokens = jax.random.randint(jax.random.key(7), (1, SEQ + 1), 0,
                                cfg.vocab)
    sys_loss, sys_grad = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    ref_loss, ref_grad = jax.value_and_grad(
        lambda p: reference.sequence_loss(p, tokens[0], model))(params)
    flat = lambda g: {jax.tree_util.keystr(p): x for p, x in  # noqa: E731
                      jax.tree_util.tree_leaves_with_path(g)}
    return (float(sys_loss), float(ref_loss), flat(sys_grad), flat(ref_grad),
            params)


_BLOCK_LEAVES = ("['eva_mu']", "['eva_phi']", "['ln1']['scale']",
                 "['ln2']['scale']", "['w1']", "['w2']", "['w3']", "['wk']",
                 "['wo']", "['wq']", "['wv']")
MODEL_LEAVES = (["['embed']", "['head']", "['final_ln']['scale']"]
                + [f"['blocks'][{i}]{leaf}" for i in range(2)
                   for leaf in _BLOCK_LEAVES])


def test_model_tree_is_what_the_reference_reads(model_case):
    assert sorted(model_case[2]) == sorted(MODEL_LEAVES)
    params = model_case[4]
    assert params["head"].shape == (8 * 40, 64)
    assert params["blocks"][0]["eva_phi"].shape == (4, 16)
    assert float(jnp.abs(params["blocks"][0]["eva_mu"]).min()) > 0


def test_model_loss_matches_reference(model_case):
    sys_loss, ref_loss = model_case[:2]
    assert abs(sys_loss - ref_loss) < 2e-5
    assert abs(ref_loss - math.log(40)) < 1.5       # a loss, not a constant


@pytest.mark.parametrize("leaf", MODEL_LEAVES)
def test_model_gradient_matches_reference(model_case, leaf):
    got, want = model_case[2][leaf], model_case[3][leaf]
    assert float(jnp.abs(want).max()) > 0
    assert _rel(got, want) < 2e-4, leaf


def test_bf16_model_tracks_reference(reference):
    """The benchmark's dtypes (bfloat16 compute, float32 stream) stay
    within bfloat16's rounding of the float32 reference."""
    cfg, model = _cfg()
    cfg = TransformerConfig(**dict(model, dtype=jnp.bfloat16,
                                   max_seq=SEQ + 1))
    params = init_params(jax.random.key(5), cfg)
    tokens = jax.random.randint(jax.random.key(7), (1, SEQ + 1), 0, 40)
    got = float(loss_fn(params, tokens, cfg))
    want = float(reference.sequence_loss(params, tokens[0], model))
    assert abs(got - want) < 0.02


def test_summaries_act_past_the_first_window_only(model_case):
    """What the on-chip comparison must catch: the summaries weigh in every
    window but the first. Window 0 of the whole sequence is the model run
    on those bytes alone; moving ``mu`` (which acts through the summaries
    only) leaves window 0 as it was and moves every later window."""
    cfg, _ = _cfg()
    params = model_case[4]
    tokens = jax.random.randint(jax.random.key(7), (1, SEQ + 1), 0, 40)
    full = forward(params, tokens[:, :-1], cfg)
    np.testing.assert_allclose(full[:, :WINDOW],
                               forward(params, tokens[:, :WINDOW], cfg),
                               atol=1e-5)
    blocks = [dict(b, eva_mu=b["eva_mu"] + 1.0) for b in params["blocks"]]
    moved = forward(dict(params, blocks=blocks), tokens[:, :-1], cfg)
    np.testing.assert_allclose(moved[:, :WINDOW], full[:, :WINDOW], atol=1e-5)
    for w in range(1, SEQ // WINDOW):
        rows = slice(w * WINDOW, (w + 1) * WINDOW)
        assert float(jnp.abs(moved[:, rows] - full[:, rows]).max()) > 1e-3


def test_forward_shape_and_dtype():
    cfg = TransformerConfig(**dict(MODEL, n_layers=1, dtype=jnp.bfloat16,
                                   max_seq=SEQ + 1))
    params = init_params(jax.random.key(0), cfg)
    logits = forward(params, jnp.zeros((2, WINDOW), jnp.int32), cfg)
    assert logits.shape == (2, WINDOW, 8, 40)
    assert logits.dtype == jnp.float32          # fp32 logits


def test_eight_head_loss_by_hand():
    """12 bytes in, a 13th as the last target: head p at position t is
    scored against byte t + 1 + p where that exists, each head's mean over
    its own positions, the heads' mean."""
    s, heads, vocab = 12, 8, 7
    logits = jax.random.normal(jax.random.key(11), (1, s, heads, vocab))
    tokens = jax.random.randint(jax.random.key(12), (1, s + 1), 0, vocab)
    lg, tk = np.asarray(logits, np.float64)[0], np.asarray(tokens)[0]
    total = 0.0
    for p in range(heads):
        nll, n = 0.0, 0
        for t in range(s):
            if t + 1 + p <= s:
                row = lg[t, p]
                nll += math.log(np.exp(row).sum()) - row[tk[t + 1 + p]]
                n += 1
        assert n == s - p
        total += nll / n
    got = float(pred_heads_xent(logits, tokens))
    assert abs(got - total / heads) < 1e-5


def test_reference_loss_is_the_same_hand_loop(reference):
    s, heads, vocab = 12, 8, 7
    logits = jax.random.normal(jax.random.key(11), (1, s, heads, vocab))
    tokens = jax.random.randint(jax.random.key(12), (1, s + 1), 0, vocab)
    assert abs(float(reference.multi_byte_loss(logits[0], tokens[0]))
               - float(pred_heads_xent(logits, tokens))) < 1e-5


def test_published_configuration_counts_821m_parameters():
    """benchmark/configs/evabyte-L4.json: a layer is 4 x 4096^2 + 3 x 4096
    x 11008 + 2 x 32 x 128 + 2 x 4096 = 202,391,552; four of them, the 320
    x 4096 embedding, the 2560 x 4096 head and the final norm."""
    conf = json.loads((ROOT / "benchmark" / "configs"
                       / "evabyte-L4.json").read_text())
    model = conf["model"]
    cfg = TransformerConfig(**dict(model, dtype=jnp.dtype(model["dtype"]),
                                   max_seq=16385))
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 32 * 128 + 2 * 4096
    assert sum(x.size for x in jax.tree.leaves(shapes["blocks"][0])) == layer
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert total == 4 * layer + 320 * 4096 + 2560 * 4096 + 4096 == 821366784
    # the published widths, as the catalog row has them
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["intermediate_size"], conf["window_size"],
            conf["chunk_size"], conf["vocab_size"], conf["num_pred_heads"],
            conf["rope_theta"]) == (4096, 32, 11008, 2048, 16, 320, 8, 100000)
    assert (model["d_model"], model["n_heads"], model["d_ff"],
            model["eva_window"], model["eva_chunk"], model["vocab"],
            model["n_pred_heads"], model["rope_theta"]) == (
                4096, 32, 11008, 2048, 16, 320, 8, 100000)
    assert list(conf["reduced"]) == ["num_hidden_layers"]


def test_starcoder2_tree_and_loss_are_what_they_were():
    """The classic block is untouched by the new fields: the StarCoder2
    cell's model at a small width has the parameter tree, the draws and
    the loss it had before ISSUE 29 (numbers from the parent commit, under
    this suite's x64)."""
    model = json.loads((ROOT / "benchmark" / "configs"
                        / "starcoder2-3b-L6.json").read_text())["model"]
    small = dict(model, vocab=64, d_model=48, n_heads=6, n_kv_heads=2,
                 d_ff=96, n_layers=2, attention_impl="dense")
    cfg = TransformerConfig(**dict(small, dtype=jnp.float32, max_seq=17))
    params = init_params(jax.random.key(7), cfg)
    assert sorted(params) == ["blocks", "embed", "final_ln"]
    assert sorted(params["blocks"][0]) == ["ln1", "ln2", "w1", "w2", "wk",
                                           "wo", "wq", "wv"]
    assert sorted(params["final_ln"]) == ["bias", "scale"]
    tokens = jax.random.randint(jax.random.key(8), (2, 17), 0, 64)
    abs_sum = float(sum(jnp.abs(x).sum() for x in jax.tree.leaves(params)))
    assert abs(abs_sum - 3841.36279296875) < 1e-2
    assert abs(float(loss_fn(params, tokens, cfg)) - 4.169129371643066) < 1e-5
    full = TransformerConfig(**dict(model, dtype=jnp.bfloat16, max_seq=4097))
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), full))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 726743040
    assert full.beyond_classic_block() == ()


@pytest.mark.parametrize("field, value", [
    ("norm", "rmsnorm_unit_offset"), ("ffn", "swiglu"),
    ("residual_dtype", "float32")])
def test_each_new_part_alone_trains(field, value):
    """Each new field is independent of the others: one at a time on the
    classic block, a train step lowers the loss."""
    cfg = TransformerConfig(vocab=32, d_model=32, n_heads=2, d_ff=64,
                            n_layers=1, max_seq=17, **{field: value})
    init_state, step = make_train_step(cfg, learning_rate=1e-2)
    state = init_state(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 17), 0, 32)
    state, first = step(state, tokens)
    for _ in range(3):
        state, last = step(state, tokens)
    assert float(last) < float(first)


def test_config_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="norm"):
        TransformerConfig(norm="batchnorm")
    with pytest.raises(ValueError, match="ffn"):
        TransformerConfig(ffn="relu")
    with pytest.raises(ValueError, match="tie_embeddings"):
        TransformerConfig(n_pred_heads=8)
    with pytest.raises(ValueError, match="causal"):
        TransformerConfig(attention_impl="eva", causal=False)


def test_train_step_on_a_tp_mesh_matches_one_device():
    """param_specs shard phi, mu by head, w3 by column and the head by row
    like their neighbours; the loss on dp 2 x tp 2 is the unsharded one."""
    from mpi_tpu.models import make_mesh_nd
    from mpi_tpu.models.transformer import init_sharded_params

    cfg, _ = _cfg(n_layers=1)
    mesh = make_mesh_nd(4, axes=("dp", "tp"))
    tokens = jax.random.randint(jax.random.key(3), (2, SEQ + 1), 0, 40)
    params = init_params(jax.random.key(4), cfg)
    want = float(loss_fn(params, tokens, cfg))
    sharded = init_sharded_params(jax.random.key(4), cfg, mesh)
    assert sharded["blocks"][0]["eva_phi"].sharding.spec[0] == "tp"
    got = float(jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))(sharded,
                                                               tokens))
    assert abs(got - want) < 1e-4


def test_generate_refuses_eva_naming_the_cache():
    from mpi_tpu.models.generate import generate

    cfg, _ = _cfg(n_layers=1)
    params = init_params(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError,
                       match="window's keys and values beside the chunk "
                             "summaries"):
        generate(params, jnp.zeros((1, 8), jnp.int32), cfg, 4)


def test_pipeline_refuses_the_new_fields_by_name():
    from mpi_tpu.models.pipeline_lm import make_pipelined_train_step

    cfg, _ = _cfg()
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    init_state, _ = make_pipelined_train_step(cfg, mesh)
    with pytest.raises(ValueError) as err:
        init_state(jax.random.key(0))
    for name in ("norm=", "ffn=", "tie_embeddings=", "n_pred_heads=",
                 "residual_dtype=", "attention_impl='eva'"):
        assert name in str(err.value), name
