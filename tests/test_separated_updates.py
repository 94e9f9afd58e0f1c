"""Which weight gradients leave the AdamW fusion, and that leaving it
changes no value.

``models/transformer.py`` ``separates_update`` picks the dense block
leaves whose gradient's product the TPU v5e compiler should write on its
own before their AdamW update (PERF.md, "models train step"); the step
puts each behind an optimization barrier of its own. The rule reads the
step's shapes alone, so these cases apply it to the benchmark's own
configurations by their shapes, and run the step with every dense leaf
picked against the step as it is.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from mpi_tpu.models import (TransformerConfig, init_params, make_mesh_nd,
                            make_train_step, transformer)
from mpi_tpu.models.transformer import make_train_parts, separated_leaves

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
DENSE = ("w1", "w2", "w3", "wq", "wk", "wv", "wo")


def _train_cell(config):
    """(the configuration's model at its cell's shapes, tokens a step)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell, = [w for w in json.load(f)["workloads"]
                 if w["config"] == config]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        model = json.load(f)["model"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    cfg = TransformerConfig(**dict(model, dtype=jnp.dtype(model["dtype"]),
                                   max_seq=traffic["seq"] + 1))
    return cfg, traffic["batch"] * traffic["seq"]


def _shapes(cfg):
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


@pytest.mark.parametrize("config, picked", [
    ("evabyte-L4", {(i, name) for i in range(4) for name in DENSE}),
    ("starcoder2-3b-L6", set()),
    ("nemotron-3-nano-L9-E8", set()),
    ("lfm2-24b-a2b-L9-E8", set())])
def test_rule_picks_evabytes_dense_leaves_alone(config, picked):
    """At its cell's tokens a step, every ``w1/w2/w3/wq/wk/wv/wo`` of the
    EvaByte configuration's four blocks (16,384 x 4096 x 2 B is 128 MiB),
    and no leaf of the other three train configurations: 8,192 x 3,072,
    16,384 x 2,688 and 16,384 x 2,048 lie under the boundary, and their
    routed expert stacks, norms, embeddings and heads are never asked."""
    cfg, tokens = _train_cell(config)
    assert set(separated_leaves(cfg, _shapes(cfg), tokens)) == picked
    assert len(picked) in (0, 28)


@pytest.mark.parametrize("axes, dims, tokens, picked", [
    (("dp",), (2,), 32768, DENSE),
    (("tp",), (2,), 16384, ("w1", "w2", "w3")),
    (("dp", "sp"), (2, 2), 32768, ())])
def test_rule_reads_what_a_device_sees(axes, dims, tokens, picked):
    """On a mesh the rule reads one device's product. The EvaByte model at
    32,768 tokens on ``dp`` 2 is 16,384 a device: all 28 leaves. At 16,384
    on ``tp`` 2 the heads are split, so ``wq``-``wo`` see 16 x 128 = 2,048
    and stay fused, where the FFN's 5,504 against 4,096 do not. ``dp`` x
    ``sp`` 2 x 2 leaves 8,192 of 32,768 a device: none."""
    cfg, _ = _train_cell("evabyte-L4")
    mesh = Mesh(np.asarray(jax.devices()[:int(np.prod(dims))]).reshape(dims),
                axes)
    got = separated_leaves(cfg, _shapes(cfg), tokens, mesh)
    assert set(got) == {(i, name) for i in range(4) for name in picked}


@pytest.mark.parametrize("grad_accum, separated", [(1, 28), (2, 0)])
def test_the_tokens_are_one_microbatchs(grad_accum, separated, traced):
    """The EvaByte model's step on 2 x 8,192 tokens: one product over all
    16,384 separates each of the 28 leaves; two microbatches of 8,192
    contract over half as many tokens each, and separate none. Counted by
    ``optimizer.separated`` while the step is traced."""
    cfg, _ = _train_cell("evabyte-L4")
    init_state, step = make_train_parts(cfg, mesh=make_mesh_nd(1),
                                        grad_accum=grad_accum)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    jax.eval_shape(step, state,
                   jax.ShapeDtypeStruct((2, 8192 + 1), jnp.int32))
    assert traced.counters().get("optimizer.separated", 0) == separated


def _tokens(batch=4, seq=17, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, 64, (batch, seq)),
        jnp.int32)


_SMALL = dict(vocab=64, d_model=32, n_heads=4, d_ff=64, max_seq=32)
_CASES = {
    "classic": (dict(_SMALL, n_layers=2, ffn="swiglu", rope=True), {}),
    "evabyte-like": (dict(
        _SMALL, n_layers=2, ffn="swiglu", rope=True, attention_impl="eva",
        eva_window=8, eva_chunk=4, n_pred_heads=2, tie_embeddings=False,
        residual_dtype="float32", remat=True), {}),
    "pattern": (dict(
        _SMALL, n_layers=3, layer_pattern="F*E", n_kv_heads=2,
        attn_head_dim=16, dense_d_ff=48, d_ff=24, ffn="swiglu",
        tie_embeddings=False, position_table=False, attention_impl="flash",
        n_experts=8, moe_top_k=2, moe_experts_held=4, moe_aux_coef=0.0), {}),
    "grad_accum": (dict(_SMALL, n_layers=2), {"grad_accum": 2}),
    "fsdp": (dict(_SMALL, n_layers=2), {"fsdp": True}),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_separated_step_equals_fused_step(case, monkeypatch, traced):
    """Two steps with every dense block leaf behind its barrier give the
    parameters and AdamW state of two steps as the step stands: a barrier
    is an identity, and only the compiler's fusion may change. The fsdp
    case runs the step's other branch, on ``dp`` 2."""
    kw, train_kw = _CASES[case]
    cfg = TransformerConfig(**kw)
    mesh = make_mesh_nd(2, axes=("dp",)) if train_kw.get("fsdp") else None
    tok = _tokens()
    every = {(i, name) for i, blk in enumerate(_shapes(cfg)["blocks"])
             for name in DENSE if name in blk}
    assert every

    def two_steps():
        init_state, step = make_train_step(cfg, mesh=mesh, **train_kw)
        state = init_state(jax.random.PRNGKey(0))
        for _ in range(2):
            state, _ = step(state, tok)
        return state

    fused = two_steps()
    assert traced.counters().get("optimizer.separated", 0) == 0
    monkeypatch.setattr(transformer, "separates_update", lambda *_: True)
    init_state, body = make_train_parts(cfg, mesh=mesh, **train_kw)
    text = jax.jit(body).lower(init_state(jax.random.PRNGKey(0)),
                               tok).as_text()
    assert traced.counters()["optimizer.separated"] == len(every)
    assert text.count("optimization_barrier") >= len(every)
    separated = two_steps()
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(separated),
                    strict=True):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6,
                                   atol=0)
