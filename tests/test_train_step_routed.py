"""The comparison of the benchmark's kind ``train_step_routed`` at the tiny
shapes of its rehearsal configuration: the program as it is lies within
every limit, and each lower precision the comparison exists to catch
fails the number meant for it. The controls are patched in here; the
program has no switch for them."""

import importlib.util
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from mpi_tpu.models import TransformerConfig, make_mesh_nd, mamba2, moe
from mpi_tpu.models.transformer import init_params
from mpi_tpu.ops import ssd

BENCH = Path(__file__).resolve().parent.parent / "benchmark"


def _load(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def cell():
    conf = json.loads(
        (BENCH / "configs" / "_rehearsal-train_step_routed.json").read_text())
    seq = 64
    cfg = TransformerConfig(**dict(conf["model"], dtype=jnp.float32,
                                   max_seq=seq + 1))
    params = init_params(jax.random.PRNGKey(3), cfg)
    one = jax.random.randint(jax.random.PRNGKey(4), (1, seq + 1), 0,
                             cfg.vocab)
    kind = _load(BENCH / "kinds" / "train_step_routed.py")
    reference = _load(BENCH / "reference" / "nemotron_h_lm.py")
    lines = []

    def compare(**limits):
        del lines[:]
        ok, numbers = kind.compare(params, one, cfg, make_mesh_nd(1),
                                   dict(conf, **limits), reference,
                                   lines.append)
        return ok, numbers, "\n".join(lines)

    return compare, kind


def test_the_program_as_it_is_lies_within_every_limit(cell):
    compare, _ = cell
    ok, numbers, said = compare()
    assert ok and said.endswith("over: none: ok")
    assert numbers["routing_outside_top_k"] == [0.0] * 4
    assert numbers["scan_rel_err"] < 1e-6
    assert max(numbers["grad_rel_err"].values()) < 1e-4
    assert set(numbers["grad_rel_err"]) == {
        "A_log", "D", "conv_b", "conv_w", "dt_bias", "in_proj", "ln1/scale",
        "out_proj", "ssm_norm"}
    assert abs(numbers["loss_system"] - numbers["loss_reference"]) < 1e-5


def test_a_leaf_has_its_own_limit_or_the_one_for_the_rest(cell):
    compare, kind = cell
    assert kind._limit(0.2, "A_log") == 0.2
    assert kind._limit({"*": 0.1, "A_log": 0.3}, "A_log") == 0.3
    assert kind._limit({"*": 0.1, "A_log": 0.3}, "D") == 0.1
    ok, _, said = compare(grad_tolerance={"*": 1.0, "D": 1e-9})
    assert not ok and "over: ['D']: FAILED" in said
    assert compare(grad_tolerance={"*": 1e-9, "D": 1.0})[0] is False


def _bf16_scores(x2, router):
    return jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x2.astype(jnp.bfloat16), router.astype(jnp.bfloat16))
    ).astype(jnp.float32)


def _bf16_state_scan():
    """``ops/ssd.py`` with the state carried in bfloat16 from chunk to
    chunk."""
    src = Path(ssd.__file__).read_text()
    carry = "        return whole_c[..., None, None] * state + own_c, state"
    start = "jnp.zeros((b, g, r, p, n), _F32)"
    assert carry in src and start in src
    src = src.replace(carry + "  # ENTERING", (
        "        nxt = whole_c[..., None, None] * state.astype(_F32) + own_c\n"
        "        return nxt.astype(jnp.bfloat16), state")).replace(
            start, start.replace("_F32", "jnp.bfloat16"))
    module = types.ModuleType("ssd_bf16_state")
    module.__package__ = ssd.__package__    # the module's relative imports
    exec(compile(src, "ssd_bf16_state", "exec"), module.__dict__)
    return module


def _no_shared(x, params, *args, **kw):
    held = dict(params, shared_down=jnp.zeros_like(params["shared_down"]))
    return _no_shared.whole(x, held, *args, **kw)


_no_shared.whole = moe.routed_share_ffn


@pytest.mark.parametrize("control, number", [
    ("router", "routing_outside_top_k"), ("scan", "scan_rel_err"),
    ("shared", "grad_rel_err")])
def test_each_control_fails_the_number_meant_for_it(cell, monkeypatch,
                                                    control, number):
    compare, _ = cell
    sound = compare()[1]
    if control == "router":
        monkeypatch.setattr(moe, "_router_scores", _bf16_scores)
    elif control == "scan":
        narrow = _bf16_state_scan()
        monkeypatch.setattr(ssd, "ssd_scan", narrow.ssd_scan)
        monkeypatch.setattr(mamba2, "ssd_scan_flat", narrow.ssd_scan_flat)
    else:
        monkeypatch.setattr(moe, "routed_share_ffn", _no_shared)
    ok, numbers, said = compare()
    assert not ok and said.endswith("FAILED")

    def worst(x):
        x = x[number]
        if isinstance(x, dict):
            return max(x.values())
        return max(x) if isinstance(x, list) else x

    assert worst(numbers) > 100 * max(worst(sound), 1e-7)
    if control != "shared":     # and nothing else moved far
        wide = {"router": "routing_tolerance", "scan": "scan_tolerance"}
        assert compare(**{wide[control]: 1.0}, grad_tolerance=0.05,
                       loss_tolerance=0.01)[0]
