"""``flops_lfm2.py``, ``lfm2_scope.py`` and the five readers of the
``lfm2-24b-a2b-L9-E8`` cell against hand arithmetic at the published
widths of its configuration."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import flops_lfm2 as flops  # noqa: E402
import lfm2_scope  # noqa: E402
import run as bench  # noqa: E402

BATCH, SEQ = 2, 8192


@pytest.fixture(scope="module")
def model():
    with open(HERE / "configs" / "lfm2-24b-a2b-L9-E8.json") as f:
        return json.load(f)["model"]


def test_a_layer_of_each_kind_by_hand(model):
    got = flops.layer_forward_flops_per_token(model, SEQ)
    assert got["C"] == {"in_proj": 2 * 2048 * 6144,
                        "out_proj": 2 * 2048 * 2048, "conv": 2 * 3 * 2048,
                        "gates": 2 * 2048}
    assert got["*"] == {"qo": 4 * 2048 * 32 * 64, "kv": 4 * 2048 * 8 * 64,
                        "scores": 4 * 32 * 64 * 8193 / 2}
    assert got["F"] == {"dense": 6 * 2048 * 11776}
    assert got["E"] == {"router": 2 * 2048 * 64,
                        "routed": 0.5 * 6 * 2048 * 1536}


def test_a_token_meets_half_a_held_expert(model):
    assert flops.pairs_per_token(model) == 4 * 8 / 64 == 0.5
    assert flops.pairs_per_token(dict(model, moe_experts_held=None)) == 4


def test_the_step_by_hand(model):
    """29.49 TFLOP a step (150 ms at a v5e's 197 TFLOP/s): the conv
    operators 39%, the dense FFN 24%, attention 18%, the routed experts
    13%, the head 6% (ISSUE 40's table)."""
    parts = flops.forward_parts_per_token(model, SEQ)
    c = 25165824 + 8388608 + 12288 + 4096                    # 33,570,816
    attn = 16777216 + 4194304 + 33558528                     # 54,530,048
    e = 262144 + 9437184                                      # 9,699,328
    assert parts == {"C": 7 * c, "*": 2 * attn, "F": 144703488,
                     "E": 8 * e, "head": 2 * 2048 * 8192}
    forward = 7 * c + 2 * attn + 144703488 + 8 * e + 33554432
    assert forward == 599908352
    assert flops.forward_flops_per_token(model, SEQ) == forward
    assert flops.train_flops_per_step(model, BATCH, SEQ) == \
        3 * 16384 * forward
    share = {k: round(100 * v / forward) for k, v in parts.items()}
    assert share == {"C": 39, "F": 24, "*": 18, "E": 13, "head": 6}
    assert 0.149 < flops.train_flops_per_step(model, BATCH, SEQ) / 197e12 \
        < 0.150


def test_the_conv_operators_and_the_routed_products_by_hand(model):
    assert flops.shortconv_flops_per_step(model, BATCH, SEQ) == \
        3 * 16384 * 7 * 33570816
    assert flops.shortconv_bytes_per_step(model, BATCH, SEQ) == \
        (3 * 4 * 2048 * 2048 + 16384 * 4 * 2048) * 2 * 7
    assert flops.routed_flops_per_step(model, BATCH, SEQ) == \
        3 * 16384 * 8 * 9437184.0
    pairs = 16384 * 0.5                                  # 8,192
    assert flops.routed_bytes_per_step(model, BATCH, SEQ) == \
        (3 * 8 * 3 * 2048 * 1536 + pairs * (4 * 2048 + 4 * 1536)) * 2 * 8
    # compute bounds both on a v5e (197 TFLOP/s, 819 GB/s)
    for count, moved in ((flops.shortconv_flops_per_step,
                          flops.shortconv_bytes_per_step),
                         (flops.routed_flops_per_step,
                          flops.routed_bytes_per_step)):
        assert count(model, BATCH, SEQ) / 197e12 > \
            moved(model, BATCH, SEQ) / 819e9


def test_the_attention_kernels_by_hand(model):
    """Two ``*`` layers of 32 heads x 64 over the causal half of 8,192:
    3.30 TFLOP a step, 16.7 ms at a v5e's 197 TFLOP/s."""
    assert flops.flash_flops_per_step(model, BATCH, SEQ) == \
        3 * 16384 * 2 * 4 * 32 * 64 * 8193 / 2
    assert 16.6e-3 < flops.flash_flops_per_step(model, BATCH, SEQ) / 197e12 \
        < 16.8e-3


@pytest.mark.parametrize("op_name, stage", [
    ("jit(step)/jvp(attn)/shortconv/shortconv.conv/mul", "shortconv.conv"),
    ("jit(step)/transpose(jvp(attn))/rematted_computation/attn/shortconv/"
     "shortconv.in_proj/dot_general", "shortconv.in_proj"),
    ("jit(step)/jvp(attn)/shortconv/shortconv.out_proj/dot_general",
     "shortconv.out_proj"),
    ("jit(step)/jvp(attn)/shortconv/mul", "shortconv"),
    ("jit(step)/jvp(ffn)/moe.routed/while/body/scatter-add", None),
    ("jit(step)/jvp(attn)/dot_general", None),
    (None, None),
])
def test_an_op_goes_to_its_stage(op_name, stage):
    assert lfm2_scope.stage_of(op_name) == stage


def test_seconds_by_op_sum_the_innermost_ops_inside_the_window():
    got = {"window": (0.5, 10.0), "device_ops": [[
        ("%a = f32[1] fusion()", 0.0, 4.0),
        ("%c = f32[1] fusion()", 1.0, 2.0),
        ("%b = f32[1] fusion()", 5.0, 1.0)]],
        "op_names": {
            "%a = f32[1] fusion()": "jit(step)/jvp(attn)/shortconv/"
                                    "shortconv.conv/mul",
            "%c = f32[1] fusion()": "jit(step)/jvp(ffn)/moe.routed/sort",
            "%b = f32[1] fusion()": "jit(step)/jvp(attn)/shortconv/"
                                    "shortconv.in_proj/dot"}}
    assert lfm2_scope.seconds_by_op(got) == {
        "shortconv.conv": {"%a = f32[1] fusion()": 1.5},
        "shortconv.in_proj": {"%b = f32[1] fusion()": 1.0}}


def test_the_readers_find_nothing_where_the_program_has_nothing(
        model, monkeypatch):
    """A run with no trace (an untraced run, or the parent of PR 40): each
    reader returns ``None`` and raises nothing; the host-clock share reads
    the step stamps."""
    import nemotron_scope

    for scope in (lfm2_scope, nemotron_scope):      # not the checkout's
        monkeypatch.setattr(scope, "of_run", lambda: None)
    run = {"record": {"model": model, "batch": BATCH, "seq": SEQ,
                      "step_stamps": [0.0, 0.5, 1.0]},
           "trace": {}, "chips": 1,
           "peaks": {"bf16_tflops": 197.0, "hbm_gbytes_per_s": 819.0}}
    for name in ("shortconv_time_share", "shortconv_roofline_share",
                 "moe_routed_roofline_share.lfm2",
                 "flash_roofline_share.lfm2"):
        assert bench.reader_of("layer_metrics", name).read(run) is None
    mfu = bench.reader_of("layer_metrics", "train_mfu.lfm2").read(run)
    want = 100 * flops.train_flops_per_step(model, BATCH, SEQ) / 0.5 / 197e12
    assert mfu == pytest.approx(want)
    other = dict(run, record=dict(run["record"], model=dict(
        model, layer_pattern="MEMEM*EME")))
    assert bench.reader_of("layer_metrics", "train_mfu.lfm2").read(
        other) is None
