"""``flops_nemotron_h.py`` and ``nemotron_scope.py`` against hand
arithmetic at the published widths of the benchmark's
``nemotron-3-nano-L9-E8`` configuration."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import flops_nemotron_h as flops  # noqa: E402
import nemotron_scope  # noqa: E402

BATCH, SEQ = 2, 8192


@pytest.fixture(scope="module")
def model():
    with open(HERE / "configs" / "nemotron-3-nano-L9-E8.json") as f:
        return json.load(f)["model"]


def test_a_layer_of_each_kind_by_hand(model):
    got = flops.layer_forward_flops_per_token(model, SEQ)
    assert got["M"] == {
        "in_proj": 2 * 2688 * (4096 + 6144 + 64),     # 55,394,304
        "conv": 2 * 4 * 6144,
        "scan": (8 * 2 * 128 * 64.5 + 64 * 2 * 64 * 64.5
                 + 2 * 64 * 2 * 64 * 128),
        "out_proj": 2 * 4096 * 2688}
    assert got["M"]["scan"] == 2757632.0
    assert got["*"] == {"qo": 4 * 2688 * 4096, "kv": 4 * 2688 * 256,
                        "scores": 4 * 4096 * 8193 / 2}
    assert got["E"] == {"router": 2 * 2688 * 128, "shared": 4 * 2688 * 3712,
                        "routed": 0.375 * 4 * 2688 * 1856}


def test_a_token_meets_three_eighths_of_a_held_expert(model):
    assert flops.pairs_per_token(model) == 6 * 8 / 128 == 0.375
    assert flops.pairs_per_token(dict(model, moe_experts_held=None)) == 6


def test_the_step_by_hand(model):
    parts = flops.forward_parts_per_token(model, SEQ)
    m = 55394304 + 49152 + 2757632 + 22020096          # 80,221,184
    assert parts == {"M": 4 * m, "*": 44040192 + 2752512 + 67117056.0,
                     "E": 4 * (688128 + 39911424 + 7483392.0),
                     "head": 2 * 2688 * 16384}
    forward = 4 * m + 113909760 + 4 * 48082944 + 88080384  # 715,206,656
    assert flops.forward_flops_per_token(model, SEQ) == forward
    assert flops.train_flops_per_step(model, BATCH, SEQ) == \
        3 * 16384 * forward                              # 35.15 TFLOP
    # the mixers are 45% of it, the experts 27%, attention 16%, the head 12%
    share = {k: round(100 * v / forward) for k, v in parts.items()}
    assert share == {"M": 45, "*": 16, "E": 27, "head": 12}


def test_the_scans_and_the_routed_products_by_hand(model):
    assert flops.scan_flops_per_step(model, BATCH, SEQ) == \
        3 * 16384 * 4 * 2757632.0
    # five passes over x-shaped arrays, three over B and C, bfloat16
    assert flops.scan_bytes_per_step(model, BATCH, SEQ) == \
        16384 * (5 * 4096 + 3 * 2048) * 2 * 4
    assert flops.routed_flops_per_step(model, BATCH, SEQ) == \
        3 * 16384 * 4 * 7483392.0
    pairs = 16384 * 0.375                                # 6,144
    assert flops.routed_bytes_per_step(model, BATCH, SEQ) == \
        (3 * 8 * 2 * 2688 * 1856 + pairs * (4 * 2688 + 2 * 1856)) * 2 * 4
    # which bounds which on a v5e (197 TFLOP/s, 819 GB/s)
    assert flops.scan_flops_per_step(model, BATCH, SEQ) / 197e12 < \
        flops.scan_bytes_per_step(model, BATCH, SEQ) / 819e9
    assert flops.routed_flops_per_step(model, BATCH, SEQ) / 197e12 > \
        flops.routed_bytes_per_step(model, BATCH, SEQ) / 819e9


def test_the_attention_kernels_by_hand(model):
    """One ``*`` layer of 32 heads x 128 over the causal half of 8,192."""
    assert flops.flash_flops_per_step(model, BATCH, SEQ) == \
        3 * 16384 * 1 * 4 * 32 * 128 * 8193 / 2
    # 3.30 TFLOP a step: 16.7 ms at the v5e's 197 TFLOP/s
    assert 16.6e-3 < flops.flash_flops_per_step(model, BATCH, SEQ) / 197e12 \
        < 16.8e-3
    two = dict(model, layer_pattern="M*E*", n_layers=4)
    assert flops.flash_flops_per_step(two, BATCH, SEQ) == \
        2 * flops.flash_flops_per_step(model, BATCH, SEQ)


@pytest.mark.parametrize("op_name, stage", [
    ("jit(step)/jvp(attn)/ssm/ssm.scan/dot_general", "ssm.scan"),
    ("jit(step)/transpose(jvp(attn))/rematted_computation/attn/ssm/"
     "ssm.in_proj/dot_general", "ssm.in_proj"),
    ("jit(step)/jvp(attn)/ssm/ssm.scan/while/body/mul", "ssm.scan"),
    ("jit(step)/jvp(attn)/ssm/mul", "ssm"),
    ("jit(step)/jvp(ffn)/moe.route/top_k", "moe.route"),
    ("jit(step)/jvp(ffn)/moe.routed/while/body/scatter-add",
     "moe.routed"),
    ("jit(step)/transpose(jvp(ffn))/moe.shared/dot_general", "moe.shared"),
    ("jit(step)/jvp(attn)/dot_general", None),
    ("jit(step)/optimizer/add", None),
    (None, None),
])
def test_an_op_goes_to_its_stage(op_name, stage):
    assert nemotron_scope.stage_of(op_name) == stage


def test_seconds_by_stage_sum_the_innermost_ops_inside_the_window():
    ops = [[("%a = f32[1] fusion()", 0.0, 4.0),
            ("%c = f32[1] fusion()", 1.0, 2.0),
            ("%b = f32[1] fusion()", 5.0, 1.0)]]
    names = {"%a = f32[1] fusion()": "jit(step)/jvp(attn)/ssm/ssm.scan/exp",
             "%c = f32[1] fusion()": "jit(step)/jvp(ffn)/moe.routed/sort",
             "%b = f32[1] fusion()": "jit(step)/optimizer/add"}
    got = nemotron_scope.seconds_by_stage(ops, names, window=(0.5, 10.0))
    assert got == {"ssm.scan": 1.5, "moe.routed": 2.0}
