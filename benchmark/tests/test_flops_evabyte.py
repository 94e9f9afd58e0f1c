"""EvaByte's operation and byte counts against hand arithmetic, and the
``eva`` scope's reduction against a hand-made trace.
Run by hand: ``python3 -m pytest benchmark/tests -q -p no:cacheprovider``."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import eva_scope  # noqa: E402
import flops_evabyte as fe  # noqa: E402

MODEL = json.loads((BENCH / "configs" / "evabyte-L4.json").read_text())["model"]
TRAFFIC = json.loads((BENCH / "traffic" / "pretrain-16k-b1.json").read_text())


def test_evabyte_l4_by_hand_at_16k():
    # One layer, one token, forward, s = 16384 (8 windows of 2048, chunks
    # of 16, 128 chunks a window):
    #   q, k, v, o: 8 x 4096^2                       = 134,217,728
    #   FFN:        6 x 4096 x 11008                 = 270,532,608
    #   EVA scores and values, 4 x 32 x 128 = 16,384 a (query, key) pair:
    #     own window: (2048 + 1) / 2 = 1024.5 keys on average
    #     summaries:  windows 0..7 see 0, 128, ..., 896: 448 on average
    #     16,384 x 1472.5                            =  24,125,440
    #   pooling:    6 x 32 x 128                     =      24,576
    parts = fe.forward_parts_per_token(MODEL, 16384)
    assert parts["qkvo"] == 4 * 134_217_728
    assert parts["ffn"] == 4 * 270_532_608
    assert parts["eva_attend"] == 4 * 24_125_440
    assert parts["eva_pool"] == 4 * 24_576
    assert parts["head"] == 2 * 4096 * 320 * 8 == 20_971_520
    # ISSUE 29's 1,736.47 M is this count without the pooling ...
    assert sum(parts.values()) - parts["eva_pool"] == 1_736_474_624
    # ... which adds 0.1 M: 1,736.57 M a token forward
    forward = fe.forward_flops_per_token(MODEL, 16384)
    assert forward == 1_736_572_928
    assert fe.train_flops_per_step(MODEL, 1, 16384) == pytest.approx(
        3 * 16384 * 1_736_572_928)                   # 85.36 TFLOP a step
    assert fe.train_flops_per_step(
        MODEL, TRAFFIC["batch"], TRAFFIC["seq"]) == pytest.approx(8.5356e13,
                                                                  rel=1e-4)


def test_eva_op_alone_by_hand():
    # forward 24,125,440 + 24,576 a token and layer; x 3 with the backward
    # pass; x 16384 tokens x 4 layers                = 4.748 TFLOP a step
    assert fe.eva_flops_per_step(MODEL, 1, 16384) == pytest.approx(
        3 * (24_125_440 + 24_576) * 16384 * 4)
    # twelve passes over a (1, 16384, 4096) bfloat16 array a layer
    assert fe.eva_bytes_per_step(MODEL, 1, 16384) == 12 * 16384 * 4096 * 2 * 4
    # at the v5e's peaks: compute bounds it (24.1 ms against 7.9 ms)
    assert fe.eva_flops_per_step(MODEL, 1, 16384) / 197e12 == pytest.approx(
        0.02410, rel=1e-3)
    assert fe.eva_bytes_per_step(MODEL, 1, 16384) / 819e9 == pytest.approx(
        0.007866, rel=1e-3)


def test_geometry_at_other_lengths():
    # one window or less: plain causal attention, no summaries
    short = fe.eva_forward_flops_per_token(MODEL, 2048)
    assert short["attend"] == 16_384 * 1024.5
    assert fe.eva_forward_flops_per_token(MODEL, 512)["attend"] == \
        16_384 * 256.5
    # 32768 = 16 windows: 0..15 x 128 summaries, 960 on average
    assert fe.eva_forward_flops_per_token(MODEL, 32768)["attend"] == \
        16_384 * (1024.5 + 960)
    with pytest.raises(ValueError):
        fe.eva_forward_flops_per_token(MODEL, 2048 + 16)


def test_full_causal_attention_would_cost_5_6_times_as_much():
    # what flops.py's count (2 s h hd a token) would charge at 16k
    full = 2 * 16384 * 4096
    assert full / 24_125_440 == pytest.approx(5.56, abs=0.01)


@pytest.mark.parametrize("op_name, stage", [
    ("jit(step)/jvp(attn)/eva/eva.local/flash_fwd/pallas_call", "eva.local"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/attn/"
     "eva/eva.remote/eva_remote_fwd/pallas_call", "eva.remote"),
    ("jit(step)/transpose(jvp(attn))/transpose(jvp(eva))/eva.merge/add",
     "eva.merge"),
    ("jit(step)/transpose(jvp(attn))/jvp(eva)/transpose(jvp(eva.summarize))"
     "/mul", "eva.summarize"),
    ("jit(step)/jvp(attn)/eva/mul", "eva"),
    ("jit(step)/jvp(attn)/bsd,dhk->bshk/dot_general", None),
    ("jit(step)/jvp(ffn)/evaluate/mul", None),
    (None, None),
])
def test_stage_of(op_name, stage):
    assert eva_scope.stage_of(op_name) == stage


def test_seconds_by_stage_by_hand():
    # one device, window [1, 9): a local kernel 2..4 (2 s), a fusion of the
    # ffn 4..6, a remote kernel 6..10 of which 3 s lie inside the window
    names = {"%flash_fwd.1": "jit(step)/jvp(attn)/eva/eva.local/flash_fwd",
             "%fusion.2": "jit(step)/jvp(ffn)/mul",
             "%eva_remote_fwd.3": "jit(step)/jvp(attn)/eva/eva.remote/x"}
    ops = [[("%flash_fwd.1", 2.0, 2.0), ("%fusion.2", 4.0, 2.0),
            ("%eva_remote_fwd.3", 6.0, 4.0)]]
    got = eva_scope.seconds_by_stage(ops, names, (1.0, 9.0))
    assert got == {"eva.local": 2.0, "eva.remote": 3.0}
    assert eva_scope.seconds_by_stage(ops, {}, (1.0, 9.0)) == {}
