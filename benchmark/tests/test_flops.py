"""The operation counts against a hand count and against ``bench.py``.
Run by hand: ``python3 -m pytest benchmark/tests -q -p no:cacheprovider``."""

import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402

MODEL = json.loads((BENCH / "configs" / "starcoder2-3b-L6.json").read_text())["model"]


def test_starcoder2_3b_l6_by_hand():
    # One layer, one token, forward, s = 4096:
    #   q and o: 2 x (2 x 3072 x 3072)            =  37,748,736
    #   k and v: 2 x (2 x 3072 x 2 x 128)          =   3,145,728
    #   FFN:     2 x (2 x 3072 x 12288)            = 150,994,944
    #   scores and values, causal: 2 x 4096 x 3072 =  25,165,824
    layer = 37_748_736 + 3_145_728 + 150_994_944 + 25_165_824
    logits = 2 * 3072 * 49152                      # 301,989,888
    forward = 6 * layer + logits
    assert flops.forward_flops_per_token(MODEL, 4096) == forward
    assert 3 * forward == 4_812_963_840           # 4.81 GFLOP a token
    assert flops.train_flops_per_step(MODEL, 2, 4096) == pytest.approx(
        3 * forward * 8192)                        # 39.4 TFLOP a step
    # flash: 6 b h s^2 hd a layer
    assert flops.flash_flops_per_step(MODEL, 2, 4096) == pytest.approx(
        6 * 2 * 24 * 4096 ** 2 * 128 * 6)


def test_equals_bench_py_without_grouped_heads():
    sys.path.insert(0, str(BENCH.parent))
    bench = pytest.importorskip("bench")
    for h, d, ff, layers, vocab, b, s in [(8, 1024, 4096, 8, 8192, 8, 1024),
                                          (24, 3072, 12288, 6, 49152, 2, 4096)]:
        model = dict(vocab=vocab, d_model=d, n_heads=h, n_kv_heads=h,
                     d_ff=ff, n_layers=layers)
        cfg = types.SimpleNamespace(d_model=d, d_ff=ff, n_layers=layers,
                                    vocab=vocab)
        assert flops.train_flops_per_step(model, b, s) == pytest.approx(
            bench.train_flops_per_step(cfg, b, s))
        model["n_kv_heads"] = None                 # the program's "MHA"
        assert flops.train_flops_per_step(model, b, s) == pytest.approx(
            bench.train_flops_per_step(cfg, b, s))


def test_grouped_heads_cost_less_than_bench_py_charges():
    sys.path.insert(0, str(BENCH.parent))
    bench = pytest.importorskip("bench")
    cfg = types.SimpleNamespace(d_model=3072, d_ff=12288, n_layers=6,
                                vocab=49152)
    ours = flops.train_flops_per_step(MODEL, 2, 4096)
    theirs = bench.train_flops_per_step(cfg, 2, 4096)
    # k and v at 2 of 24 heads: 2 x 2 x 3072 x (3072 - 256) fewer a layer
    assert theirs - ours == pytest.approx(
        3 * 8192 * 6 * 2 * 2 * 3072 * (3072 - 256))
