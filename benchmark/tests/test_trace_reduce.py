"""The trace reduction against hand arithmetic. Run by hand:

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

Not part of ``tests/``: the benchmark's own check of its yardstick.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce as tr  # noqa: E402

FUSION = "%fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(f32[8,8]{1,0} %p0), kind=kLoop"
KERNEL = ('%jvp__.4 = (bf16[48,4096,128]{2,1,0:T(8,128)(2,1)}, f32[48,1,4096]'
          '{2,1,0:T(1,128)}) custom-call(bf16[48,4096,128]{2,1,0} %bitcast.1), '
          'custom_call_target="tpu_custom_call"')


def hand_made():
    """One device, window 0..10 s. Ops: fusion 1..4 and kernel 3..6 overlap
    (union 1..6 = 5 s), fusion again 8..9 (1 s). Busy 6 s, idle 4 s: gaps
    0..1 (under bench.loader_wait), 6..8 (mostly under bench.wait_step),
    9..10 (under no span)."""
    ops = [(FUSION, 1.0, 3.0), (KERNEL, 3.0, 3.0), (FUSION, 8.0, 1.0)]
    spans = [("bench.window", 0.0, 10.0), ("bench.loader_wait", 0.0, 1.5),
             ("bench.wait_step", 6.5, 1.5), ("bench.dispatch", 6.0, 0.2)]
    return ops, spans


def test_busy_union_idle_share_and_totals():
    ops, spans = hand_made()
    r = tr.reduce_events([ops], spans)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(6.0)          # 5 + 1, overlap once
    assert r["idle_share"] == pytest.approx(0.4)
    assert r["ops"][FUSION]["s"] == pytest.approx(4.0)  # 3 + 1
    assert r["ops"][FUSION]["n"] == 2
    assert r["ops"][KERNEL]["s"] == pytest.approx(3.0)
    assert sorted(r["ops"][FUSION]["durations"]) == pytest.approx([1.0, 3.0])
    assert r["longest_gap_s"] == pytest.approx(2.0)


def test_gaps_go_to_the_span_that_covers_most():
    ops, spans = hand_made()
    gaps = tr.reduce_events([ops], spans)["gaps"]
    assert gaps == pytest.approx({"bench.loader_wait": 1.0,
                                  "bench.wait_step": 2.0,
                                  tr.OUTSIDE: 1.0})
    assert sum(gaps.values()) == pytest.approx(4.0)    # all the idle time


def test_events_are_clipped_to_the_window():
    ops, spans = hand_made()
    spans[0] = ("bench.window", 2.0, 6.0)              # window 2..8
    r = tr.reduce_events([ops], spans)
    assert r["window_s"] == pytest.approx(6.0)
    assert r["busy_s"] == pytest.approx(4.0)           # 2..6
    assert r["ops"][FUSION]["s"] == pytest.approx(2.0)  # 2..4 of 1..4


def test_mean_over_devices():
    ops, spans = hand_made()
    r = tr.reduce_events([ops, [(FUSION, 0.0, 10.0)]], spans)
    assert r["busy_s_per_device"] == pytest.approx([6.0, 10.0])
    assert r["busy_s"] == pytest.approx(8.0)
    assert r["idle_share"] == pytest.approx(0.2)
    assert r["ops"][FUSION]["s"] == pytest.approx((4.0 + 10.0) / 2)


def test_names_and_matching():
    assert tr.short_name(FUSION) == "fusion.1 fusion f32[8,8]"
    assert tr.opcode(KERNEL) == "custom-call"
    assert tr.short_name(KERNEL) == (
        "jvp__.4 custom-call (bf16[48,4096,128], f32[48,1,4096])")
    assert tr.short_name("bench.window") == "bench.window"
    ops, spans = hand_made()
    r = tr.reduce_events([ops], spans)
    assert list(tr.pallas_ops(r)) == [KERNEL]
    assert list(tr.ops_matching(r, opcodes=("fusion",))) == [FUSION]
    assert tr.collective_ops(r) == {}
    psum = "%psum.7 = f32[1,16]{1,0:T(1,128)} all-reduce(f32[1,16]{1,0} %param.1)"
    assert list(tr.collective_ops(
        tr.reduce_events([[(psum, 0.0, 1.0)]], []))) == [psum]
    b = tr.breakdown(r)
    assert b["device_ops"][0] == ["fusion.1 fusion f32[8,8]", pytest.approx(4.0)]
    assert b["idle_gaps"][0] == ["bench.wait_step", pytest.approx(2.0)]


def test_innermost_runtime_event_of_a_thread():
    thread = [("A", 0.0, 10.0), ("B", 2.0, 3.0), ("C", 3.0, 1.0),
              ("D", 12.0, 1.0)]
    assert tr.leaf_segments(thread) == [
        ("A", 0.0, 2.0), ("B", 2.0, 3.0), ("C", 3.0, 4.0), ("B", 4.0, 5.0),
        ("A", 5.0, 10.0), ("D", 12.0, 13.0)]


def test_what_the_host_did_while_the_device_was_idle():
    """Idle stretches 0..1, 6..8, 9..10. Thread 1 is in A but for 2..5 (B, C
    inside it); thread 2 is in E for 6.5..7.5. A: 1 + 2 + 1; E: 1; B and C
    ran while the device was busy."""
    ops, spans = hand_made()
    threads = [[("A", 0.0, 10.0), ("B", 2.0, 3.0), ("C", 3.0, 1.0)],
               [("E", 6.5, 1.0)]]
    r = tr.reduce_events([ops], spans, host_threads=threads)
    assert r["host_in_idle"] == pytest.approx({"A": 4.0, "E": 1.0})
    gaps = tr.breakdown(r)["idle_gaps"]
    assert gaps[-2:] == [["host: A", pytest.approx(4.0)],
                         ["host: E", pytest.approx(1.0)]]


def test_no_device_events_reduce_to_nothing():
    assert tr.reduce_events([], []) == {}
