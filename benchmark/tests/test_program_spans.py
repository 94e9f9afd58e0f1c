"""The program-span and scope reductions against hand arithmetic. Run by
hand, with ``test_trace_reduce.py``:

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

Not part of ``tests/``: the benchmark's own check of its yardstick.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import program_spans as ps  # noqa: E402

AR = {"op": "allreduce"}


def one_call(t0, *, host_read=2.0, device_put=0.5, launch=0.1,
             read_back=1.0, slack=0.4, op=AR):
    """A leader thread's spans of one call starting at ``t0``: the leader
    span covers its four stages back to back plus ``slack`` of its own."""
    spans, t = [], t0
    for name, dur in (("host_read", host_read), ("device_put", device_put),
                      ("launch", launch), ("read_back", read_back)):
        spans.append((f"xla.coll.{name}", t, dur, dict(op, bytes=64)))
        t += dur
    total = host_read + device_put + launch + read_back + slack
    return [("xla.coll.leader", t0, total, dict(op))] + spans


def hand_made():
    """Window 0..20. Thread A (rank 0, the leader of both calls): facade
    span 0..5 round a leader 0.5..4.5 (stages 3.6, own 0.4), then facade
    10..16 round a leader 10.5..14.5. Thread B: waits only."""
    a = ([("bench.window", 0.0, 20.0, {}),
          ("mpi.allreduce", 0.0, 5.0, {}), ("mpi.allreduce", 10.0, 6.0, {})]
         + one_call(0.5) + one_call(10.5))
    b = [("xla.coll.arrive_wait", 0.0, 0.5, AR),
         ("xla.coll.release_wait", 0.5, 4.2, AR),
         ("xla.coll.arrive_wait", 10.0, 0.5, AR),
         ("xla.coll.release_wait", 10.5, 4.2, AR)]
    return [a, b]


def test_self_time_is_duration_minus_what_children_cover():
    table = ps.stage_table(ps.rows_of(hand_made()))
    leader = table["xla.coll.leader op=allreduce"]
    assert leader["calls"] == 2
    assert leader["total_s"] == pytest.approx(8.0)
    assert leader["self_s"] == pytest.approx(0.8)      # 2 x (4.0 - 3.6)
    assert leader["median_s"] == pytest.approx(4.0)
    facade = table["mpi.allreduce"]
    assert facade["total_s"] == pytest.approx(11.0)
    assert facade["self_s"] == pytest.approx(3.0)      # (5 - 4) + (6 - 4)
    # Grandchildren are taken from their parent only, not from the facade.
    assert table["xla.coll.host_read op=allreduce"]["self_s"] \
        == pytest.approx(4.0)
    assert "bench.window" not in table                 # the clip, no stage
    assert table["xla.coll.release_wait op=allreduce"]["total_s"] \
        == pytest.approx(8.4)


def test_spans_are_clipped_to_the_window():
    threads = hand_made()
    threads[0][0] = ("bench.window", 2.0, 10.0, {})    # window 2..12
    rows = ps.rows_of(threads)
    table = ps.stage_table(rows)
    # First call: host_read 0.5..2.5 keeps 0.5 s; leader 0.5..4.5 keeps
    # 2.5 s. Second call: leader 10.5..14.5 keeps 1.5 s, all of it
    # host_read (10.5..12.5 cut at 12). Whole durations stay whole.
    assert table["xla.coll.host_read op=allreduce"]["total_s"] \
        == pytest.approx(0.5 + 1.5)
    assert table["xla.coll.leader op=allreduce"]["total_s"] \
        == pytest.approx(2.5 + 1.5)
    assert table["xla.coll.leader op=allreduce"]["median_s"] \
        == pytest.approx(4.0)
    # device_put of the second call (12.5..13.0) lies outside: not there.
    assert table["xla.coll.device_put op=allreduce"]["calls"] == 1
    # Self time inside the window: first leader 2.5 - (0.5 + 0.5 + 0.1 +
    # 1.0) = 0.4; second 1.5 - 1.5 = 0.
    assert table["xla.coll.leader op=allreduce"]["self_s"] \
        == pytest.approx(0.4)


def test_host_copy_share_sums_the_three_copy_stages():
    rows = ps.rows_of(hand_made())
    # (2.0 + 0.5 + 1.0) x 2 calls / (5 + 6) s of calls; launch is no copy.
    assert ps.host_copy_share(rows, [5.0, 6.0], "allreduce") \
        == pytest.approx(100 * 7.0 / 11.0)


def test_host_copy_share_reads_only_its_own_collective():
    threads = hand_made()
    threads[0] += one_call(17.0, host_read=1.0, device_put=0.2, launch=0.1,
                           read_back=0.3, slack=0.1, op={"op": "bcast"})
    rows = ps.rows_of(threads)
    assert ps.host_copy_share(rows, [5.0, 6.0], "allreduce") \
        == pytest.approx(100 * 7.0 / 11.0)
    assert ps.host_copy_share(rows, [2.0], "bcast") \
        == pytest.approx(100 * 1.5 / 2.0)


def test_host_copy_share_is_zero_not_none_when_nothing_is_copied():
    # Payloads that stay on the device: leader and launch, no copy span.
    threads = [[("bench.window", 0.0, 10.0, {}),
                ("xla.coll.leader", 1.0, 1.0, AR),
                ("xla.coll.launch", 1.2, 0.5, AR)]]
    assert ps.host_copy_share(ps.rows_of(threads), [2.0], "allreduce") == 0.0
    # A program without the driver's spans (the parent of PR 27): nothing.
    bare = [[("bench.window", 0.0, 10.0, {}),
             ("bench.allreduce", 1.0, 2.0, {})]]
    assert ps.host_copy_share(ps.rows_of(bare), [2.0], "allreduce") is None
    assert ps.sync_us(ps.rows_of(bare), [2.0], "allreduce") is None
    assert ps.median_ms(ps.rows_of(bare), "data.batch") is None
    # And no traced calls: nothing, whatever the spans.
    assert ps.host_copy_share(ps.rows_of(threads), [], "allreduce") is None


def test_sync_us_pairs_the_kth_call_with_the_kth_leader():
    rows = ps.rows_of(hand_made())
    # Calls of 5.0, 6.0 s over leaders of 4.0, 4.0 s: 1.0 and 2.0 s; with
    # a third call of 4.5 s over a leader of 4.25 s: 0.25 s. Median 1.0 s.
    threads = hand_made()
    threads[1] += one_call(15.0, slack=0.65)           # led by thread B
    rows3 = ps.rows_of(threads)
    assert ps.sync_us(rows, [5.0, 6.0], "allreduce") \
        == pytest.approx(1.5e6)
    assert ps.sync_us(rows3, [5.0, 6.0, 4.5], "allreduce") \
        == pytest.approx(1.0e6)
    # Pairing is by order of start, not by duration: swapped call times
    # give 6 - 4, 5 - 4, 4.5 - 4.25 all the same; unequal leaders do not.
    threads = [[("bench.window", 0.0, 30.0, {}),
                ("xla.coll.leader", 20.0, 1.0, AR),
                ("xla.coll.leader", 1.0, 3.0, AR)]]
    assert ps.sync_us(ps.rows_of(threads), [3.5, 1.1], "allreduce") \
        == pytest.approx(0.3e6)                        # (0.5 + 0.1) / 2
    # One leader span a call, or no pairing at all.
    assert ps.sync_us(rows, [5.0], "allreduce") is None


def test_median_ms_of_whole_durations():
    threads = [[("bench.window", 0.0, 10.0, {}),
                ("data.batch", 1.0, 0.002, {"step": 3}),
                ("data.source", 1.0, 0.001, {"step": 3}),
                ("data.batch", 2.0, 0.004, {"step": 4}),
                ("data.batch", 9.999, 0.009, {"step": 5})]]  # cut by the window
    assert ps.median_ms(ps.rows_of(threads), "data.batch") \
        == pytest.approx(4.0)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/jvp(attn)/bsd,dhk->bshk/dot_general:", "attn"),
    ("jit(step)/transpose(jvp(attn))/flash_bwd_dkv/pallas_call:", "attn"),
    ("jit(step)/transpose(jvp(ffn))/bsf,fd->bsd/dot_general:", "ffn"),
    ("jit(step)/optimizer/add:", "optimizer"),
    ("jit(step)/jit(main)/checkpoint(attn)/rematted_computation/mul", "attn"),
    ("jit(step)/transpose(jvp(logits_loss))/jit(take_along_axis)/gather",
     "logits_loss"),
    ("jit(step)/transpose(jvp(embed))/scatter-add", "embed"),
    # The outermost scope wins; a primitive or a jitted helper of a
    # scope's name inside another path component is no scope.
    ("jit(step)/jvp(ffn)/attn/mul", "ffn"),
    ("jit(step)/jit(_var)/reduce_sum", "unscoped"),
    ("jit(step)/jit(attn_helper)/mul", "unscoped"),
    ("jit(step)/attnx/mul", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of_both_forms_of_op_name(op_name, scope):
    assert ps.scope_of(op_name) == scope


def test_scope_shares_and_unscoped_sum_to_the_busy_time():
    names = {"%f.1": "jit(step)/jvp(attn)/dot_general:",
             "%k.2": "jit(step)/transpose(jvp(attn))/flash_bwd_dq/pallas_call:",
             "%f.3": "jit(step)/transpose(jvp(ffn))/dot_general:",
             "%f.4": "jit(step)/optimizer/add:",
             "%f.5": "jit(step)/jvp(logits_loss)/reduce_max:",
             "%f.6": "jit(step)/jvp(embed)/gather:"}
    # One device, window 0..10: attn 1 + 2 s, ffn 1.5 s, optimizer 0.5 s,
    # logits_loss 1 s, embed 0.25 s, a copy of 0.75 s with no name, and a
    # while op 8..9.5 whose body's ffn op 8.5..9 lies inside it.
    ops = [("%f.1", 0.0, 1.0), ("%k.2", 1.0, 2.0), ("%f.3", 3.0, 1.5),
           ("%f.4", 4.5, 0.5), ("%f.5", 5.0, 1.0), ("%f.6", 6.0, 0.25),
           ("%copy.7", 6.25, 0.75), ("%while.8", 8.0, 1.5),
           ("%f.3", 8.5, 0.5)]
    got = ps.scope_seconds([ops], names, (0.0, 10.0))
    assert got == pytest.approx({"attn": 3.0, "ffn": 2.0, "optimizer": 0.5,
                                 "logits_loss": 1.0, "embed": 0.25,
                                 "unscoped": 0.75 + 1.0})
    assert sum(got.values()) == pytest.approx(8.5)     # the busy union
    assert ps.scope_share(got, "attn") == pytest.approx(100 * 3.0 / 8.5)
    assert ps.scope_share(got, "embed", "logits_loss") \
        == pytest.approx(100 * 1.25 / 8.5)
    shares = [ps.scope_share(got, s) for s in ps.SCOPES + (ps.UNSCOPED,)]
    assert sum(shares) == pytest.approx(100.0)
    # Clipped to the window 0.5..3.5: attn 0.5 + 2, ffn 0.5.
    cut = ps.scope_seconds([ops], names, (0.5, 3.5))
    assert cut["attn"] == pytest.approx(2.5)
    assert cut["ffn"] == pytest.approx(0.5)
    assert sum(cut.values()) == pytest.approx(3.0)
    # Two devices: the mean.
    two = ps.scope_seconds([ops, [("%f.1", 0.0, 2.0)]], names, (0.0, 10.0))
    assert two["attn"] == pytest.approx((3.0 + 2.0) / 2)


def test_scope_share_is_none_where_no_op_carries_a_scope():
    ops = [("%fusion.1", 0.0, 1.0), ("%copy.2", 2.0, 1.0)]
    got = ps.scope_seconds([ops], {}, (0.0, 10.0))
    assert got["unscoped"] == pytest.approx(2.0)
    assert ps.scope_share(got, "attn") is None         # the parent of PR 27
    assert ps.scope_share(None, "attn") is None        # no device plane
    # A scope with no op of its own reads 0.0 beside ones that have.
    some = ps.scope_seconds([ops], {"%fusion.1": "jit(f)/jvp(ffn)/mul"},
                            (0.0, 10.0))
    assert ps.scope_share(some, "optimizer") == 0.0
    assert ps.scope_share(some, "ffn") == pytest.approx(50.0)


def test_idle_time_by_innermost_program_span():
    # Device busy 1..2 and 6..7 of the window 0..10: idle 0..1, 2..6,
    # 7..10. Thread A: leader 0..8 with host_read 0.5..5; thread B:
    # release_wait 0..9.
    threads = [[("bench.window", 0.0, 10.0, {}),
                ("xla.coll.leader", 0.0, 8.0, AR),
                ("xla.coll.host_read", 0.5, 4.5, AR)],
               [("xla.coll.release_wait", 0.0, 9.0, AR)]]
    ops = [("%f.1", 1.0, 1.0), ("%f.2", 6.0, 1.0)]
    idle = ps.idle_by_span(threads, [ops], (0.0, 10.0))
    assert idle == pytest.approx({
        "xla.coll.host_read": 0.5 + 3.0,               # 0.5..1, 2..5
        "xla.coll.leader": 0.5 + 1.0 + 1.0,            # 0..0.5, 5..6, 7..8
        "xla.coll.release_wait": 1.0 + 4.0 + 2.0})     # side by side: overlaps


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_op_names_reads_tf_op_from_the_event_metadata_of_tpu_planes():
    """A hand-encoded XSpace: one TPU plane whose stat 7 is ``tf_op``, two
    ops (one by ``str_value``, one by ``ref_value`` to a stat's name), and
    a host plane that must be passed over."""
    def stat_meta(i, name):
        return _field(5, _field(1, i) + _field(2, _field(1, i)
                                               + _field(2, name)))

    def event_meta(i, name, *stats):
        body = _field(1, i) + _field(2, name) + b"".join(
            _field(5, s) for s in stats)
        return _field(4, _field(1, i) + _field(2, body))

    tpu = (_field(1, 3) + _field(2, b"/device:TPU:0")
           + stat_meta(7, b"tf_op") + stat_meta(8, b"flops")
           + stat_meta(9, b"jit(step)/optimizer/add:")
           + event_meta(1, b"%fusion.1 = f32[8] fusion()",
                        _field(1, 8) + _field(3, 123),
                        _field(1, 7) + _field(5, b"jit(step)/jvp(attn)/mul:"))
           + event_meta(2, b"%fusion.2 = f32[8] fusion()",
                        _field(1, 7) + _field(7, 9))
           + event_meta(3, b"%copy.3 = f32[8] copy()"))
    host = (_field(2, b"/host:CPU") + stat_meta(7, b"tf_op")
            + event_meta(1, b"np.asarray", _field(1, 7) + _field(5, b"x")))
    space = _field(1, host) + _field(1, tpu)
    assert ps.op_names(space) == {
        "%fusion.1 = f32[8] fusion()": "jit(step)/jvp(attn)/mul:",
        "%fusion.2 = f32[8] fusion()": "jit(step)/optimizer/add:"}
