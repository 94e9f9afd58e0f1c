"""The set-up readers against hand arithmetic, on records written by hand
in the form ``mpi_tpu.utils.trace.compiles()`` gives them. Run by hand with
the benchmark's other checks:

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import setup_spans as ss  # noqa: E402


def rec(stage, fun, start_s, seconds, *, nth=1, cache=None, thread="a"):
    return {"stage": stage, "fun": fun, "nth": nth, "ts_us": start_s * 1e6,
            "dur_us": seconds * 1e6, "cache": cache, "thread": thread}


def hand_made():
    """Thread a: the step traced 0..2, lowered 2..3, compiled 3..9; traced
    again 20..21, lowered 21..22, compiled 22..27 (the recompile). Thread
    b: a loader's program compiled 4..11 (over a's compile by 5 s) and a
    small one traced 8..8.5 inside that; a's block gradient compiled 12..14
    with the cache off, and one more compile at 40, after a window that
    opens at 30."""
    return [
        rec("trace", "step", 0, 2), rec("lower", "jit(step)", 2, 1),
        rec("compile", "jit(step)", 3, 6, cache="miss"),
        rec("compile", "jit(place)", 4, 7, cache="hit", thread="b"),
        rec("trace", "small", 8, 0.5, thread="b"),
        rec("compile", "jit(f)", 12, 2, cache="off"),
        rec("trace", "step", 20, 1, nth=2),
        rec("lower", "jit(step)", 21, 1, nth=2),
        rec("compile", "jit(step)", 22, 5, nth=2, cache="miss"),
        rec("compile", "jit(late)", 40, 3, cache="miss"),
    ]


def test_union_counts_an_instant_once_over_threads_and_nesting():
    records = hand_made()
    # 3..11 (two threads overlapping), 12..14, 22..27, 40..43.
    assert ss.union_s(records, ("compile",)) == pytest.approx(8 + 2 + 5 + 3)
    # 0..3, 8..8.5, 20..22.
    assert ss.union_s(records, ("trace", "lower")) == pytest.approx(5.5)
    # A record inside another adds nothing; the sum would say 24.
    inside = records + [rec("compile", "jit(g)", 5, 1, thread="c")]
    assert ss.union_s(inside, ("compile",)) == pytest.approx(18)
    assert ss.union_s([], ("compile",)) == 0.0


def test_the_cut_keeps_what_began_before_the_window():
    kept = ss.before(hand_made(), 30e6)
    assert [r["fun"] for r in kept][-1] == "jit(step)" and len(kept) == 9
    assert ss.union_s(kept, ("compile",)) == pytest.approx(15)
    # One that began before and ended after is set-up's.
    assert len(ss.before(hand_made(), 41e6)) == 10
    assert len(ss.before(hand_made(), float("inf"))) == 10


def test_step_compiles_are_counted_by_name():
    assert ss.step_compiles(hand_made()) == 2
    assert ss.step_compiles(ss.before(hand_made(), 10e6)) == 1
    assert ss.step_compiles([rec("compile", "jit(f)", 0, 1),
                             rec("trace", "step", 0, 1),
                             rec("lower", "jit(step)", 0, 1)]) == 0


def test_hit_share_leaves_out_what_did_not_ask_the_cache():
    # hit 1 of (hit 1 + miss 3); the `off` compile is no request.
    assert ss.hit_share(hand_made()) == pytest.approx(25.0)
    assert ss.hit_share(ss.before(hand_made(), 10e6)) == pytest.approx(50.0)
    assert ss.hit_share([rec("compile", "jit(f)", 0, 1, cache="off"),
                         rec("trace", "f", 0, 1)]) is None
    assert ss.hit_share([]) is None


def test_the_table_is_largest_first_and_sums_the_rest():
    lines = ss.table_lines(hand_made(), rows=3)
    assert "10 stages" in lines[0] and "18.00 s" in lines[0]
    assert [line.split()[0] for line in lines[2:5]] \
        == ["jit(place)", "jit(step)", "jit(step)"]
    assert lines[2].split()[-1] == "hit"
    assert lines[-1].startswith("  7 smaller stages: 10.500 s")
    assert len(ss.table_lines([])) == 1


RUN = {"record": {"step_stamps": [30.0, 31.0]}}


def test_of_run_reads_the_programs_table_up_to_the_first_stamp(monkeypatch):
    from mpi_tpu.utils import trace

    monkeypatch.setattr(trace, "compiles", hand_made, raising=False)
    assert len(ss.of_run(RUN)) == 9
    # The collective kind records no stamp: every record of the process.
    assert len(ss.of_run({"record": {}})) == 10


@pytest.mark.parametrize("metric", ["setup_compile_s", "setup_trace_lower_s",
                                    "step_compiles",
                                    "compile_cache_hit_share"])
def test_readers_on_a_program_with_and_without_the_table(monkeypatch, metric,
                                                         capsys):
    import importlib.util

    from mpi_tpu.utils import trace

    path = Path(ss.__file__).parent / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location("reader_" + metric, path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    monkeypatch.setattr(trace, "compiles", hand_made, raising=False)
    assert reader.read(RUN) == pytest.approx(
        {"setup_compile_s": 15.0, "setup_trace_lower_s": 5.5,
         "step_compiles": 2, "compile_cache_hit_share": 100 / 3}[metric])
    printed = capsys.readouterr().out
    assert ("setup_spans: 9 stages" in printed) \
        == (metric == "setup_compile_s")

    monkeypatch.delattr(trace, "compiles")      # the parent's program
    assert ss.of_run(RUN) is None
    assert reader.read(RUN) is None
    assert capsys.readouterr().out == ""
