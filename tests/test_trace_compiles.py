"""Set-up's spans (ISSUE 38): every trace, lowering and backend compile of a
jitted function is a span of the program (``jax.trace`` / ``jax.lower`` /
``jax.compile``, with ``fun`` and ``nth``) and a row of a table that is kept
whether or not recording is on (``trace.compiles()``,
``trace.compile_table()``).

The table is the process's history and ``nth`` counts from the process's
start, so every case jits a function of its own name and reads the growth.
The profiler is process-wide: the one case that opens it closes it again.
"""

import json
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi_tpu
from mpi_tpu import api
from mpi_tpu.backends.xla import XlaNetwork, run_spmd
from mpi_tpu.utils import trace

STAGES = ("trace", "lower", "compile")


@pytest.fixture(autouse=True)
def listening(monkeypatch):
    """Listening, with room in the bounded list for what the case adds:
    the files run before this one in the same process may have filled it."""
    assert trace.listen_compiles() is True
    monkeypatch.setattr(trace, "_MAX_COMPILES",
                        len(trace.compiles()) + trace._MAX_COMPILES)


def _records(name):
    """The listed records of the function ``name``, by stage."""
    return {stage: [r for r in trace.compiles() if r["stage"] == stage
                    and r["fun"] in (name, f"jit({name})")]
            for stage in STAGES}


def _row(name):
    return trace.compile_table().get(name, {
        "traces": 0, "nested_traces": 0, "lowerings": 0, "compiles": 0,
        "cache_hits": 0})


# -- the listeners -------------------------------------------------------------

def test_listeners_are_registered_once_however_often_the_entry_points_run():
    from mpi_tpu.data import ShardedLoader, SyntheticLM
    from mpi_tpu.models import TransformerConfig
    from mpi_tpu.models.transformer import make_train_parts

    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, d_ff=32,
                            n_layers=1, max_seq=9, attention_impl="dense")
    for _ in range(3):
        assert trace.listen_compiles() is True
        make_train_parts(cfg)
        ShardedLoader(SyntheticLM(32, 2, 9, seed=0))
        XlaNetwork(n=2)

    @jax.jit
    def compiled_once(x):
        return x + 1

    compiled_once(jnp.ones(3))
    # A second set of listeners would list every stage twice.
    assert {k: len(v) for k, v in _records("compiled_once").items()} \
        == {"trace": 1, "lower": 1, "compile": 1}
    assert _row("compiled_once")["compiles"] == 1


def test_an_end_event_without_its_begin_is_ignored():
    before = len(trace.compiles())
    trace._on_duration("/jax/core/compile/backend_compile_duration", 0.5,
                       fun_name="jit(never_began)")
    trace._on_event("/jax/compilation_cache/cache_hits")
    assert len(trace.compiles()) == before
    assert "never_began" not in trace.compile_table()


# -- records: fun, nth, and what does not compile again ------------------------

def test_stages_of_a_jitted_function_and_nth_of_a_recompile():
    @jax.jit
    def tc_step(x):
        return x * 2 + jnp.sum(x)

    t0 = time.perf_counter_ns() / 1e3
    tc_step(jnp.ones(3))
    first = _records("tc_step")
    assert [r["fun"] for r in first["trace"]] == ["tc_step"]
    assert [r["fun"] for r in first["lower"]] == ["jit(tc_step)"]
    assert [r["fun"] for r in first["compile"]] == ["jit(tc_step)"]
    for stage in STAGES:
        r, = first[stage]
        assert r["nth"] == 1 and r["dur_us"] > 0
        assert t0 <= r["ts_us"] <= time.perf_counter_ns() / 1e3
        assert r["thread"] == threading.current_thread().name
    assert first["compile"][0]["cache"] in ("hit", "miss", "off")
    assert first["trace"][0]["cache"] is None

    tc_step(jnp.ones(3))                    # the same shape: nothing
    assert _records("tc_step") == first

    tc_step(jnp.ones(4))                    # a new shape: the recompile
    again = _records("tc_step")
    for stage in STAGES:
        assert [r["nth"] for r in again[stage]] == [1, 2]
    row = _row("tc_step")
    assert (row["traces"], row["lowerings"], row["compiles"]) == (2, 2, 2)
    assert row["compile_s"] == pytest.approx(
        sum(r["dur_us"] for r in again["compile"]) / 1e6)


def test_records_are_kept_with_recording_off_and_the_buffer_stays_empty():
    was = trace.enabled()
    trace.disable()
    trace.clear()
    try:
        @jax.jit
        def tc_quiet(x):
            return x - 1

        tc_quiet(jnp.ones(2))
        assert trace.events() == []
    finally:
        if was:
            trace.enable()
    assert {k: len(v) for k, v in _records("tc_quiet").items()} \
        == {"trace": 1, "lower": 1, "compile": 1}


def test_clear_leaves_the_compile_table():
    @jax.jit
    def tc_kept(x):
        return x + 2

    tc_kept(jnp.ones(2))
    trace.clear()
    assert len(_records("tc_kept")["compile"]) == 1
    assert _row("tc_kept")["compiles"] == 1


def test_recording_on_puts_the_three_spans_inside_a_bracketing_span():
    was = trace.enabled()
    trace.clear()
    trace.enable()
    try:
        @jax.jit
        def tc_loud(x):
            return x * 3

        with trace.span("tc.bracket"):
            tc_loud(jnp.ones(2))
        events = trace.events()
    finally:
        if not was:
            trace.disable()
        trace.clear()
    bracket, = [e for e in events if e["name"] == "tc.bracket"]
    for stage in STAGES:
        e, = [e for e in events if e["name"] == f"jax.{stage}"
              and e["fun"] in ("tc_loud", "jit(tc_loud)")]
        assert e["nth"] == 1 and e["thread"] == bracket["thread"]
        assert bracket["ts_us"] <= e["ts_us"]
        assert e["ts_us"] + e["dur_us"] <= bracket["ts_us"] + bracket["dur_us"]
        assert ("cache" in e) == (stage == "compile")
    # The table's record and the buffer's event are one interval.
    rec, = _records("tc_loud")["compile"]
    span, = [e for e in events if e["name"] == "jax.compile"
             and e["fun"] == "jit(tc_loud)"]
    assert span["cache"] == rec["cache"]
    assert abs(span["ts_us"] - rec["ts_us"]) < 1e3


def test_an_open_profiler_trace_holds_the_recompile_by_name(tmp_path):
    from jax.profiler import ProfileData

    @jax.jit
    def tc_profiled(x):
        return x + 5

    tc_profiled(jnp.ones(2))
    was = trace.enabled()
    trace.disable()
    trace.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("tc.bracket"):
            tc_profiled(jnp.ones(3))        # a new shape, inside the trace
    finally:
        jax.profiler.stop_trace()
        if was:
            trace.enable()
    assert trace.events() == []
    found = []
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("jax.", "tc.")):
                    found.append((e.name, {k: str(v) for k, v in e.stats}, i,
                                  e.start_ns, e.start_ns + e.duration_ns))
    (_, _, line, b0, b1), = [f for f in found if f[0] == "tc.bracket"]
    for stage, fun in (("trace", "tc_profiled"), ("lower", "jit(tc_profiled)"),
                       ("compile", "jit(tc_profiled)")):
        (_, stats, on, t0, t1), = [f for f in found if f[0] == f"jax.{stage}"
                                   and f[1].get("fun") == fun]
        assert stats["nth"] == "2"
        assert on == line and b0 <= t0 and t1 <= b1     # the compiling thread


def test_nested_traces_are_counted_not_listed():
    @jax.jit
    def tc_inner(x):
        return x * 2

    @jax.jit
    def tc_outer(x):
        return tc_inner(x) + tc_inner(x + 1)

    tc_outer(jnp.ones(3))
    assert len(_records("tc_outer")["trace"]) == 1
    assert _records("tc_inner") == {"trace": [], "lower": [], "compile": []}
    assert "tc_inner" not in trace.compile_table()
    # tc_inner once (the second call is served by jax's trace cache) and
    # the jnp functions inside both.
    assert _row("tc_outer")["nested_traces"] >= 1
    assert _row("tc_outer")["traces"] == 1


def test_the_bound_drops_and_counts(monkeypatch):
    listed, dropped = len(trace.compiles()), trace.compiles_dropped()
    monkeypatch.setattr(trace, "_MAX_COMPILES", listed + 2)

    @jax.jit
    def tc_bounded(x):
        return x - 3

    tc_bounded(jnp.ones(2))
    tc_bounded(jnp.ones(3))
    assert len(trace.compiles()) == listed + 2
    assert trace.compiles_dropped() == dropped + 4
    # The table by function is never dropped from.
    row = _row("tc_bounded")
    assert (row["traces"], row["lowerings"], row["compiles"]) == (2, 2, 2)


def test_threads_compiling_at_once_lose_no_count():
    """More threads than cores compile shapes of one function at once:
    every stage is listed once and ``nth`` runs 1..N with no gap."""
    @jax.jit
    def tc_raced(x):
        return x + 7

    n, failures = 12, []
    start = threading.Barrier(n)

    def work(i):
        try:
            start.wait(30)
            tc_raced(jnp.ones(i + 1))
        except Exception as e:  # noqa: BLE001 - reported below
            failures.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not failures and not any(t.is_alive() for t in threads)
    for stage, records in _records("tc_raced").items():
        assert sorted(r["nth"] for r in records) == list(range(1, n + 1))
    row = _row("tc_raced")
    assert (row["traces"], row["lowerings"], row["compiles"]) == (n, n, n)


# -- the persistent cache ------------------------------------------------------

_CACHE_PROGRAM = """
import json, sys
import jax, jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from mpi_tpu.utils import trace

jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
trace.listen_compiles()

def cached(x):
    return jnp.sin(x) * 2

def one():
    jax.clear_caches()
    jax.jit(cached)(jnp.ones(5))
    return [r for r in trace.compiles() if r["fun"] == "jit(cached)"
            and r["stage"] == "compile"][-1]

out = {"first": one(), "second": one()}
jax.config.update("jax_enable_compilation_cache", False)
compilation_cache.reset_cache()
out["disabled"] = one()
out["row"] = trace.compile_table()["cached"]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def cache_story(tmp_path_factory):
    """One process: a program compiled into an empty persistent cache,
    compiled again from it, and again with the cache turned off."""
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_PROGRAM,
         str(tmp_path_factory.mktemp("jax_cache"))],
        capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("which,cache,nth", [
    ("first", "miss", 1), ("second", "hit", 2), ("disabled", "off", 3)])
def test_a_compile_says_what_the_persistent_cache_did(cache_story, which,
                                                      cache, nth):
    rec = cache_story[which]
    assert (rec["cache"], rec["nth"]) == (cache, nth)
    assert ("saved_s" in rec) == (cache == "hit")


def test_the_table_counts_the_hits(cache_story):
    row = cache_story["row"]
    assert (row["compiles"], row["cache_hits"]) == (3, 1)
    assert row["saved_s"] == pytest.approx(cache_story["second"]["saved_s"])


# -- the programs the benchmark's kinds count by private state -----------------

def test_step_compiles_equal_the_growth_of_the_steps_program_cache():
    from mpi_tpu.models import TransformerConfig, make_train_step

    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, d_ff=32,
                            n_layers=1, max_seq=9, dtype=jnp.float32,
                            attention_impl="dense")
    init_state, step = make_train_step(cfg, mesh=None, learning_rate=1e-3)
    state = init_state(jax.random.key_data(jax.random.key(0)))
    before, programs = _row("step")["compiles"], step._cache_size()
    for batch in (2, 2, 3):
        state, loss = step(state, jnp.zeros((batch, 9), jnp.int32))
    jax.block_until_ready(loss)
    grown = step._cache_size() - programs
    assert grown >= 2
    assert _row("step")["compiles"] - before == grown
    assert [r["nth"] for r in _records("step")["compile"]][-grown:] \
        == list(range(before + 1, before + grown + 1))


def test_a_collective_of_two_shapes_is_two_compile_records():
    def program():
        mpi_tpu.init()
        try:
            rank = mpi_tpu.rank()
            for n in (5, 5, 11):
                total = mpi_tpu.allreduce(
                    np.arange(n, dtype=np.float32) + rank)
            return float(total[0])
        finally:
            mpi_tpu.finalize()

    before = {r["nth"] for r in _records("per_shard")["compile"]}
    api._reset_for_testing()
    try:
        out = run_spmd(program, net=XlaNetwork(n=4))
    finally:
        api._reset_for_testing()
    assert out == [6.0] * 4
    new = [r for r in _records("per_shard")["compile"]
           if r["nth"] not in before]
    assert len(new) == 2
    assert len({r["thread"] for r in new}) >= 1     # the leader's thread


# -- what it may not cost ------------------------------------------------------

def test_trace_module_imports_and_answers_with_jax_blocked():
    code = ("import sys; sys.modules['jax'] = None; "
            "import mpi_tpu.utils.trace as t; "
            "assert t.listen_compiles() is False; "
            "assert t.compiles() == [] and t.compile_table() == {}; "
            "assert t.compiles_dropped() == 0; "
            "s = t.span('x', a=1); s.__enter__(); "
            "s.__exit__(None, None, None)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_a_disabled_span_still_costs_under_ten_microseconds():
    was = trace.enabled()
    trace.disable()
    try:
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("xla.coll.launch", op="allreduce", bytes=4):
                pass
        per_us = (time.perf_counter() - t0) / n * 1e6
    finally:
        if was:
            trace.enable()
    assert per_us < 10.0, per_us
