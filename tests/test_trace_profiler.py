"""The program's spans on the profiler's clock (ISSUE 27).

``trace.span`` has two sinks: the process-local buffer (``enable()`` /
``MPI_TPU_TRACE``) and, with no flag at all, whatever ``jax.profiler``
trace is open. These cases read an xplane file back and look for the
driver's collective stages, the loader's spans and the facade's ``mpi.*``
spans in it; check the buffer path and the cost of the idle path; and
check that layer scopes and kernel names reach the lowered programs.

The profiler is process-wide, so everything that opens it lives in this
one file (xdist's ``--dist loadfile`` keeps a file in one worker) and the
trace is taken once, in a module-scoped fixture.
"""

import functools
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi_tpu
from mpi_tpu import api
from mpi_tpu.backends.xla import XlaNetwork, run_spmd
from mpi_tpu.utils import trace

RANKS = 4
ROUNDS = 2
STAGES = ("host_read", "device_put", "launch", "read_back")


def _rank_program():
    """allreduce and bcast of array payloads, twice each, on every rank."""
    mpi_tpu.init()
    try:
        rank = mpi_tpu.rank()
        x = np.arange(8, dtype=np.float32) + rank
        for _ in range(ROUNDS):
            total = mpi_tpu.allreduce(x)
            got = mpi_tpu.bcast(x if rank == 1 else None, root=1)
        return float(total[0]), float(got[0])
    finally:
        mpi_tpu.finalize()


def _run_ranks():
    api._reset_for_testing()
    try:
        out = run_spmd(_rank_program, net=XlaNetwork(n=RANKS))
    finally:
        api._reset_for_testing()
    assert out == [(float(sum(range(RANKS))), 1.0)] * RANKS
    return out


def _host_spans(trace_dir):
    """``[(name, start_ns, end_ns, attrs, line index)]`` of ``/host:CPU``."""
    from jax.profiler import ProfileData

    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert files, f"no xplane file under {trace_dir}"
    spans = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("mpi.", "xla.", "data.")):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  {k: str(v) for k, v in e.stats}, i))
    return spans


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profiler session round four rank threads and a loader, with
    recording into the buffer OFF; also what the buffer held after it."""
    from mpi_tpu.data import ShardedLoader, SyntheticLM

    was = trace.enabled()
    trace.disable()
    trace.clear()
    trace_dir = tmp_path_factory.mktemp("xplane")
    jax.profiler.start_trace(str(trace_dir))
    try:
        _run_ranks()
        loader = iter(ShardedLoader(SyntheticLM(64, 2, 9, seed=0)))
        batches = [next(loader) for _ in range(3)]
        loader.close()
    finally:
        jax.profiler.stop_trace()
    buffered = trace.events()
    if was:
        trace.enable()
    assert batches[0].shape == (2, 9)
    return {"spans": _host_spans(trace_dir), "buffered": buffered}


def _named(spans, name, **attrs):
    return [s for s in spans if s[0] == name
            and all(s[3].get(k) == v for k, v in attrs.items())]


# -- (a) an open profiler trace holds the program's spans, with no flag ----

@pytest.mark.parametrize("op", ["allreduce", "bcast"])
def test_profiler_holds_facade_span_of_every_rank(profiled, op):
    assert len(_named(profiled["spans"], f"mpi.{op}")) == RANKS * ROUNDS


@pytest.mark.parametrize("op", ["allreduce", "bcast"])
def test_profiler_holds_one_leader_span_a_collective(profiled, op):
    leaders = _named(profiled["spans"], "xla.coll.leader", op=op)
    assert len(leaders) == ROUNDS
    # The leader is one rank thread: its span lies inside that thread's
    # facade span of the same collective.
    for _, start, end, _, line in leaders:
        assert any(s <= start and end <= e and ln == line
                   for _, s, e, _, ln in _named(profiled["spans"],
                                                f"mpi.{op}"))


@pytest.mark.parametrize("op", ["allreduce", "bcast"])
@pytest.mark.parametrize("stage", STAGES)
def test_profiler_holds_stage_inside_a_leader(profiled, op, stage):
    leaders = _named(profiled["spans"], "xla.coll.leader", op=op)
    found = _named(profiled["spans"], f"xla.coll.{stage}", op=op)
    # bcast reads one payload (the root's); allreduce reads all at once.
    assert len(found) == ROUNDS
    for _, start, end, attrs, line in found:
        assert any(s <= start and end <= e and ln == line
                   for _, s, e, _, ln in leaders), (stage, op)
        assert int(attrs["bytes"]) > 0


def _waits_inside(profiled, op, leads):
    """How many ``release_wait`` spans lie inside each facade span of
    ``op``: of the calls that held the leader (``leads``) or the rest."""
    leaders = _named(profiled["spans"], "xla.coll.leader", op=op)
    waits = _named(profiled["spans"], "xla.coll.release_wait", op=op)
    counts = []
    for _, start, end, _, line in _named(profiled["spans"], f"mpi.{op}"):
        def inside(span):
            return start <= span[1] and span[2] <= end and span[4] == line
        if any(map(inside, leaders)) == leads:
            counts.append(sum(map(inside, waits)))
    return counts


@pytest.mark.parametrize("op", ["allreduce", "bcast"])
def test_profiler_holds_one_wait_a_call_of_every_sleeping_rank(profiled, op):
    assert _waits_inside(profiled, op, leads=False) \
        == [1] * ((RANKS - 1) * ROUNDS)
    assert _named(profiled["spans"], "xla.coll.arrive_wait") == []


@pytest.mark.parametrize("op", ["allreduce", "bcast"])
def test_profiler_holds_no_wait_of_the_leader(profiled, op):
    assert _waits_inside(profiled, op, leads=True) == [0] * ROUNDS
    assert len(_named(profiled["spans"], "xla.coll.release_wait", op=op)) \
        == (RANKS - 1) * ROUNDS


def test_profiler_alone_records_nothing_into_the_buffer(profiled):
    assert profiled["buffered"] == []


# -- (d) the loader's spans -------------------------------------------------

@pytest.mark.parametrize("name", ["data.batch", "data.source",
                                  "data.device_put", "data.wait"])
def test_loader_spans_reach_the_profiler(profiled, name):
    assert len(_named(profiled["spans"], name)) >= 3


def test_loader_children_lie_inside_their_batch(profiled):
    # From the batch down: the producer runs ahead, so the trace may end
    # with a child recorded and its batch still open.
    batches = _named(profiled["spans"], "data.batch")
    for child in ("data.source", "data.device_put"):
        inner = _named(profiled["spans"], child)
        for _, start, end, attrs, line in batches:
            assert any(start <= s and e <= end and ln == line
                       and a["step"] == attrs["step"]
                       for _, s, e, a, ln in inner), (child, attrs)


# -- (b) enable() and no profiler: the same spans, in the buffer ------------

def test_enabled_buffer_holds_the_same_spans_nested_by_time():
    was = trace.enabled()
    trace.clear()
    trace.enable()
    try:
        _run_ranks()
        events = trace.events()
        sleeps = trace.counters().get("xla.coll.sleeps", 0)
    finally:
        if not was:
            trace.disable()
        trace.clear()
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["mpi.allreduce"]) == RANKS * ROUNDS
    # One wait a sleeping rank a call, none of the leader's, and the
    # counter counts the same sleeps.
    assert "xla.coll.arrive_wait" not in by_name
    for op in ("allreduce", "bcast"):
        assert len([e for e in by_name["xla.coll.release_wait"]
                    if e["op"] == op]) == (RANKS - 1) * ROUNDS
    assert sleeps == len(by_name["xla.coll.release_wait"]) \
        == 2 * (RANKS - 1) * ROUNDS
    leaders = [e for e in by_name["xla.coll.leader"]
               if e["op"] == "allreduce"]
    assert len(leaders) == ROUNDS
    for stage in STAGES:
        inner = [e for e in by_name[f"xla.coll.{stage}"]
                 if e["op"] == "allreduce"]
        assert len(inner) == ROUNDS
        for e in inner:
            assert any(ld["thread"] == e["thread"]
                       and ld["ts_us"] <= e["ts_us"]
                       and e["ts_us"] + e["dur_us"]
                       <= ld["ts_us"] + ld["dur_us"] for ld in leaders)
            assert e["bytes"] > 0


# -- (c) neither: no event, and nearly free ---------------------------------

def test_idle_span_records_nothing_and_costs_under_ten_microseconds():
    was = trace.enabled()
    trace.disable()
    trace.clear()
    try:
        assert trace.span("x", op="y", bytes=1) is trace.span("z")
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("xla.coll.launch", op="allreduce", bytes=4):
                pass
        per_us = (time.perf_counter() - t0) / n * 1e6
        assert trace.events() == []
    finally:
        if was:
            trace.enable()
    # Measured 0.45 us; generous, as the smoke in test_observe.py is.
    assert per_us < 10.0, per_us


def test_trace_module_imports_without_jax():
    code = ("import sys; import mpi_tpu.utils.trace as t; "
            "assert 'jax' not in sys.modules, 'trace imported jax'; "
            "s = t.span('x', a=1); s.__enter__(); s.__exit__(None, None, "
            "None); assert 'jax' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# -- (f) profile() is gone ---------------------------------------------------

def test_profile_is_gone():
    assert "profile" not in trace.__all__
    assert not hasattr(trace, "profile")


# -- (e) scopes and kernel names in the lowered programs -------------------

@functools.lru_cache(maxsize=None)
def _tiny_step_hlo():
    """The lowered tiny train step's text, lowered once for all scopes."""
    from mpi_tpu.models import TransformerConfig, make_train_step

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=2, d_ff=64,
                            n_layers=2, max_seq=17, dtype=jnp.float32,
                            attention_impl="dense")
    init_state, step = make_train_step(cfg, mesh=None, learning_rate=1e-4)
    state = jax.eval_shape(init_state, jax.random.key_data(jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((2, 17), jnp.int32)
    return step.lower(state, tokens).as_text(debug_info=True)


@pytest.mark.parametrize("scope", ["embed", "attn", "ffn", "logits_loss",
                                   "optimizer"])
def test_train_step_hlo_names_the_layer_scopes(scope):
    text = _tiny_step_hlo()
    assert f"/{scope}/" in text or f"({scope})" in text, scope


def test_flash_kernels_are_named_in_the_jaxpr():
    from mpi_tpu.ops import flash_attention

    q = jnp.ones((1, 128, 2, 128), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    names = sorted(re.findall(r"\bname=(flash\w*)", text))
    assert names == ["flash_bwd", "flash_fwd"], names
