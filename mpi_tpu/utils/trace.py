"""Tracing — spans (into a buffer and into any open jax.profiler trace) and
comm counters.

The reference has no tracing subsystem at all — its only instrument is the
bounce example's manual ``time.Now()`` deltas (SURVEY.md §5; bounce.go:
90-101). This module supplies the idiomatic tpu equivalents:

  * **spans** — wall-clock regions (``with span("allreduce", bytes=n)``)
    with two sinks. (1) A bounded process-local buffer (events beyond the
    cap are dropped and counted — see :func:`dropped`), exportable as a
    chrome://tracing / Perfetto JSON trace (``dump_chrome_trace``) and
    gathered job-wide by :mod:`mpi_tpu.observe`: on when
    ``MPI_TPU_TRACE=1`` or after :func:`enable`. (2) Whatever
    ``jax.profiler`` trace is open: every span is also a
    ``jax.profiler.TraceAnnotation`` with the same name and attributes,
    so inside ``with jax.profiler.trace(dir):`` (or the benchmark's
    profiler context) the program's stages lie on ``/host:CPU`` on the
    clock of the runtime's events and the device's ops. That sink needs
    no flag; it exists once jax is imported (this module never imports
    jax — the socket drivers run without it);
  * **counters** — monotonically accumulated values (bytes sent/received
    per peer, collective invocations), queryable for bench harnesses;
  * **compiles** — once :func:`listen_compiles` has run (the entry
    points that build jitted programs call it), every trace, lowering and
    backend compile of a jitted function is a span ``jax.trace`` /
    ``jax.lower`` / ``jax.compile`` with ``fun`` and ``nth``, and a row
    of a table kept whether or not recording is on: :func:`compiles`,
    :func:`compile_table`.

With no profiler session and recording off a span allocates no event and
costs well under a microsecond. :func:`add_span` records a span that has
already ended, which the profiler's annotation cannot express: it reaches
the buffer only. The facade (:mod:`mpi_tpu.api`) instruments
send/receive/collectives through this module, so any backend gets comm
accounting for free.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "enable",
    "disable",
    "enabled",
    "span",
    "count",
    "counters",
    "events",
    "dropped",
    "clear",
    "dump_chrome_trace",
    "wall_anchor_ns",
    "add_span",
    "set_stream",
    "stream",
    "flush_stream",
    "listen_compiles",
    "compiles",
    "compile_table",
    "compiles_dropped",
]

_MAX_EVENTS = 100_000


class _Tracer:
    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.events: List[Dict[str, Any]] = []
        self.counters: Dict[str, float] = {}
        self.enabled = bool(os.environ.get("MPI_TPU_TRACE"))
        self.dropped = 0
        # Span timestamps are perf_counter_ns (monotonic, arbitrary
        # origin). This anchor maps them onto the wall clock —
        # wall_ns ≈ ts_ns + anchor — which is what the job-wide merge
        # (mpi_tpu.observe.collect) aligns across ranks.
        self.wall_anchor_ns = time.time_ns() - time.perf_counter_ns()
        # Optional streaming sink (mpi_tpu.observe.stream.SpoolWriter).
        # When set, the resident buffer is bounded by the sink's chunk
        # watermarks instead of _MAX_EVENTS: full batches are detached
        # and handed to the sink, keeping memory O(chunk) over any job
        # length and making flushed spans crash-durable.
        self.stream: Optional[Any] = None

    def add_event(self, ev: Dict[str, Any]) -> None:
        with self.lock:
            st = self.stream
            if st is None:
                if len(self.events) >= _MAX_EVENTS:
                    self.dropped += 1
                    return
                self.events.append(ev)
                return
            self.events.append(ev)
            now = time.monotonic()
            if st.first_t is None:
                st.first_t = now
            if (len(self.events) >= st.max_events
                    or now - st.first_t >= st.max_age_s):
                batch = self.events
                self.events = []
                st.write_chunk(batch)

    def add_count(self, name: str, value: float) -> None:
        with self.lock:
            self.counters[name] = self.counters.get(name, 0) + value


_tracer = _Tracer()


def enable() -> None:
    """Turn span/counter recording on for this process."""
    _tracer.enabled = True


def disable() -> None:
    _tracer.enabled = False


def enabled() -> bool:
    return _tracer.enabled


_annotation: Any = None  # jax.profiler.TraceAnnotation, once jax is imported


def _profiling() -> bool:
    """Whether a profiler session is collecting host events right now.
    False without importing anything while jax is not in the process."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
        if _annotation is None:
            return False
    return _annotation.is_enabled()


class _NoSpan:
    """The span of a process that neither records nor profiles."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc: Any) -> bool:
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("name", "attrs", "annotation", "t0")

    def __init__(self, name: str, attrs: Dict[str, Any], profiling: bool):
        self.name, self.attrs = name, attrs
        self.annotation = _annotation(name, **attrs) if profiling else None
        self.t0 = 0

    def __enter__(self) -> None:
        if self.annotation is not None:
            self.annotation.__enter__()
        if _tracer.enabled:
            self.t0 = time.perf_counter_ns()

    def __exit__(self, *exc: Any) -> bool:
        if self.t0:
            t1 = time.perf_counter_ns()
            _tracer.add_event({
                "name": self.name,
                "ts_us": self.t0 / 1e3,
                "dur_us": (t1 - self.t0) / 1e3,
                "thread": threading.current_thread().name,
                **self.attrs,
            })
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        return False


def span(name: str, **attrs: Any):
    """A wall-clock region, as a context manager: a host event of the
    open ``jax.profiler`` trace, if there is one, and an event of the
    buffer, if recording is on. Two flag checks when neither is."""
    profiling = _profiling()
    if not profiling and not _tracer.enabled:
        return _NO_SPAN
    return _Span(name, attrs, profiling)


def add_span(name: str, ts_us: float, dur_us: float, **attrs: Any) -> None:
    """Record a completed span with explicit perf_counter timestamps
    (µs). For sub-op stages measured outside Python's control flow —
    e.g. the native wirecore stage scratch read back after the call —
    where a ``with span(...)`` block cannot bracket the work."""
    if not _tracer.enabled:
        return
    _tracer.add_event({
        "name": name,
        "ts_us": ts_us,
        "dur_us": dur_us,
        "thread": threading.current_thread().name,
        **attrs,
    })


def set_stream(writer: Optional[Any]) -> None:
    """Install (or remove, with None) a streaming sink — an object with
    ``max_events`` / ``max_age_s`` / ``first_t`` attributes and a
    ``write_chunk(events)`` method (see
    :class:`mpi_tpu.observe.stream.SpoolWriter`). While installed, full
    event batches are flushed to it instead of accumulating."""
    with _tracer.lock:
        _tracer.stream = writer


def stream() -> Optional[Any]:
    """The installed streaming sink, or None."""
    return _tracer.stream


def flush_stream() -> int:
    """Force the resident tail out to the streaming sink (finalize /
    fatal-error path). Returns the number of events flushed; no-op
    without a sink."""
    with _tracer.lock:
        st = _tracer.stream
        if st is None:
            return 0
        batch = _tracer.events
        _tracer.events = []
        st.write_chunk(batch)
        return len(batch)


def count(name: str, value: float = 1) -> None:
    """Accumulate a counter (e.g. ``comm.send.bytes``). No-op when
    disabled."""
    if _tracer.enabled:
        _tracer.add_count(name, value)


def counters() -> Dict[str, float]:
    with _tracer.lock:
        return dict(_tracer.counters)


def events() -> List[Dict[str, Any]]:
    with _tracer.lock:
        return list(_tracer.events)


def dropped() -> int:
    """Events discarded because the buffer cap was hit."""
    with _tracer.lock:
        return _tracer.dropped


def wall_anchor_ns() -> int:
    """This process's perf_counter→wall-clock anchor: add it to a
    span's ``ts_us * 1e3`` to place the span on the wall clock (the
    cross-rank merge substrate; see :mod:`mpi_tpu.observe.collect`)."""
    return _tracer.wall_anchor_ns


# -- compiles ---------------------------------------------------------------
#
# jax reports each stage of a jitted program through ``jax.monitoring``:
# a scalar event as the stage begins and a duration event as it ends, both
# on the compiling thread and both with the function's name; inside a
# backend compile, whether the request went to the persistent cache and
# whether the cache served it (jax/_src/dispatch.py, compiler.py).

_MAX_COMPILES = 4096

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_COUNT_OF = {"trace": "traces", "lower": "lowerings", "compile": "compiles"}
_CACHE_SAID = {     # asked is a miss until the cache says otherwise
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"


def _bare(fun: str) -> str:
    """``jit(step)`` -> ``step``: jax names a function bare as it traces
    it and wrapped as it lowers and compiles it; the table has one row."""
    if fun.endswith(")") and "(" in fun:
        return fun[fun.index("(") + 1:-1]
    return fun


class _Stage:
    """One open stage of one thread, from its begin event to its end. A
    trace nested in another stage has no ``span``: it is counted only."""

    __slots__ = ("stage", "row", "fun", "nth", "span", "t0", "cache",
                 "saved_s")

    def __init__(self, stage: str, row: Dict[str, Any], fun: str = "",
                 nth: int = 0, span: Any = None):
        self.stage, self.row, self.fun, self.nth = stage, row, fun, nth
        self.span = span
        self.cache = "off" if stage == "compile" else None
        self.saved_s: Optional[float] = None
        self.t0 = 0


def _new_row() -> Dict[str, Any]:
    return {"traces": 0, "nested_traces": 0, "lowerings": 0, "compiles": 0,
            "cache_hits": 0, "trace_s": 0.0, "lower_s": 0.0,
            "compile_s": 0.0, "saved_s": 0.0}


class _Compiles:
    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.listening = False
        self.records: List[Dict[str, Any]] = []
        self.dropped = 0
        self.by_fun: Dict[str, Dict[str, Any]] = {}
        self.open = threading.local()   # .stack: this thread's open stages

    def stack(self) -> List[_Stage]:
        try:
            return self.open.stack
        except AttributeError:
            self.open.stack = []
            return self.open.stack

    def begin(self, stage: str, fun: str) -> None:
        stack = self.stack()
        if stage == "trace" and stack:
            # A jitted function called while another is traced, or traced
            # by a lowering rule (thousands a model): counted on the
            # function whose stage is open, not listed.
            row = stack[-1].row
            with self.lock:
                row["nested_traces"] += 1
            stack.append(_Stage(stage, row))
            return
        with self.lock:
            row = self.by_fun.get(_bare(fun))
            if row is None:
                row = self.by_fun[_bare(fun)] = _new_row()
            row[_COUNT_OF[stage]] += 1
            nth = row[_COUNT_OF[stage]]
        st = _Stage(stage, row, fun, nth,
                    span("jax." + stage, fun=fun, nth=nth))
        stack.append(st)
        st.span.__enter__()
        st.t0 = time.perf_counter_ns()

    def end(self, stage: str) -> None:
        t1 = time.perf_counter_ns()
        stack = self.stack()
        if not any(st.stage == stage for st in stack):
            return              # began before the listeners were there
        while True:
            st = stack.pop()
            if st.stage == stage:
                break
            if st.span is not None:     # jax left it without an end event
                st.span.__exit__(None, None, None)
        if st.span is None:
            return
        rec = {"stage": stage, "fun": st.fun, "nth": st.nth,
               "ts_us": st.t0 / 1e3, "dur_us": (t1 - st.t0) / 1e3,
               "cache": st.cache,
               "thread": threading.current_thread().name}
        if st.saved_s is not None:
            rec["saved_s"] = st.saved_s
        if st.cache is not None and st.span is not _NO_SPAN:
            # Known only now: the buffer's event gets them; the profiler's,
            # whose stats are fixed as it begins, has ``fun`` and ``nth``.
            st.span.attrs["cache"] = st.cache
            if st.saved_s is not None:
                st.span.attrs["saved_s"] = st.saved_s
        st.span.__exit__(None, None, None)
        with self.lock:
            st.row[stage + "_s"] += (t1 - st.t0) / 1e9
            if st.cache == "hit":
                st.row["cache_hits"] += 1
                st.row["saved_s"] += st.saved_s or 0.0
            if len(self.records) >= _MAX_COMPILES:
                self.dropped += 1
            else:
                self.records.append(rec)

    def compiling(self) -> Optional[_Stage]:
        """The backend compile open on this thread: jax's cache events
        carry no name and belong to it."""
        stack = self.stack()
        return stack[-1] if stack and stack[-1].stage == "compile" else None


_compiles = _Compiles()


def _on_scalar(event: str, value: Any, fun_name: str = "", **kw: Any) -> None:
    stage = _STAGES.get(event)
    if stage is not None:
        _compiles.begin(stage, fun_name)


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    stage = _STAGES.get(event)
    if stage is not None:
        _compiles.end(stage)
    elif event == _CACHE_SAVED:
        st = _compiles.compiling()
        if st is not None:
            st.saved_s = duration


def _on_event(event: str, **kw: Any) -> None:
    said = _CACHE_SAID.get(event)
    st = _compiles.compiling() if said is not None else None
    if st is not None:
        st.cache = said


def listen_compiles() -> bool:
    """Start listening to jax's compile events, once a process however
    often it is called; ``False``, and nothing done, while jax is not in
    the process. Called by whatever builds jitted programs
    (``make_train_parts``, the xla driver's collectives, the loader), so
    that the table holds every stage from the first program on."""
    if _compiles.listening:
        return True
    monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
    if monitoring is None:
        return False
    with _compiles.lock:
        if not _compiles.listening:
            monitoring.register_scalar_listener(_on_scalar)
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _compiles.listening = True
    return True


def compiles() -> List[Dict[str, Any]]:
    """Every stage a jitted function went through since
    :func:`listen_compiles`, in the order they ended, recording on or
    off: ``{stage, fun, nth, ts_us, dur_us, cache, thread}`` with
    ``stage`` ``trace`` | ``lower`` | ``compile``, ``fun`` as jax names
    it (``step`` traced, ``jit(step)`` lowered and compiled), ``nth`` the
    times this function has reached this stage in the process (``nth=2``
    on a ``compile`` is the recompile), ``ts_us`` / ``dur_us`` on the
    spans' ``perf_counter`` timeline (:func:`wall_anchor_ns` maps it to
    jax's own wall-clock stamps), and on a ``compile`` ``cache``
    ``hit`` | ``miss`` | ``off`` (``off``: the request did not go to the
    persistent cache) with ``saved_s`` on a hit. A trace that begins
    inside another stage of its thread is not listed (``nested_traces``
    of :func:`compile_table`). Bounded: see :func:`compiles_dropped`."""
    with _compiles.lock:
        return [dict(r) for r in _compiles.records]


def compile_table() -> Dict[str, Dict[str, Any]]:
    """The same by function (``jit(step)`` and ``step`` are one row,
    ``step``): ``traces``, ``nested_traces`` (jitted functions traced
    inside its own traces and lowerings), ``lowerings``, ``compiles``
    (each the last ``nth`` of its stage), ``cache_hits``, and the seconds
    ``trace_s``, ``lower_s``, ``compile_s``, ``saved_s``. Never dropped
    from."""
    with _compiles.lock:
        return {fun: dict(row) for fun, row in _compiles.by_fun.items()}


def compiles_dropped() -> int:
    """Stages left out of :func:`compiles` because its bound was hit."""
    with _compiles.lock:
        return _compiles.dropped


def clear() -> None:
    """Empty the span buffer and the counters. The compile table stays:
    it is the process's history, as the programs it lists stay compiled."""
    with _tracer.lock:
        _tracer.events.clear()
        _tracer.counters.clear()
        _tracer.dropped = 0
        if _tracer.stream is not None:
            _tracer.stream.first_t = None


def dump_chrome_trace(path: str) -> int:
    """Write recorded spans as a chrome://tracing / Perfetto JSON file.
    Returns the number of events written."""
    with _tracer.lock:
        evs = list(_tracer.events)
        cts = dict(_tracer.counters)
        ndropped = _tracer.dropped
    trace = {
        "traceEvents": [
            {
                "name": e["name"],
                "ph": "X",
                "ts": e["ts_us"],
                "dur": e["dur_us"],
                "pid": os.getpid(),
                "tid": e.get("thread", "main"),
                "args": {k: v for k, v in e.items()
                         if k not in ("name", "ts_us", "dur_us", "thread")},
            }
            for e in evs
        ],
        "metadata": {"counters": cts, "dropped_events": ndropped},
    }
    with open(path, "w") as f:
        json.dump(trace, f)
    return len(evs)
