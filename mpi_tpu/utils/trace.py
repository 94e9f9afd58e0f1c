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
    per peer, collective invocations), queryable for bench harnesses.

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


def clear() -> None:
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
