#!/usr/bin/env python3
"""From a profiler trace to the program's own spans and layer scopes.

``mpi_tpu.utils.trace.span`` is also a ``jax.profiler.TraceAnnotation``
(PR 27), so a traced run's xplane file holds the program's stages on
``/host:CPU`` beside the runtime's events, one line a thread, with their
attributes (``op=allreduce``, ``bytes=...``) as the event's stats; and the
model's ``jax.named_scope``s (``attn``, ``ffn``, ``logits_loss``,
``embed``, ``optimizer``) come back as the ``tf_op`` stat of each device
op's metadata: ``jit(step)/transpose(jvp(attn))/dot_general``. (On a v5e,
read by hand, PR 27: the event's name is the instruction's text without
its ``metadata={...}``, its own stats are offsets and durations only, and
``jax.profiler.ProfileData`` does not show the stats of an event's
metadata, so :func:`op_names` reads those from the file's bytes.)

The reductions are arithmetic over ``(name, start, duration, attrs)``
spans and ``(name, start, duration)`` device events, checked by
``benchmark/tests/test_program_spans.py`` against hand arithmetic; only
:func:`load` touches a file. Spans are clipped to the ``bench.window``
span. A span's self time is its duration minus the part of it that its
child spans (the spans of the same thread that lie inside it) cover.

A trace of the parent of PR 27 holds none of these spans and scopes, and
a CPU rehearsal holds no device plane: every reader then gets ``None``.

Run as a script it prints, for the newest trace under ``.bench_trace``
(or the directory given), the stage table, idle device time by innermost
program span, and device time by scope with the largest ops:

    python3 benchmark/program_spans.py [trace_dir]
"""

from __future__ import annotations

import re
import statistics
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import trace_reduce

Span = Tuple[str, float, float, Dict[str, Any]]   # name, start, duration, attrs
Event = trace_reduce.Event

TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"
# What the program calls its spans (docs/OBSERVABILITY.md), and the
# benchmark its own: everything else on a host line is the runtime's.
PROGRAM_PREFIXES = ("mpi.", "xla.", "data.", "wire.", "hybrid.",
                    trace_reduce.SPAN_PREFIX)
SCOPES = ("attn", "ffn", "logits_loss", "embed", "optimizer")
UNSCOPED = "unscoped"
COPY_STAGES = ("xla.coll.host_read", "xla.coll.device_put",
               "xla.coll.read_back")
LEADER = "xla.coll.leader"


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

def window_of(threads: Sequence[Sequence[Span]]
              ) -> Optional[Tuple[float, float]]:
    for thread in threads:
        for name, start, dur, _ in thread:
            if name == trace_reduce.WINDOW_SPAN:
                return start, start + dur
    return None


def with_self_time(thread: Sequence[Span],
                   window: Optional[Tuple[float, float]] = None
                   ) -> List[Dict[str, Any]]:
    """The spans of ONE thread that touch the window (the window's own
    span is the clip, not a stage, and is left out), each as ``{name,
    start, duration (whole), attrs, total_s (inside the window), self_s
    (``total_s`` minus what its child spans cover inside the window)}``."""
    w0, w1 = window if window else (float("-inf"), float("inf"))
    out: List[Dict[str, Any]] = []
    stack: List[Dict[str, Any]] = []
    for name, start, dur, attrs in sorted(thread,
                                          key=lambda s: (s[1], -s[2])):
        if start + dur < w0 or start > w1 or name == trace_reduce.WINDOW_SPAN:
            continue
        inside = max(0.0, min(start + dur, w1) - max(start, w0))
        row = {"name": name, "start": start, "duration": dur,
               "end": start + dur, "attrs": attrs, "total_s": inside,
               "self_s": inside}
        while stack and stack[-1]["end"] <= start:
            stack.pop()
        if stack:
            stack[-1]["self_s"] -= row["total_s"]
        stack.append(row)
        out.append(row)
    return out


def rows_of(threads: Sequence[Sequence[Span]],
            window: Optional[Tuple[float, float]] = None
            ) -> List[Dict[str, Any]]:
    """:func:`with_self_time` over every thread, in order of start."""
    if window is None:
        window = window_of(threads)
    rows = [r for t in threads for r in with_self_time(t, window)]
    return sorted(rows, key=lambda r: r["start"])


def named(rows: Sequence[Dict[str, Any]], name: str,
          **attrs: Any) -> List[Dict[str, Any]]:
    """The rows called ``name`` whose attributes hold ``attrs`` (compared
    as text: the profiler keeps a number or a string as it likes)."""
    return [r for r in rows if r["name"] == name and all(
        str(r["attrs"].get(k)) == str(v) for k, v in attrs.items())]


def stage_table(rows: Sequence[Dict[str, Any]]) -> Dict[str, Dict[str, float]]:
    """``{span name (with its ``op=`` where it has one): {calls, total_s,
    self_s, median_s}}``; the median is of whole durations, the sums of
    what lies inside the window."""
    table: Dict[str, Dict[str, Any]] = {}
    for r in rows:
        key = r["name"] + (f" op={r['attrs']['op']}" if "op" in r["attrs"]
                           else "")
        slot = table.setdefault(key, {"calls": 0, "total_s": 0.0,
                                      "self_s": 0.0, "durations": []})
        slot["calls"] += 1
        slot["total_s"] += r["total_s"]
        slot["self_s"] += r["self_s"]
        slot["durations"].append(r["duration"])
    return {n: {"calls": s["calls"], "total_s": s["total_s"],
                "self_s": s["self_s"],
                "median_s": statistics.median(s["durations"])}
            for n, s in table.items()}


def host_copy_share(rows: Sequence[Dict[str, Any]],
                    call_s: Sequence[float], op: str) -> Optional[float]:
    """Percent of the calls' time spent in the driver's three copy stages
    of collective ``op``. ``None`` where the driver has no stage spans (no
    ``xla.coll.leader``); 0.0 where it has and copied nothing."""
    if not call_s or not named(rows, LEADER, op=op):
        return None
    copied = sum(r["total_s"] for stage in COPY_STAGES
                 for r in named(rows, stage, op=op))
    return 100.0 * copied / sum(call_s)


def sync_us(rows: Sequence[Dict[str, Any]], call_s: Sequence[float],
            op: str) -> Optional[float]:
    """Median, in microseconds, of a call's time minus the leader's span
    of the same call, the k-th call with the k-th span: what the rank
    waits for arrival and release and what the facade adds. ``None``
    unless there is one leader span a call."""
    leaders = named(rows, LEADER, op=op)
    if not call_s or len(leaders) != len(call_s):
        return None
    return 1e6 * statistics.median(
        t - r["duration"] for t, r in zip(call_s, leaders))


def traced_calls(run) -> Optional[Tuple[List[Dict[str, Any]], List[float],
                                        str]]:
    """For the readers of the collective cell: the traced run's rows,
    rank 0's call times at ``judged_large`` and the collective's name."""
    rec, got = run["record"], of_run()
    calls = rec.get("call_s", {}).get(str(rec.get("judged_large")))
    if got is None or not calls:
        return None
    return got["rows"], calls, run["config"]["collective"]


def median_ms(rows: Sequence[Dict[str, Any]], name: str) -> Optional[float]:
    durations = [r["duration"] for r in named(rows, name)]
    return 1e3 * statistics.median(durations) if durations else None


def idle_by_span(threads: Sequence[Sequence[Span]],
                 device_ops: Sequence[Sequence[Event]],
                 window: Tuple[float, float]) -> Dict[str, float]:
    """Seconds of idle device time (mean over devices) during which the
    innermost program span of some thread had that name. Threads run
    side by side, so the rows overlap and do not sum to the idle time."""
    w0, w1 = window
    doing: Dict[str, List[Tuple[float, float]]] = {}
    for thread in threads:
        for name, a, b in trace_reduce.leaf_segments(
                [(n, s, d) for n, s, d, _ in thread
                 if n != trace_reduce.WINDOW_SPAN]):
            doing.setdefault(name, []).append((a, b))
    out: Dict[str, float] = {}
    for ops in device_ops:
        busy = trace_reduce._union(
            (max(s, w0), min(s + d, w1)) for _, s, d in ops
            if min(s + d, w1) > max(s, w0))
        edges = [w0] + [t for ab in busy for t in ab] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for name, intervals in doing.items():
            seconds = trace_reduce._intersection(
                trace_reduce._union(intervals), idle)
            if seconds > 0:
                out[name] = out.get(name, 0.0) + seconds / len(device_ops)
    return out


# ---------------------------------------------------------------------------
# Device ops by scope
# ---------------------------------------------------------------------------

_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def scope_of(op_name: Optional[str]) -> str:
    """``jit(step)/transpose(jvp(attn))/dot_general`` -> ``attn``;
    ``jit(step)/optimizer/add`` -> ``optimizer``: the outermost path
    component that is one of :data:`SCOPES` once the transformations
    round it (``jvp(...)``, ``transpose(...)``, ``checkpoint``...) are
    taken off. Anything else is ``unscoped``."""
    for part in (op_name or "").split("/"):
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        if part in SCOPES:
            return part
    return UNSCOPED


def scope_seconds(device_ops: Sequence[Sequence[Event]],
                  names: Dict[str, str],
                  window: Optional[Tuple[float, float]] = None
                  ) -> Dict[str, float]:
    """Device seconds by scope, mean over devices: every busy instant
    inside the window goes to the scope of the innermost op running, so
    the scopes and ``unscoped`` sum to the busy time. ``names`` maps an
    event's name to its ``tf_op``."""
    w0, w1 = window if window else (float("-inf"), float("inf"))
    out = {scope: 0.0 for scope in SCOPES + (UNSCOPED,)}
    for ops in device_ops:
        for name, a, b in trace_reduce.leaf_segments(ops):
            seconds = min(b, w1) - max(a, w0)
            if seconds > 0:
                out[scope_of(names.get(name))] += seconds / len(device_ops)
    return out


def scope_share(shares: Optional[Dict[str, float]],
                *scopes: str) -> Optional[float]:
    """Percent of busy device time in ``scopes``; ``None`` where no op of
    the trace carries any scope (the program has none)."""
    if not shares:
        return None
    busy = sum(shares.values())
    if busy <= 0 or shares[UNSCOPED] >= busy:
        return None
    return 100.0 * sum(shares[s] for s in scopes) / busy


def run_scope_share(*scopes: str) -> Optional[float]:
    """:func:`scope_share` of the run's trace: what the four readers of
    the train cell's scope shares return."""
    got = of_run()
    return None if got is None else scope_share(got["scopes"], *scopes)


# ---------------------------------------------------------------------------
# The loader
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: varints
    as ints, length-delimited fields as bytes, fixed ones skipped over."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"xplane: wire type {wire}")
        yield field, wire, value


def _map_value(entry: bytes) -> bytes:
    return next((v for f, w, v in _fields(entry) if f == 2 and w == 2), b"")


def op_names(xplane_bytes: bytes) -> Dict[str, str]:
    """``{device event name: tf_op}`` over the TPU planes of an XSpace
    (xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata =
    4, .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.id = 1, .name = 2)."""
    out: Dict[str, str] = {}
    for field, wire, plane in _fields(xplane_bytes):
        if field != 1 or wire != 2:
            continue
        parts = list(_fields(plane))
        name = next((v for f, w, v in parts if f == 2 and w == 2), b"")
        if not trace_reduce.DEVICE_PLANE.match(name.decode("utf-8", "replace")):
            continue
        stat_names: Dict[int, str] = {}
        for f, w, v in parts:
            if f == 5 and w == 2:
                meta = dict((ff, vv) for ff, _, vv in _fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = (meta.get(2) or b"").decode()
        tf_op = next((i for i, n in stat_names.items() if n == "tf_op"), None)
        if tf_op is None:
            continue
        for f, w, v in parts:
            if f != 4 or w != 2:
                continue
            event_name, found = "", None
            for ff, ww, vv in _fields(_map_value(v)):
                if ff == 2 and ww == 2:
                    event_name = vv.decode("utf-8", "replace")
                elif ff == 5 and ww == 2:
                    stat = dict((a, c) for a, _, c in _fields(vv))
                    if stat.get(1) == tf_op:
                        found = (stat[5].decode("utf-8", "replace")
                                 if 5 in stat else stat_names.get(stat.get(7)))
            if event_name and found:
                out[event_name] = found
    return out


def load(xplane_path) -> Dict[str, Any]:
    """``{threads: the program's and the benchmark's spans, one list a
    host thread; device_ops: op events, one list a TPU plane (as
    ``trace_reduce.load``); op_names: event name -> tf_op}``."""
    from jax.profiler import ProfileData

    raw = Path(xplane_path).read_bytes()
    threads: List[List[Span]] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                      dict(e.stats)) for e in line.events
                     if e.name.startswith(PROGRAM_PREFIXES)]
            if spans:
                threads.append(spans)
    device_ops, _, _ = trace_reduce.load(xplane_path)
    return {"threads": threads, "device_ops": device_ops,
            "op_names": op_names(raw) if device_ops else {}}


_loaded: Dict[Tuple[str, int], Dict[str, Any]] = {}


def of_run(trace_dir=TRACE_DIR) -> Optional[Dict[str, Any]]:
    """What the metric readers read: the newest trace under ``trace_dir``
    loaded and reduced once a process (``rows``, ``window``, and
    ``scopes``: device seconds by scope, ``None`` without a device
    plane). ``None`` where there is no trace or no window span."""
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _loaded:
        _loaded.clear()
        got = load(path)
        window = window_of(got["threads"])
        got["window"] = window
        got["rows"] = rows_of(got["threads"], window) if window else []
        got["scopes"] = (scope_seconds(got["device_ops"], got["op_names"],
                                       window)
                         if got["device_ops"] and window else None)
        _loaded[key] = got
    got = _loaded[key]
    return got if got["window"] else None


def _print_tables(trace_dir) -> int:
    got = of_run(trace_dir)
    if got is None:
        print(f"program_spans: no trace with a {trace_reduce.WINDOW_SPAN} "
              f"span under {trace_dir}", file=sys.stderr)
        return 1
    w0, w1 = got["window"]
    print(f"window {w1 - w0:.6f} s, {len(got['threads'])} host threads "
          f"with spans, {len(got['device_ops'])} device plane(s)\n")
    print("| span | calls | total ms | self ms | median ms |")
    print("| --- | --- | --- | --- | --- |")
    table = stage_table(got["rows"])
    for name, s in sorted(table.items(), key=lambda kv: -kv[1]["total_s"]):
        print(f"| `{name}` | {s['calls']} | {s['total_s'] * 1e3:.3f} | "
              f"{s['self_s'] * 1e3:.3f} | {s['median_s'] * 1e3:.3f} |")
    for name, s in table.items():
        if name.startswith(LEADER) and s["total_s"] > 0:
            print(f"\nthe stages under `{name}` cover "
                  f"{100 * (1 - s['self_s'] / s['total_s']):.2f}% of it")
    if not got["device_ops"]:
        print("\nno device plane (a CPU run): host spans only")
        return 0
    print("\n| innermost program span | idle device ms while some thread "
          "was in it |")
    print("| --- | --- |")
    idle = idle_by_span(got["threads"], got["device_ops"], got["window"])
    for name, seconds in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"| `{name}` | {seconds * 1e3:.3f} |")
    shares = got["scopes"]
    busy = sum(shares.values())
    print(f"\nbusy {busy * 1e3:.3f} ms a device\n")
    print("| scope | device ms | % of busy |")
    print("| --- | --- | --- |")
    for scope, seconds in sorted(shares.items(), key=lambda kv: -kv[1]):
        print(f"| `{scope}` | {seconds * 1e3:.3f} | "
              f"{100 * seconds / busy if busy else 0:.2f} |")
    reduced = trace_reduce.reduce_events(
        got["device_ops"], [], window=got["window"])
    print("\n| op | device ms | scope | tf_op |")
    print("| --- | --- | --- | --- |")
    for name, v in sorted(reduced["ops"].items(),
                          key=lambda kv: -kv[1]["s"])[:12]:
        tf_op = got["op_names"].get(name)
        print(f"| `{trace_reduce.short_name(name)[:70]}` | "
              f"{v['s'] * 1e3:.3f} | `{scope_of(tf_op)}` | `{tf_op}` |")
    return 0


if __name__ == "__main__":
    sys.exit(_print_tables(sys.argv[1] if len(sys.argv) > 1 else TRACE_DIR))
