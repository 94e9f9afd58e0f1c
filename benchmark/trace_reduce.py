"""From a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU v5e (read by hand, PR 25) that file holds one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per executed
HLO instruction (name = the instruction's text, ``%fusion.11 = (f32[...``),
and a plane ``/host:CPU`` with one line per host thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names. All
events share one clock (``start_ns``, ``duration_ns``).

The reduction is pure arithmetic over ``(name, start, duration)`` triples
(:func:`reduce_events`, checked by ``benchmark/tests/test_trace_reduce.py``
against hand arithmetic); :func:`load` only turns the file into triples.

The window is the host span named ``bench.window``: device events are
clipped to it, so starting and stopping the profiler does not count as
idle time. Idle time is named twice: by the benchmark span the host was in
(each idle stretch goes to one span, so these sum to the idle time), and by
what the runtime's host threads were doing meanwhile (``host: <event>``:
seconds of idle time during which some thread's innermost runtime event had
that name; threads run side by side, so these overlap and do not sum).
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start (s), duration (s)

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OUTSIDE = "outside bench spans"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"[\])]\s+([a-z][\w\-]*)\(")


def short_name(hlo_text: str) -> str:
    """``%fusion.11 = (f32[8,8]{1,0:T(8,128)}, ...) fusion(...)`` ->
    ``fusion.11 fusion (f32[8,8], ...)``: instruction, opcode and result
    shapes, without layouts and operands. Text that is not an HLO
    instruction comes back unchanged."""
    if " = " not in hlo_text:
        return hlo_text
    name, rest = hlo_text.split(" = ", 1)
    rest = _LAYOUT.sub("", rest)
    m = _OPCODE.search(rest)
    if m is None:
        return hlo_text[:120]
    shapes = rest[:m.start() + 1]
    return f"{name.lstrip('%')} {m.group(1)} {shapes}"[:120]


def opcode(hlo_text: str) -> str:
    if " = " not in hlo_text:
        return ""
    m = _OPCODE.search(_LAYOUT.sub("", hlo_text.split(" = ", 1)[1]))
    return "" if m is None else m.group(1)


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def leaf_segments(events: Sequence[Event]) -> List[Tuple[str, float, float]]:
    """For the nested events of ONE thread, what the thread was doing at
    each instant: ``(name of the innermost event, start, end)`` stretches."""
    segments: List[Tuple[str, float, float]] = []
    stack: List[Tuple[str, float]] = []            # (name, end), outermost first
    cursor = 0.0

    def emit_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cursor:
                segments.append((name, cursor, end))
                cursor = end
        if stack and t > cursor:
            segments.append((stack[-1][0], cursor, t))
        cursor = max(cursor, t)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        emit_until(start)
        stack.append((name, start + dur))
    emit_until(float("inf"))
    return segments


def _intersection(a: Sequence[Tuple[float, float]],
                  b: Sequence[Tuple[float, float]]) -> float:
    """Total length of the overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += _overlap(a[i][0], a[i][1], b[j][0], b[j][1])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_events(device_ops: Sequence[Sequence[Event]],
                  host_spans: Sequence[Event],
                  window: Optional[Tuple[float, float]] = None,
                  host_threads: Sequence[Sequence[Event]] = ()) -> Dict:
    """``device_ops[i]`` are device ``i``'s op events, ``host_spans`` the
    benchmark's own host spans (names starting ``bench.``). Returns

    - ``window_s``: length of the window (the ``bench.window`` span, else
      ``window``, else first to last device event);
    - ``busy_s``: union of the device-op intervals inside the window, mean
      over devices; ``busy_s_per_device``;
    - ``idle_share``: ``1 - busy_s / window_s``;
    - ``ops``: ``{name: {"s": seconds (mean over devices), "n": events on
      the busiest device, "durations": that device's durations}}``;
    - ``gaps``: ``{span name: idle seconds (mean over devices)}``: every idle
      stretch is given to the benchmark span that covers most of it, the
      window itself excepted, or to ``outside bench spans``;
    - ``host_in_idle``: ``{runtime event: seconds of idle time (mean over
      devices) during which it was the innermost event of some thread of
      ``host_threads``}`` (the runtime's own events, one list a thread);
    - ``longest_gap_s``.
    """
    if window is None:
        for name, start, dur in host_spans:
            if name == WINDOW_SPAN:
                window = (start, start + dur)
                break
    if window is None:
        starts = [s for ops in device_ops for _, s, _ in ops]
        ends = [s + d for ops in device_ops for _, s, d in ops]
        if not starts:
            return {}
        window = (min(starts), max(ends))
    w0, w1 = window
    window_s = w1 - w0
    n_dev = max(1, len(device_ops))
    spans = [(n, s, s + d) for n, s, d in host_spans if n != WINDOW_SPAN]

    doing: Dict[str, List[Tuple[float, float]]] = {}
    for thread in host_threads:
        for name, a, b in leaf_segments(thread):
            doing.setdefault(name, []).append((a, b))
    doing = {name: _union(iv) for name, iv in doing.items()}

    busy_per_device: List[float] = []
    ops: Dict[str, Dict] = {}
    gaps: Dict[str, float] = {}
    host_in_idle: Dict[str, float] = {}
    longest_gap = 0.0
    for dev_ops in device_ops:
        clipped = []
        per_name: Dict[str, List[float]] = {}
        for name, start, dur in dev_ops:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            per_name.setdefault(name, []).append(b - a)
        merged = _union(clipped)
        busy_per_device.append(sum(b - a for a, b in merged))
        for name, durs in per_name.items():
            slot = ops.setdefault(name, {"s": 0.0, "n": 0, "durations": []})
            slot["s"] += sum(durs) / n_dev
            if len(durs) > slot["n"]:
                slot["n"], slot["durations"] = len(durs), durs
        edges = [w0] + [t for ab in merged for t in ab] + [w1]
        idle = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        for name, intervals in doing.items():
            seconds = _intersection(intervals, idle)
            if seconds > 0:
                host_in_idle[name] = host_in_idle.get(name, 0.0) \
                    + seconds / n_dev
        for g0, g1 in idle:
            longest_gap = max(longest_gap, g1 - g0)
            best, best_cover = OUTSIDE, 0.0
            for name, s0, s1 in spans:
                cover = _overlap(g0, g1, s0, s1)
                if cover > best_cover:
                    best, best_cover = name, cover
            gaps[best] = gaps.get(best, 0.0) + (g1 - g0) / n_dev
    busy_s = sum(busy_per_device) / n_dev
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_per_device": busy_per_device,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "ops": ops,
        "gaps": gaps,
        "host_in_idle": host_in_idle,
        "longest_gap_s": longest_gap,
    }


def breakdown(reduced: Dict, top: int = 10) -> Dict:
    """The final line's ``breakdown``: the device operations that took
    most time and the idle time by what the host was doing."""
    ops = sorted(((short_name(n), v["s"]) for n, v in reduced["ops"].items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["gaps"].items(), key=lambda kv: -kv[1])[:top // 2]
    host = sorted(reduced.get("host_in_idle", {}).items(),
                  key=lambda kv: -kv[1])[:top - len(gaps)]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]
            + [["host: " + n, s] for n, s in host]}


def ops_matching(reduced: Dict, *, opcodes: Sequence[str] = (),
                 substrings: Sequence[str] = ()) -> Dict[str, Dict]:
    """The entries of ``reduced['ops']`` whose HLO opcode is one of
    ``opcodes`` or whose text holds one of ``substrings``."""
    return {n: v for n, v in reduced.get("ops", {}).items()
            if opcode(n) in opcodes or any(s in n for s in substrings)}


# How the work the per-layer metrics ask about is named in a v5e trace
# (read by hand, PR 25). The Pallas kernels carry no ``name=`` today: they
# are ``%jvp__.N`` / ``%transpose_jvp___.N`` custom calls, told from XLA's own
# custom calls (``ConcatBitcast``...) by their target. A collective is its
# HLO opcode (``%psum.7 = f32[1,16777216] all-reduce(...)``), also where the
# compiler splits it into ``-start`` / ``-done``.
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def pallas_ops(reduced: Dict) -> Dict[str, Dict]:
    return ops_matching(reduced, substrings=(PALLAS_TARGET,))


def collective_ops(reduced: Dict, hlo: str = "all-reduce") -> Dict[str, Dict]:
    return ops_matching(reduced, opcodes=(hlo, hlo + "-start", hlo + "-done"))


def find_xplane(trace_dir) -> Optional[Path]:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


def load(xplane_path) -> Tuple[List[List[Event]], List[Event],
                              List[List[Event]]]:
    """(device op events per TPU plane, the benchmark's host spans, the
    runtime's events per host thread: everything on ``/host:CPU`` that is
    neither a benchmark span nor a Python frame, ``$file:line name``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane_path))
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    threads: List[List[Event]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                runtime: List[Event] = []
                for e in line.events:
                    event = (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    if e.name.startswith(SPAN_PREFIX):
                        host.append(event)
                    elif not e.name.startswith("$"):
                        runtime.append(event)
                if runtime:
                    threads.append(runtime)
    return [devices[i] for i in sorted(devices)], host, threads


def reduce_dir(trace_dir) -> Dict:
    """Reduce the newest trace under ``trace_dir``; ``{}`` if there is none
    or it holds no device plane (a CPU run)."""
    path = find_xplane(trace_dir)
    if path is None:
        return {}
    device_ops, host, threads = load(path)
    if not device_ops:
        return {}
    return reduce_events(device_ops, host, host_threads=threads)
