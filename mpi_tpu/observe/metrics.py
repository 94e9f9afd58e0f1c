"""Live metrics + straggler detection.

Four data sources, one renderer:

  * **flight recorder** (:mod:`.flight`) — per-op duration samples →
    op p50/p99 and counts;
  * **trace counters** (:mod:`mpi_tpu.utils.trace`) — per-peer wire
    byte counters (``wire.*.bytes.peer*``) → bytes/s per peer;
  * **collective arrivals** — every facade collective records its
    local entry wall time here (``note_collective_entry``); in-process
    drivers (xla/hybrid rank threads share one clock) additionally
    report exact per-collective arrival skew (``note_session_skew``),
    and the trace-collection merge (:mod:`.collect`) computes
    cross-process skew from clock-aligned entries;
  * **the compile table** (``trace.compile_table()``) — which jitted
    function was traced, lowered and compiled how often, for how long,
    and how often the persistent cache served it.

``summary_text()`` renders the ``mpi_tpu observe top``-style report —
printed on SIGUSR1 (installed at init) or at finalize; ``write()``
emits the machine-readable ``--mpi-metrics-out`` JSON artifact
(schema in docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from . import flight

__all__ = ["note_collective_entry", "note_session_skew",
           "collective_entries", "session_skews", "snapshot", "write",
           "summary_text", "install_sigusr1", "reset_for_testing"]

SCHEMA_VERSION = 1

_COMPILE_FIELDS = ("traces", "lowerings", "compiles", "cache_hits",
                   "trace_s", "lower_s", "compile_s")
_ENTRIES_CAP = 16384
_SKEWS_CAP = 4096

_lock = threading.Lock()
_entries: deque = deque(maxlen=_ENTRIES_CAP)  # (name, seq, wall_ns)
_entry_seq: Dict[str, int] = {}
_skews: deque = deque(maxlen=_SKEWS_CAP)      # (name, skew_us, slowest)
_t_start = time.time()


def note_collective_entry(name: str) -> None:
    """Record this rank's arrival at a collective. Per-name sequence
    numbers align across ranks because collectives are invoked in the
    same order on every rank (the standard MPI requirement)."""
    with _lock:
        seq = _entry_seq.get(name, 0)
        _entry_seq[name] = seq + 1
        _entries.append((name, seq, time.time_ns()))


def note_session_skew(name: str, skew_us: float, slowest: int) -> None:
    """Exact arrival skew for one in-process collective session
    (xla/hybrid rank threads — one clock, no alignment needed)."""
    with _lock:
        _skews.append((name, float(skew_us), int(slowest)))


def collective_entries() -> List[Tuple[str, int, int]]:
    with _lock:
        return list(_entries)


def session_skews() -> List[Tuple[str, float, int]]:
    with _lock:
        return list(_skews)


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _op_stats() -> Dict[str, Dict[str, float]]:
    snap = flight.snapshot()
    counts = snap["op_counts"]
    out: Dict[str, Dict[str, float]] = {}
    for op, samples in flight.op_durations().items():
        s = sorted(samples)
        out[op] = {
            "count": counts.get(op, len(s)),
            "p50_us": _percentile(s, 0.50),
            "p99_us": _percentile(s, 0.99),
        }
    return out


def _peer_bytes() -> Dict[str, Dict[str, float]]:
    """Per-peer tx/rx byte totals from the wire counters."""
    from ..utils import trace

    peers: Dict[str, Dict[str, float]] = {}
    for name, val in trace.counters().items():
        # wire.<proto>.{tx,rx}.bytes.peer<r>
        if ".bytes.peer" not in name:
            continue
        head, _, peer = name.rpartition(".peer")
        direction = "tx" if ".tx." in head else "rx"
        rec = peers.setdefault(peer, {"tx_bytes": 0.0, "rx_bytes": 0.0})
        rec[f"{direction}_bytes"] += val
    return peers


def _worst_session_skews(k: int = 8) -> List[Dict[str, Any]]:
    worst: Dict[str, Tuple[float, int]] = {}
    for name, skew_us, slowest in session_skews():
        if name not in worst or skew_us > worst[name][0]:
            worst[name] = (skew_us, slowest)
    rows = [{"collective": n, "max_skew_us": s, "slowest_rank": r}
            for n, (s, r) in worst.items()]
    rows.sort(key=lambda r: -r["max_skew_us"])
    return rows[:k]


def snapshot(rank: Optional[int] = None,
             size: Optional[int] = None) -> Dict[str, Any]:
    """The metrics-out artifact body (one per rank)."""
    from ..utils import trace

    elapsed = max(1e-9, time.time() - _t_start)
    peers = _peer_bytes()
    for rec in peers.values():
        rec["tx_bytes_per_s"] = rec["tx_bytes"] / elapsed
        rec["rx_bytes_per_s"] = rec["rx_bytes"] / elapsed
    return {
        "schema_version": SCHEMA_VERSION,
        "rank": rank,
        "size": size,
        "pid": os.getpid(),
        "elapsed_s": elapsed,
        "ops": _op_stats(),
        "peers": peers,
        "counters": trace.counters(),
        "trace_dropped_events": trace.dropped(),
        "compiles": trace.compile_table(),
        "compiles_dropped": trace.compiles_dropped(),
        "stragglers": _worst_session_skews(),
        "collective_entries": len(collective_entries()),
    }


def validate(doc: Dict[str, Any]) -> None:
    """Raise ValueError unless ``doc`` is a well-formed metrics artifact
    (the schema contract the observe CLI relies on)."""
    if not isinstance(doc, dict):
        raise ValueError("metrics artifact is not an object")
    if doc.get("schema_version") != SCHEMA_VERSION:
        raise ValueError(
            f"unsupported metrics schema_version {doc.get('schema_version')}")
    for key, typ in (("ops", dict), ("peers", dict), ("counters", dict),
                     ("stragglers", list), ("elapsed_s", (int, float))):
        if not isinstance(doc.get(key), typ):
            raise ValueError(f"metrics artifact field {key!r} malformed")
    for op, st in doc["ops"].items():
        for f in ("count", "p50_us", "p99_us"):
            if f not in st:
                raise ValueError(f"metrics op {op!r} missing {f!r}")
    # An artifact written before the section existed has none.
    compiles = doc.get("compiles", {})
    if not isinstance(compiles, dict):
        raise ValueError("metrics artifact field 'compiles' malformed")
    for fun, row in compiles.items():
        missing = [f for f in _COMPILE_FIELDS
                   if not isinstance(row, dict) or f not in row]
        if missing:
            raise ValueError(f"metrics compiles {fun!r} missing {missing}")


def compile_lines(doc: Dict[str, Any], k: int = 8) -> List[str]:
    """The ``compiles`` section as ``observe top`` prints it, the ``k``
    functions that cost most first: which program the set-up went to,
    and which one compiled again (``x2``)."""
    def seconds(row):
        return row["trace_s"] + row["lower_s"] + row["compile_s"]

    rows = sorted(doc.get("compiles", {}).items(),
                  key=lambda kv: -seconds(kv[1]))
    return [f"  compiles: {fun} x{row['compiles']} {seconds(row):.1f} s "
            f"({row['cache_hits']} from cache; traced x{row['traces']}, "
            f"lowered x{row['lowerings']})" for fun, row in rows[:k]]


def write(path: str, rank: Optional[int] = None,
          size: Optional[int] = None) -> str:
    """Write this rank's metrics artifact. ``{rank}`` in the path is
    substituted; otherwise multi-rank jobs get a ``.rank<r>`` suffix so
    ranks never clobber each other."""
    if "{rank}" in path:
        path = path.replace("{rank}", str(rank if rank is not None else 0))
    elif size is not None and size > 1:
        path = f"{path}.rank{rank}"
    doc = snapshot(rank=rank, size=size)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


def summary_text(rank: Optional[int] = None,
                 size: Optional[int] = None) -> str:
    """The ``observe top`` report: bytes/s per peer, op p50/p99,
    slowest rank per collective."""
    doc = snapshot(rank=rank, size=size)
    lines = [f"mpi_tpu observe top — rank "
             f"{doc['rank'] if doc['rank'] is not None else '?'} "
             f"(pid {doc['pid']}, {doc['elapsed_s']:.1f}s)"]
    if doc["ops"]:
        lines.append(f"  {'op':<18} {'count':>8} {'p50 µs':>10} "
                     f"{'p99 µs':>10}")
        for op in sorted(doc["ops"]):
            st = doc["ops"][op]
            lines.append(f"  {op:<18} {int(st['count']):>8} "
                         f"{st['p50_us']:>10.1f} {st['p99_us']:>10.1f}")
    else:
        lines.append("  (no completed operations recorded)")
    if doc["peers"]:
        lines.append(f"  {'peer':<6} {'tx MB/s':>10} {'rx MB/s':>10} "
                     f"{'tx MB':>10} {'rx MB':>10}")
        for peer in sorted(doc["peers"], key=lambda p: int(p)):
            rec = doc["peers"][peer]
            lines.append(
                f"  {peer:<6} {rec['tx_bytes_per_s'] / 1e6:>10.2f} "
                f"{rec['rx_bytes_per_s'] / 1e6:>10.2f} "
                f"{rec['tx_bytes'] / 1e6:>10.2f} "
                f"{rec['rx_bytes'] / 1e6:>10.2f}")
    lines += compile_lines(doc)
    for row in doc["stragglers"]:
        lines.append(
            f"  straggler: {row['collective']:<12} max skew "
            f"{row['max_skew_us']:.1f} µs, slowest rank "
            f"{row['slowest_rank']}")
    return "\n".join(lines)


_sig_installed = False


def install_sigusr1(rank_fn=None) -> bool:
    """Print the top summary on SIGUSR1. Only possible from the main
    thread (signal module contract) — rank threads (xla driver) skip
    silently — and only when the application has not installed its own
    SIGUSR1 handler (observability must not steal a user's signal).
    Returns True when installed."""
    global _sig_installed
    if _sig_installed:
        return True
    if threading.current_thread() is not threading.main_thread():
        return False
    try:
        current = signal.getsignal(signal.SIGUSR1)
    except (ValueError, AttributeError):
        return False
    if current not in (signal.SIG_DFL, signal.SIG_IGN, None):
        return False  # the application owns SIGUSR1 — leave it

    def _handler(signum, frame):  # pragma: no cover - signal timing
        try:
            r = rank_fn() if rank_fn is not None else None
        except Exception:  # noqa: BLE001
            r = None
        print(summary_text(rank=r), file=sys.stderr, flush=True)

    try:
        signal.signal(signal.SIGUSR1, _handler)
    except (ValueError, OSError, AttributeError):
        return False
    _sig_installed = True
    return True


def reset_for_testing() -> None:
    global _t_start, _sig_installed
    with _lock:
        _entries.clear()
        _entry_seq.clear()
        _skews.clear()
    _t_start = time.time()
    _sig_installed = False
