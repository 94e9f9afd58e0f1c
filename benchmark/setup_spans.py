"""What set-up went to, by jitted program: the program's own table of the
stages its jitted functions went through (``mpi_tpu.utils.trace.compiles()``:
one record a trace, a lowering and a backend compile, each with the
function's name, ``nth``, start and length on ``perf_counter``, and on a
compile whether the persistent cache served it), read in the run's own
process after the kind has returned. The table is always on, so these are
host-side readers that need no profiler: a CPU rehearsal reports them too.

Which records count: those that **began before the window opened**. The
train kinds stamp that instant (``record.step_stamps[0]``, the same
``perf_counter`` the records use). The collective kind records no such
stamp and lets nothing compile after its warm-up (its ``correct`` asks for
the compiled collective, and every size is warmed), so there every record
of the process counts. Seconds are the **union** of the records' intervals
over all threads, never their sum: a trace nests in a trace, and the four
rank threads of the collective cell compile at once.

A program without the table (the parent of PR 38) gives ``None`` from every
reader, and the result line leaves the metric out. Run as a script it has
nothing to print: the table lives in the process that compiled.
"""

from __future__ import annotations

import sys
from typing import Dict, Iterable, List, Optional

STEP = "jit(step)"      # the train step's program, as jax names it


def of_run(run) -> Optional[List[Dict]]:
    """The process's compile records that began before the window opened;
    ``None`` on a program that keeps no such table."""
    from mpi_tpu.utils import trace

    read = getattr(trace, "compiles", None)
    if read is None:
        return None
    stamps = run["record"].get("step_stamps")
    return before(read(), stamps[0] * 1e6 if stamps else float("inf"))


def before(records: Iterable[Dict], cut_us: float) -> List[Dict]:
    return [r for r in records if r["ts_us"] < cut_us]


def union_s(records: Iterable[Dict], stages) -> float:
    """Seconds covered by at least one record of ``stages``."""
    spans = sorted((r["ts_us"], r["ts_us"] + r["dur_us"])
                   for r in records if r["stage"] in stages)
    total, end = 0.0, float("-inf")
    for a, b in spans:
        if b > end:
            total += b - max(a, end)
            end = b
    return total / 1e6


def step_compiles(records: Iterable[Dict]) -> int:
    """Backend compiles of the train step's program: 1 is the floor."""
    return sum(r["stage"] == "compile" and r["fun"] == STEP for r in records)


def hit_share(records: Iterable[Dict]) -> Optional[float]:
    """Percent of the compile requests that went to the persistent cache
    which it served; ``None`` where none went (``cache`` ``off``)."""
    asked = [r["cache"] for r in records
             if r["stage"] == "compile" and r["cache"] in ("hit", "miss")]
    return 100.0 * asked.count("hit") / len(asked) if asked else None


def table_lines(records: List[Dict], rows: int = 40) -> List[str]:
    """The set-up table, largest first: function, stage, ``nth``, start in
    seconds since the process started (``run.py``'s first line; else since
    the first record), seconds, cache."""
    if not records:
        return ["setup_spans: no jitted program went through a stage"]
    t0 = getattr(sys.modules.get("__main__"), "_T0", None)
    origin = t0 * 1e6 if t0 is not None else min(r["ts_us"] for r in records)
    ordered = sorted(records, key=lambda r: -r["dur_us"])
    lines = [f"setup_spans: {len(records)} stages before the window: compile "
             f"{union_s(records, ('compile',)):.2f} s, trace + lower "
             f"{union_s(records, ('trace', 'lower')):.2f} s (unions)",
             f"  {'function':<40} {'stage':<8} {'nth':>3} {'start s':>9} "
             f"{'seconds':>9}  cache"]
    for r in ordered[:rows]:
        lines.append(
            f"  {r['fun'][:40]:<40} {r['stage']:<8} {r['nth']:>3} "
            f"{(r['ts_us'] - origin) / 1e6:>9.2f} {r['dur_us'] / 1e6:>9.3f}"
            f"  {r['cache'] or ''}")
    if len(ordered) > rows:
        lines.append(f"  {len(ordered) - rows} smaller stages: "
                     f"{sum(r['dur_us'] for r in ordered[rows:]) / 1e6:.3f} s"
                     f" summed")
    return lines
