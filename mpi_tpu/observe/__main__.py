"""CLI for observe artifacts::

    python -m mpi_tpu.observe top metrics.json [...]   # render metrics
    python -m mpi_tpu.observe postmortem dir_or_file   # summarize dumps

``top`` renders one or more ``--mpi-metrics-out`` artifacts as the
same text report SIGUSR1 prints live; ``postmortem`` summarizes
per-rank flight-recorder dumps (or an ``mpirun`` job report), naming
each rank's last in-flight operation — the first thing to read after
a crashed job.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Any, Dict, List


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _render_metrics(doc: Dict[str, Any], path: str) -> None:
    from . import metrics

    metrics.validate(doc)
    r = doc.get("rank")
    print(f"== {path} (rank {r if r is not None else '?'}, "
          f"{doc['elapsed_s']:.1f}s) ==")
    for op in sorted(doc["ops"]):
        st = doc["ops"][op]
        print(f"  {op:<18} n={int(st['count']):<8} "
              f"p50={st['p50_us']:.1f}µs p99={st['p99_us']:.1f}µs")
    for peer in sorted(doc["peers"], key=lambda p: int(p)):
        rec = doc["peers"][peer]
        print(f"  peer {peer}: tx {rec['tx_bytes_per_s'] / 1e6:.2f} MB/s"
              f"  rx {rec['rx_bytes_per_s'] / 1e6:.2f} MB/s")
    for line in metrics.compile_lines(doc):
        print(line)
    for row in doc.get("stragglers", []):
        print(f"  straggler: {row['collective']} skew "
              f"{row['max_skew_us']:.1f}µs slowest rank "
              f"{row['slowest_rank']}")


def _describe_op(ent: Dict[str, Any]) -> str:
    peer = ent.get("peer")
    tag = ent.get("tag")
    loc = "" if peer in (None, -1) else f" peer={peer} tag={tag}"
    return f"{ent.get('op', '?')}{loc} bytes={ent.get('bytes', 0)}"


def _render_postmortem(doc: Dict[str, Any], path: str) -> None:
    ranks = doc["ranks"] if "ranks" in doc else {str(doc.get("rank")): doc}
    print(f"== {path} ==")
    for r in sorted(ranks, key=lambda x: (x == "None", x)):
        snap = ranks[r]
        inflight = snap.get("in_flight", [])
        print(f"  rank {r} (pid {snap.get('pid')}): "
              f"reason: {snap.get('reason', '?')}")
        if inflight:
            for ent in inflight:
                print(f"    in flight: {_describe_op(ent)} "
                      f"({ent.get('elapsed_us', 0):.0f}µs elapsed)")
        else:
            print("    no operation in flight")
        recent = snap.get("recent", [])[-3:]
        for ent in recent:
            print(f"    recent: {_describe_op(ent)} -> "
                  f"{ent.get('state', '?')} "
                  f"({ent.get('dur_us', 0):.0f}µs)")


def main(argv: List[str]) -> int:
    if len(argv) < 2 or argv[0] not in ("top", "postmortem"):
        print(__doc__, file=sys.stderr)
        return 2
    cmd, targets = argv[0], argv[1:]
    paths: List[str] = []
    for t in targets:
        if os.path.isdir(t):
            paths += sorted(glob.glob(os.path.join(t, "*.json")))
        else:
            paths += sorted(glob.glob(t)) or [t]
    rc = 0
    for p in paths:
        try:
            doc = _load(p)
            if cmd == "top":
                _render_metrics(doc, p)
            else:
                _render_postmortem(doc, p)
        except Exception as exc:  # noqa: BLE001 - report and continue
            print(f"{p}: unreadable ({exc})", file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
