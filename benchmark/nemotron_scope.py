"""Device time of the Mamba-2 mixer's and the routed experts' scopes in a
traced run: the ops whose ``tf_op`` path holds one of the program's
``jax.named_scope``s ``ssm`` (with ``ssm.in_proj``, ``ssm.conv``,
``ssm.scan``, ``ssm.norm``, ``ssm.out_proj`` inside; ``mpi_tpu/models/
mamba2.py``) or ``moe.route``, ``moe.routed``, ``moe.shared`` (``mpi_tpu/
models/moe.py`` ``routed_share_ffn``), read as ``eva_scope.py`` reads its
own: transformations round a path component taken off, every busy instant
of the window going to the innermost op running. ``ssm`` is a part of
``attn``'s time, the three ``moe.*`` are parts of ``ffn``'s.

A program without these scopes (the parent of PR 36, or another model)
gives ``None``. Run as a script after a traced run it prints the stages
and the largest ops of each (innermost time).
"""

from __future__ import annotations

import sys
from typing import Dict, Optional

import eva_scope
import program_spans
import trace_reduce

STAGES = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.norm", "ssm.out_proj",
          "moe.route", "moe.routed", "moe.shared")


def stage_of(op_name: Optional[str]) -> Optional[str]:
    """The stage an op belongs to (``ssm`` itself for an op inside the
    mixer and no stage), else ``None``."""
    parts = eva_scope.parts_of(op_name)
    for stage in STAGES:
        if stage in parts:
            return stage
    return "ssm" if "ssm" in parts else None


def seconds_by_stage(device_ops, names: Dict[str, str],
                     window=None) -> Dict[str, float]:
    """Device seconds inside the scopes by stage, mean over devices."""
    w0, w1 = window if window else (float("-inf"), float("inf"))
    out: Dict[str, float] = {}
    for ops in device_ops:
        for name, a, b in trace_reduce.leaf_segments(ops):
            stage = stage_of(names.get(name))
            seconds = min(b, w1) - max(a, w0)
            if stage is not None and seconds > 0:
                out[stage] = out.get(stage, 0.0) + seconds / len(device_ops)
    return out


def of_run() -> Optional[Dict[str, float]]:
    """``{stage: seconds}`` of the run's trace; ``None`` where there is no
    trace, no device plane or no op inside the scopes."""
    got = program_spans.of_run()
    if got is None or not got["device_ops"]:
        return None
    stages = seconds_by_stage(got["device_ops"], got["op_names"],
                              got["window"])
    return stages or None


def seconds_in(prefix: str) -> Optional[float]:
    """Seconds of the stages that are ``prefix`` or begin with it and a
    dot; ``None`` where the run has none of them."""
    stages = of_run() or {}
    found = [s for name, s in stages.items()
             if name == prefix or name.startswith(prefix + ".")]
    return sum(found) if found else None


def _print_tables(largest: int = 6) -> int:
    got = program_spans.of_run()
    stages = of_run()
    if got is None or stages is None:
        print("nemotron_scope: no op inside the ssm or moe scopes in the "
              "newest trace", file=sys.stderr)
        return 1
    w0, w1 = got["window"]
    by_op: Dict[str, Dict[str, float]] = {}
    for ops in got["device_ops"]:
        for name, a, b in trace_reduce.leaf_segments(ops):
            stage = stage_of(got["op_names"].get(name))
            seconds = min(b, w1) - max(a, w0)
            if stage is not None and seconds > 0:
                slot = by_op.setdefault(stage, {})
                slot[name] = slot.get(name, 0.0) + seconds / len(
                    got["device_ops"])
    for stage, total in sorted(stages.items(), key=lambda kv: -kv[1]):
        print(f"{stage:14s} {total * 1e3:10.3f} ms")
        for name, s in sorted(by_op[stage].items(),
                              key=lambda kv: -kv[1])[:largest]:
            print(f"    {s * 1e3:9.3f}  "
                  f"{trace_reduce.short_name(name)[:100]}")
    return 0


if __name__ == "__main__":
    sys.exit(_print_tables())
