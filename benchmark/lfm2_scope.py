"""Device time of the gated short-conv mixer's scopes in a traced run: the
ops whose ``tf_op`` path holds the program's ``jax.named_scope``
``shortconv`` (with ``shortconv.in_proj``, ``shortconv.conv`` and
``shortconv.out_proj`` inside; ``mpi_tpu/models/short_conv.py``), read as
``nemotron_scope.py`` reads the ``ssm`` and ``moe`` scopes: transformations
round a path component taken off, every busy instant of the window going
to the innermost op running. ``shortconv`` is a part of ``attn``'s time.
The routed experts' scopes (``moe.route``, ``moe.routed``) are
``nemotron_scope.py``'s to read.

A program without the scope (the parent of PR 40, or another model)
gives ``None``. Run as a script after a traced run it prints the stages
and the largest ops of each (innermost time).
"""

from __future__ import annotations

import sys
from typing import Dict, Optional

import eva_scope
import program_spans
import trace_reduce

SCOPE = "shortconv"
STAGES = ("shortconv.in_proj", "shortconv.conv", "shortconv.out_proj")


def stage_of(op_name: Optional[str]) -> Optional[str]:
    """The stage an op belongs to (``shortconv`` itself for an op inside
    the mixer and no stage), else ``None``."""
    parts = eva_scope.parts_of(op_name)
    for stage in STAGES:
        if stage in parts:
            return stage
    return SCOPE if SCOPE in parts else None


def seconds_by_op(got) -> Dict[str, Dict[str, float]]:
    """``{stage: {op: seconds}}`` inside the window, mean over devices."""
    w0, w1 = got["window"]
    out: Dict[str, Dict[str, float]] = {}
    for ops in got["device_ops"]:
        for name, a, b in trace_reduce.leaf_segments(ops):
            stage = stage_of(got["op_names"].get(name))
            seconds = min(b, w1) - max(a, w0)
            if stage is not None and seconds > 0:
                slot = out.setdefault(stage, {})
                slot[name] = slot.get(name, 0.0) + seconds / len(
                    got["device_ops"])
    return out


def of_run() -> Optional[Dict[str, float]]:
    """``{stage: seconds}`` of the run's trace; ``None`` where there is no
    trace, no device plane or no op inside the scope."""
    got = program_spans.of_run()
    if got is None or not got["device_ops"]:
        return None
    stages = {stage: sum(ops.values())
              for stage, ops in seconds_by_op(got).items()}
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
    if got is None or not got["device_ops"] or not seconds_by_op(got):
        print("lfm2_scope: no op inside the shortconv scope in the newest "
              "trace", file=sys.stderr)
        return 1
    by_op = seconds_by_op(got)
    for stage, ops in sorted(by_op.items(),
                             key=lambda kv: -sum(kv[1].values())):
        print(f"{stage:20s} {sum(ops.values()) * 1e3:10.3f} ms")
        for name, s in sorted(ops.items(), key=lambda kv: -kv[1])[:largest]:
            print(f"    {s * 1e3:9.3f}  "
                  f"{trace_reduce.short_name(name)[:100]}")
    return 0


if __name__ == "__main__":
    sys.exit(_print_tables())
