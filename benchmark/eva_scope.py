"""Device time of the EVA op in a traced run: the ops whose ``tf_op`` path
holds the program's ``jax.named_scope("eva")`` (``mpi_tpu/ops/
eva_attention.py``: pooling, the window's kernels, the summaries' kernels,
the merge, and the gradients of all of them), with the transformations
round a path component (``jvp(...)``, ``transpose(...)``) taken off as
``program_spans.scope_of`` does for its five. Every busy instant of the
window goes to the innermost op running, as in
``program_spans.scope_seconds``, so this is a part of ``attn``'s time.

A program without the scope (the parent of PR 29) gives ``None``.
"""

from __future__ import annotations

from typing import Dict, Optional

import program_spans
import trace_reduce

SCOPE = "eva"


def parts_of(op_name: Optional[str]):
    """``jit(step)/transpose(jvp(attn))/eva/eva.local/mul`` ->
    ``[jit(step)->step, attn, eva, eva.local, mul]``: each component with
    its transformations taken off."""
    out = []
    for part in (op_name or "").split("/"):
        while True:
            m = program_spans._WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        out.append(part)
    return out


def stage_of(op_name: Optional[str]) -> Optional[str]:
    """``eva.local`` / ``eva.remote`` / ``eva.summarize`` / ``eva.merge``
    (or ``eva`` itself) for an op inside the scope, else ``None``."""
    parts = parts_of(op_name)
    if SCOPE not in parts:
        return None
    rest = parts[parts.index(SCOPE) + 1:]
    return rest[0] if rest and rest[0].startswith(SCOPE + ".") else SCOPE


def seconds_by_stage(device_ops, names: Dict[str, str],
                     window=None) -> Dict[str, float]:
    """Device seconds inside the scope by stage, mean over devices."""
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
    trace, no device plane or no op inside the scope."""
    got = program_spans.of_run()
    if got is None or not got["device_ops"]:
        return None
    stages = seconds_by_stage(got["device_ops"], got["op_names"],
                              got["window"])
    return stages or None


if __name__ == "__main__":
    stages = of_run()
    if stages is None:
        raise SystemExit("no op inside the eva scope in the newest trace")
    total = sum(stages.values())
    for stage, s in sorted(stages.items(), key=lambda kv: -kv[1]):
        print(f"{stage:16s} {s * 1e3:10.3f} ms  {100 * s / total:6.2f}%")
    print(f"{'eva':16s} {total * 1e3:10.3f} ms")
