"""The benchmark's harness, run the way the driver runs it.

``benchmark/run.py`` is what every PR's chip numbers come from, and its
kinds reach into the program (``make_train_step``'s ``_cache_size``,
the xla driver's ``_jit_cache``, the loader, ``run_spmd``). The driver
runs it only after a session is over, on the chip; ``--rehearse`` runs
the same code at tiny shapes on four virtual CPU devices, so a program
change that breaks the harness shows here first. One case for each
kind of cell (``--rehearse`` swaps in one tiny configuration a kind,
so a second train cell would run the same program again).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())


def _end_to_end_names(cell: str) -> set:
    """The cell's end-to-end metrics: an entry of ``BENCHMARK.json``
    with no ``workloads`` belongs to every cell."""
    return {m["name"] for m in BENCHMARK["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.integration
@pytest.mark.parametrize("cell", ["osu-allreduce-f32-r4.sweep-4B-64MiB",
                                  "starcoder2-3b-L6.pretrain-4k-b2",
                                  "nemotron-3-nano-L9-E8.pretrain-8k",
                                  "lfm2-24b-a2b-L9-E8.pretrain-8k"])
def test_rehearsal_prints_the_cells_record(cell, tmp_path):
    assert cell in {w["name"] for w in BENCHMARK["workloads"]}
    # Without what conftest.py puts into the environment for the tests'
    # own jax (8 virtual devices, x64): run.py asks for its own four, and
    # the driver runs it with neither.
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    res = subprocess.run(
        [sys.executable, *BENCHMARK["command"][1:], "--workload", cell,
         "--seed", "1", "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr[-2000:]
    record = json.loads(res.stdout.strip().splitlines()[-1])
    assert record["rehearsal"] is True
    assert record["workload"] == cell
    assert record["failed"] == 0 and record["attempted"] > 0
    assert _end_to_end_names(cell) <= set(record["metrics"])


@pytest.mark.integration
@pytest.mark.parametrize("cell", ["osu-allreduce-f32-r4.sweep-4B-64MiB",
                                  "starcoder2-3b-L6.pretrain-4k-b2"])
def test_traced_rehearsal_reports_the_cells_set_up_metrics(cell, tmp_path):
    """The four ``jit programs`` metrics read the program's always-on
    compile table on the host, so a traced CPU rehearsal reports those of
    the cell, the set-up table on the lines before the result."""
    wanted = {m["name"] for m in BENCHMARK["per_layer"]
              if m["layer"] == "jit programs" and cell in m["workloads"]}
    assert {"setup_compile_s", "setup_trace_lower_s"} < wanted
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    res = subprocess.run(
        [sys.executable, *BENCHMARK["command"][1:], "--workload", cell,
         "--seed", "2147483659", "--seconds", "2", "--trace", "1",
         "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr[-2000:]
    lines = res.stdout.strip().splitlines()
    metrics = json.loads(lines[-1])["metrics"]
    assert wanted <= set(metrics)
    assert any(line.startswith("setup_spans: ") for line in lines[:-1])
    assert metrics["setup_compile_s"]["value"] > 0
    assert metrics["setup_trace_lower_s"]["value"] > 0
    if "step_compiles" in wanted:
        assert metrics["step_compiles"]["value"] >= 1
    # An empty cache of its own: every request went to it, none was served.
    assert metrics["compile_cache_hit_share"]["value"] == 0.0
