"""The EVA op against its roofline: the least time the chip could take for
what the op must do in the traced steps — the larger of its required
operations at the published bf16 peak and its least HBM bytes at the
published bandwidth (``flops_evabyte.eva_flops_per_step`` /
``eva_bytes_per_step``; compute bounds it at the cell's shapes) — / the
device time of the ops inside the ``eva`` scope (``eva_scope.py``), which
under ``remat`` holds a recomputed forward pass that the count leaves out."""

import eva_scope
import flops_evabyte


def read(run):
    rec, peaks = run["record"], run["peaks"]
    stages = eva_scope.of_run()
    stamps = rec.get("step_stamps")
    if not stages or not stamps or len(stamps) < 2 or not peaks:
        return None
    steps, chips = len(stamps) - 1, run["chips"]
    args = (rec["model"], rec["batch"], rec["seq"])
    least = steps * max(
        flops_evabyte.eva_flops_per_step(*args)
        / (peaks["bf16_tflops"] * 1e12 * chips),
        flops_evabyte.eva_bytes_per_step(*args)
        / (peaks["hbm_gbytes_per_s"] * 1e9 * chips))
    return 100.0 * least / sum(stages.values())
