"""``moe_routed_roofline_share`` over the ``lfm2_moe`` stack's own count:
the least time the chip could take for the SwiGLU experts' three products
in the traced steps at the **expected** pairs — the larger of their
required operations at the published bf16 peak and their least HBM bytes
at the published bandwidth (``flops_lfm2.routed_flops_per_step`` /
``routed_bytes_per_step``; compute bounds it at the cell's shapes) — / the
device time of the ops inside the ``moe.routed`` scope
(``nemotron_scope.py``), which also holds the sort, the dispatch, the
combine and, under ``remat``, a recomputed forward pass: none of them in
the count."""

import flops_lfm2
import nemotron_scope


def read(run):
    rec, peaks = run["record"], run["peaks"]
    seconds = nemotron_scope.seconds_in("moe.routed")
    stamps = rec.get("step_stamps")
    if not seconds or not stamps or len(stamps) < 2 or not peaks:
        return None
    steps, chips = len(stamps) - 1, run["chips"]
    args = (rec["model"], rec["batch"], rec["seq"])
    least = steps * max(
        flops_lfm2.routed_flops_per_step(*args)
        / (peaks["bf16_tflops"] * 1e12 * chips),
        flops_lfm2.routed_bytes_per_step(*args)
        / (peaks["hbm_gbytes_per_s"] * 1e9 * chips))
    return 100.0 * least / seconds
