"""The gated short-conv mixers against their roofline: the least time the
chip could take for what they must do in the traced steps — the larger of
their required operations at the published bf16 peak and their least HBM
bytes at the published bandwidth (``flops_lfm2.shortconv_flops_per_step``
/ ``shortconv_bytes_per_step``; compute bounds it at the cell's shapes) —
/ the device time of the ops inside the ``shortconv`` scope
(``lfm2_scope.py``), which under ``remat`` holds a recomputed forward pass
that the count leaves out."""

import flops_lfm2
import lfm2_scope


def read(run):
    rec, peaks = run["record"], run["peaks"]
    seconds = lfm2_scope.seconds_in("shortconv")
    stamps = rec.get("step_stamps")
    if not seconds or not stamps or len(stamps) < 2 or not peaks:
        return None
    steps, chips = len(stamps) - 1, run["chips"]
    args = (rec["model"], rec["batch"], rec["seq"])
    least = steps * max(
        flops_lfm2.shortconv_flops_per_step(*args)
        / (peaks["bf16_tflops"] * 1e12 * chips),
        flops_lfm2.shortconv_bytes_per_step(*args)
        / (peaks["hbm_gbytes_per_s"] * 1e9 * chips))
    return 100.0 * least / seconds
