"""The chunked scan against its roofline: the least time the chip could
take for what the scans must do in the traced steps — the larger of their
required operations at the published bf16 peak and their least HBM bytes
at the published bandwidth (``flops_nemotron_h.scan_flops_per_step`` /
``scan_bytes_per_step``; the bytes bound it at the cell's shapes) — / the
device time of the ops inside the ``ssm.scan`` scope
(``nemotron_scope.py``), which under ``remat`` holds a recomputed forward
pass that the count leaves out."""

import flops_nemotron_h
import nemotron_scope


def read(run):
    rec, peaks = run["record"], run["peaks"]
    seconds = nemotron_scope.seconds_in("ssm.scan")
    stamps = rec.get("step_stamps")
    if not seconds or not stamps or len(stamps) < 2 or not peaks:
        return None
    steps, chips = len(stamps) - 1, run["chips"]
    args = (rec["model"], rec["batch"], rec["seq"])
    least = steps * max(
        flops_nemotron_h.scan_flops_per_step(*args)
        / (peaks["bf16_tflops"] * 1e12 * chips),
        flops_nemotron_h.scan_bytes_per_step(*args)
        / (peaks["hbm_gbytes_per_s"] * 1e9 * chips))
    return 100.0 * least / seconds
