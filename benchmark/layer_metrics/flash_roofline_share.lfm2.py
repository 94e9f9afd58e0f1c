"""``flash_roofline_share`` over the ``lfm2_moe`` stack's own count: the
least time the chip could take for what the attention kernels must compute
in the traced steps (``flops_lfm2.flash_flops_per_step``: the ``*`` layers
of ``layer_pattern`` alone, at their head size of 64, causal, forward plus
twice that backward, no recomputation counted, at the published bf16
peak) / the device time the Pallas kernels took
(``trace_reduce.pallas_ops``: the flash kernels are the only Pallas calls
of this stack's step)."""

import flops_lfm2
import trace_reduce


def read(run):
    rec, peaks = run["record"], run["peaks"]
    seconds = sum(v["s"] for v in trace_reduce.pallas_ops(run["trace"]).values())
    stamps = rec.get("step_stamps")
    if (not seconds or not stamps or not peaks
            or "*" not in rec["model"].get("layer_pattern", "")):
        return None
    need = flops_lfm2.flash_flops_per_step(
        rec["model"], rec["batch"], rec["seq"]) * (len(stamps) - 1)
    least = need / (peaks["bf16_tflops"] * 1e12 * run["chips"])
    return 100.0 * least / seconds
