"""The attention kernels against their roofline: the least time the chip
could take for what they must compute (``flops.flash_flops_per_step``:
causal, forward plus twice that backward, no recomputation counted, at
the published bf16 peak; compute bounds them at these shapes, not HBM) /
the device time they took in the traced steps."""

import flops
import trace_reduce


def read(run):
    rec, peaks = run["record"], run["peaks"]
    seconds = sum(v["s"] for v in trace_reduce.pallas_ops(run["trace"]).values())
    stamps = rec.get("step_stamps")
    if not seconds or not stamps or not peaks:
        return None
    need = flops.flash_flops_per_step(
        rec["model"], rec["batch"], rec["seq"]) * (len(stamps) - 1)
    least = need / (peaks["bf16_tflops"] * 1e12 * run["chips"])
    return 100.0 * least / seconds
