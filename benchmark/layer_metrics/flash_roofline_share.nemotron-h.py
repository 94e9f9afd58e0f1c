"""``flash_roofline_share`` over the ``nemotron_h`` stack's own count: the
least time the chip could take for what the attention kernels must compute
in the traced steps (``flops_nemotron_h.flash_flops_per_step``: the ``*``
layers of ``layer_pattern`` alone, at ``attn_head_dim``, causal, forward
plus twice that backward, no recomputation counted, at the published bf16
peak; compute bounds them at these shapes, not HBM) / the device time the
Pallas kernels took (``trace_reduce.pallas_ops``)."""

import flops_nemotron_h
import trace_reduce


def read(run):
    rec, peaks = run["record"], run["peaks"]
    seconds = sum(v["s"] for v in trace_reduce.pallas_ops(run["trace"]).values())
    stamps = rec.get("step_stamps")
    if (not seconds or not stamps or not peaks
            or "layer_pattern" not in rec["model"]):
        return None
    need = flops_nemotron_h.flash_flops_per_step(
        rec["model"], rec["batch"], rec["seq"]) * (len(stamps) - 1)
    least = need / (peaks["bf16_tflops"] * 1e12 * run["chips"])
    return 100.0 * least / seconds
