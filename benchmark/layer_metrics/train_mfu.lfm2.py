"""``train_mfu`` over the ``lfm2_moe`` stack's own count: operations the
forward and backward passes require (``flops_lfm2.train_flops_per_step``:
every layer of ``layer_pattern`` by its kind, the SwiGLU experts at the
expected pairs, the head; recomputed operations do not count) x steps /
host time of those steps / the published bf16 peak of the chips used: the
share of the whole step."""

import flops_lfm2


def read(run):
    rec, peaks = run["record"], run["peaks"]
    stamps = rec.get("step_stamps")
    if (not stamps or len(stamps) < 2 or not peaks
            or "C" not in rec["model"].get("layer_pattern", "")):
        return None
    need = flops_lfm2.train_flops_per_step(
        rec["model"], rec["batch"], rec["seq"])
    rate = need * (len(stamps) - 1) / (stamps[-1] - stamps[0])
    return 100.0 * rate / (peaks["bf16_tflops"] * 1e12 * run["chips"])
