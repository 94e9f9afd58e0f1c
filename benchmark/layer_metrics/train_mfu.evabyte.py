"""``train_mfu`` over EvaByte's own count: operations the forward and
backward passes require (``flops_evabyte.train_flops_per_step``: gated
FFN, EVA attention, the eight-head output; recomputed operations do not
count) x steps / host time of those steps / the published bf16 peak of the
chips used."""

import flops_evabyte


def read(run):
    rec, peaks = run["record"], run["peaks"]
    stamps = rec.get("step_stamps")
    if not stamps or len(stamps) < 2 or not peaks:
        return None
    need = flops_evabyte.train_flops_per_step(
        rec["model"], rec["batch"], rec["seq"])
    rate = need * (len(stamps) - 1) / (stamps[-1] - stamps[0])
    return 100.0 * rate / (peaks["bf16_tflops"] * 1e12 * run["chips"])
