#!/usr/bin/env python
"""Headline benchmark: flagship train-step MFU on the real TPU.

The reference's only perf harness is ``examples/bounce`` — an even/odd-pair
ping-pong over its TCP transport (/root/reference/examples/bounce/
bounce.go:37-153) — and it publishes no numbers. This
framework's headline is therefore what its *new* capability does on the
actual hardware: one fully-jitted optimizer step of the flagship sharded
Transformer (bf16 compute, Pallas flash attention), reported as **MFU**
(model FLOPs / peak bf16 FLOPs), plus the BASELINE.json north-star
Allreduce bandwidth, plus the reference's own bounce method with the TCP
baseline re-measured in the same run (no stale constants).

Prints ONE JSON line on stdout::

    {"metric": "train_step_mfu", "value": <pct of peak>, "unit": "pct",
     "vs_baseline": <value / 40.0>, ...extra keys...}

``vs_baseline`` compares against a 40%-of-peak bar — the MFU a well-tuned
large-transformer training run sustains on TPUs (the scaling-book
heuristic); >1.0 means this step beats that bar. The extra keys carry the
other measurements machine-readably: ``allreduce_256MiB_gbps`` (north
star, BASELINE.json:5 — null when only one chip is visible, because a
1-device psum is the identity; the ``_cpu8mesh`` twin then carries the
multi-device collective measured on a virtual 8-device mesh),
``bounce_tcp_us`` / ``bounce_xla_us`` / ``bounce_speedup`` (reference
method, both sides measured same-machine same-run),
``bounce_device_us`` (the same ping-pong with a committed device-array
payload riding the DevicePipe's compiled ppermute p2p between two
distinct devices of a virtual mesh — no host round-trip of the bytes),
``decode_tokens_per_s`` (KV-cache greedy decode of the same flagship —
the serving-side twin of the training headline), and provenance
(device kind, peak TFLOP/s used, model shape).

Timing method: every measurement differences two chained device-side
programs (e.g. a ``lax.scan`` of 10 train steps vs 2) and divides by the
step delta — the fixed dispatch and host-sync cost cancels and only
device time remains.

Without ``--platform`` the device legs need a TPU: a leg that finds none
fails, and the run exits non-zero after printing its line. ``--platform
cpu[:N]`` is the path the tests use to exercise the harness at smoke
sizes; its line carries no MFU (the CPU has no published peak).

``--suite`` additionally runs the Allreduce bandwidth sweep
(BASELINE.json config 3: 1 KiB → 256 MiB over every visible device) and
prints the table to **stderr**, keeping stdout's single-line contract.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys
import threading
import time
from typing import Optional

BOUNCE_SIZE = 1_000_000   # bytes — the 1e6 row of the bounce sweep
BOUNCE_REPS = 10          # bounce.go:35
BOUNCE_WARMUP = 3
MFU_BASELINE_PCT = 40.0   # well-tuned large-model training bar

# Peak dense bf16 TFLOP/s per chip, by device_kind substring (first match
# wins). A TPU kind that is not listed is an error, not a default.
_PEAK_BF16_TFLOPS = (
    ("v6", 918.0), ("trillium", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0), ("v5e", 197.0), ("v5lite", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 46.0),
)


def _peak_tflops(device) -> tuple:
    """(peak bf16 TFLOP/s, provenance string) for ``device``. Only the
    CPU backend (``--platform cpu``, the tests' path) has no peak: its
    line reports mfu null and lets tokens/s carry it."""
    if device.platform != "tpu":
        return None, f"unknown-kind:{device.device_kind}"
    kind = device.device_kind.lower()
    for sub, tf in _PEAK_BF16_TFLOPS:
        if sub in kind:
            return tf, f"table:{device.device_kind}"
    raise RuntimeError(
        f"bench: no published bf16 peak for TPU kind "
        f"{device.device_kind!r}; add it to _PEAK_BF16_TFLOPS")


# --------------------------------------------------------------------------
# Train-step MFU (headline)
# --------------------------------------------------------------------------

def train_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Analytic matmul FLOPs for one optimizer step (fwd + 2x bwd).

    Counts only MXU work (the MFU convention): qkvo projections, FFN,
    attention score/value matmuls, and the logits projection. Causal
    attention is charged at HALF the full s² cost because the flash
    kernel's grid actually skips blocks above the diagonal
    (ops/attention.py) — the conservative accounting."""
    b, s = batch, seq
    d, ff, L, v = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab
    qkvo = 8 * b * s * d * d
    ffn = 4 * b * s * d * ff
    attn = 2 * b * s * s * d          # 4bs²d full, halved: causal
    fwd = L * (qkvo + ffn + attn) + 2 * b * s * d * v
    return 3.0 * fwd


def _last_json(text: str):
    """The LAST JSON object in a child's stdout, or None. raw_decode
    from each brace-opening line: immune to another process's output
    landing on the same line (the interleaving class behind the
    helloworld flake — tests/test_examples.py uses the same defense)."""
    dec = json.JSONDecoder()
    found = None
    for line in (text or "").splitlines():
        start = line.find("{")
        if start < 0:
            continue
        try:
            found = dec.raw_decode(line[start:])[0]
        except ValueError:
            continue
    return found


def _median_time(fn, reps: int = 3):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def _differenced(run_short, run_long, n_short: int, n_long: int):
    """(per_unit_seconds, timing_method): difference a long- and a
    short-program timing so fixed dispatch/host-sync latency cancels; on
    timing noise (non-positive delta) fall back to total/n and SAY SO
    — the shared scaffold of every train/decode-style leg."""
    t_short = _median_time(run_short)
    t_long = _median_time(run_long)
    per_unit = (t_long - t_short) / (n_long - n_short)
    if per_unit <= 0:
        return t_long / n_long, "fallback_total_over_n"
    return per_unit, "differenced"


def measure_train_step(d_model: int = 1024, n_layers: int = 8,
                       n_heads: int = 8, d_ff: int = 4096,
                       vocab: int = 8192, batch: int = 8,
                       seq: int = 1024, short: int = 2, long: int = 10,
                       remat: bool = False,
                       attention: Optional[str] = None) -> dict:
    """One fully-jitted AdamW step of the flagship Transformer at a real
    size (VERDICT round-1 item 1: d_model >= 1024, seq >= 1024, bf16,
    flash attention, on the real chip). Per-step time is the difference
    of a ``long``- and ``short``-step ``lax.scan`` so fixed dispatch /
    host-sync latency cancels."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from mpi_tpu.models import TransformerConfig

    if attention is None:
        # Flash is the measured path. Dense is reachable only under
        # --platform cpu (the tests' path), where flash would time the
        # Pallas interpreter; without --platform a leg with no TPU fails.
        attention = "flash" if jax.default_backend() == "tpu" else "dense"
    # Autotune the flash block grid for THIS chip and shape before the
    # model traces (the winner registers for the exact (seq, seq)
    # attention shape the transformer's flash calls hit). The sweep
    # table doubles as the kernel-level breakdown in the bench line.
    # One sweep per (shape, backend) per process — the long-context
    # leg re-tunes at its own sequence length.
    tuned: dict = {}
    if attention == "flash":
        from mpi_tpu.ops import tune_flash_blocks

        # Winners persist in the COMMITTED package cache
        # (mpi_tpu/ops/flash_tune_cache.json, the autotune default):
        # any run after a completed sweep — this process, a retry, a
        # later round — skips tuning entirely. The candidate list is
        # trimmed to 6; each one costs a kernel compile on a cache miss.
        try:
            best, table = tune_flash_blocks(
                batch, seq, n_heads, d_model // n_heads, reps=2,
                candidates=[(128, 128), (128, 512), (256, 256),
                            (256, 512), (256, 1024), (512, 512)])
            tuned = {"flash_block_q": best[0], "flash_block_k": best[1]}
            if table:
                # Errored configs stay visible ("err:...") — a config
                # that cannot fit VMEM is part of the breakdown too.
                tuned["flash_tune_table_ms"] = {
                    f"{t['block_q']}x{t['block_k']}":
                        t["ms"] if "ms" in t
                        else f"err:{t.get('error', '?')[:60]}"
                    for t in table}
        except Exception as exc:  # noqa: BLE001 - tuning is best-effort
            tuned = {"flash_tune_error": str(exc)[:200]}
    cfg = TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        d_ff=d_ff, max_seq=seq + 1, dtype=jnp.bfloat16,
        attention_impl=attention, remat=remat)
    # MFU stays model-FLOPs based (3x fwd): remat's recompute is real
    # hardware work but not model work — it shows up as lower MFU.
    # The un-jitted body of the SAME step make_train_step ships (shared
    # via make_train_parts), scanned so n steps are one program with one
    # host sync.
    from mpi_tpu.models import make_train_parts

    init_state, step_body = make_train_parts(cfg)
    state = init_state(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, vocab, (batch, seq + 1)),
        dtype=jnp.int32)

    def steps(n):
        @jax.jit
        def run(st):
            st, losses = lax.scan(lambda s, _: step_body(s, tokens),
                                  st, None, length=n)
            return st, losses[-1]
        return run

    run_short, run_long = steps(short), steps(long)
    # Warm both executables synchronously (first TPU compile is the slow
    # part; the float() readbacks keep warm-up work out of the timings).
    loss_v = float(run_short(state)[1])
    float(run_long(state)[1])
    if not math.isfinite(loss_v):
        raise RuntimeError(f"bench train step diverged: loss={loss_v}")

    per_step, timing_method = _differenced(
        lambda: float(run_short(state)[1]),
        lambda: float(run_long(state)[1]), short, long)

    flops = train_flops_per_step(cfg, batch, seq)
    dev = jax.devices()[0]
    peak, peak_src = _peak_tflops(dev)
    achieved_tflops = flops / per_step / 1e12
    result = {
        "train_step_ms": round(per_step * 1e3, 3),
        "train_tokens_per_s": round(batch * seq / per_step),
        "train_achieved_tflops": round(achieved_tflops, 2),
        "mfu_pct": (None if peak is None
                    else round(100.0 * achieved_tflops / peak, 3)),
        "model": {"d_model": d_model, "n_layers": n_layers,
                  "n_heads": n_heads, "d_ff": d_ff, "vocab": vocab,
                  "batch": batch, "seq": seq, "dtype": "bfloat16",
                  "attention": attention},
        "flops_per_step": flops,
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        "peak_tflops": peak,
        "peak_source": peak_src,
        "timing_method": timing_method,
        "loss_first_step": round(loss_v, 4),
        **tuned,
    }
    # Component split AFTER the headline is banked on stdout: the
    # breakdown costs ~6 more jitted programs, and a hang there must
    # cost the split, never the MFU (the leg parent salvages the last
    # complete JSON line when it kills a timed-out child). Disable with
    # MPI_TPU_BENCH_BREAKDOWN=0.
    if os.environ.get("MPI_TPU_BENCH_BREAKDOWN", "1") != "0":
        print(json.dumps(result), flush=True)
        try:
            result.update(_train_breakdown(cfg, state, batch, seq,
                                           short, long, per_step * 1e3))
        except Exception as exc:  # noqa: BLE001 - split is best-effort
            result["train_breakdown_error"] = str(exc)[:200]
    return result


def _train_breakdown(cfg, state, batch: int, seq: int, short: int,
                     long: int, step_ms: float) -> dict:
    """Per-component device-time estimate for the train leg (VERDICT r3
    weak#1: nobody can say where the non-MFU time goes). Components:

    - ``attn``:  fwd+bwd of ONE layer's attention sub-block (the model's
      own ``_attention`` — qkv/o projections + the selected kernel — at
      the model's shapes, grads w.r.t. activations AND weights), scaled
      by ``n_layers``.
    - ``ffn``:   same for the FFN sub-block (gelu MLP).
    - ``opt``:   one AdamW update on the full parameter tree.
    - ``rest``:  ``step - (attn + ffn + opt)`` — embed/head matmuls,
      layernorms, residuals, the loss, and fusion differences.

    Each is its own scanned+differenced jitted program, so the
    cross-component fusion the full step enjoys is NOT captured: the
    split is a lever-finder, not an exact account (``rest`` can go
    slightly negative when isolated programs fuse worse than the step;
    reported as measured)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mpi_tpu.models import make_optimizer
    from mpi_tpu.models.transformer import _attention, _ffn

    blk = state["params"]["blocks"][0]
    # Only the weights each sub-block actually reads: differentiating
    # the WHOLE block dict would charge every component a full-tree
    # read+write per scan step for parameters whose grads are zero
    # (wq..wo traffic in the ffn timing and vice versa), inflating
    # both splits identically and pushing `rest` spuriously negative.
    ablk = {k: blk[k] for k in ("wq", "wk", "wv", "wo")}
    fblk = ({"moe": blk["moe"]} if "moe" in blk
            else {k: blk[k] for k in ("w1", "w2")})
    x0 = jax.random.normal(jax.random.PRNGKey(7),
                           (batch, seq, cfg.d_model), cfg.dtype)

    def timed(body, carry0):
        def steps(n):
            @jax.jit
            def run(c):
                c, _ = lax.scan(body, c, None, length=n)
                return c
            return run
        rs, rl = steps(short), steps(long)
        jax.block_until_ready(rs(carry0))
        jax.block_until_ready(rl(carry0))
        per, _ = _differenced(
            lambda: jax.block_until_ready(rs(carry0)),
            lambda: jax.block_until_ready(rl(carry0)), short, long)
        return per

    def evolve(c, g, eps=1e-6):
        # Fold the grads back into the carry so the scan has a real
        # data dependence step-to-step (nothing dead-code-eliminates)
        # while staying numerically tame.
        return jax.tree.map(
            lambda a, b: a + eps * b.astype(a.dtype), c, g)

    attn_grad = jax.grad(
        lambda x, b: jnp.sum(
            _attention(x, b, cfg, None).astype(jnp.float32)),
        argnums=(0, 1))

    def attn_body(c, _):
        x, b = c
        gx, gb = attn_grad(x, b)
        return (evolve(x, gx), evolve(b, gb)), ()

    ffn_grad = jax.grad(
        lambda x, b: jnp.sum(_ffn(x, b, cfg, None)[0]
                             .astype(jnp.float32)), argnums=(0, 1))

    def ffn_body(c, _):
        x, b = c
        gx, gb = ffn_grad(x, b)
        return (evolve(x, gx), evolve(b, gb)), ()

    opt = make_optimizer("adamw", 1e-3)
    fake_grads = jax.tree.map(
        lambda p: jnp.full_like(p, 1e-4), state["params"])

    def opt_body(c, _):
        import optax
        params, opt_state = c
        updates, opt_state = opt.update(fake_grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), ()

    out: dict = {}
    attn_ms = timed(attn_body, (x0, ablk)) * 1e3 * cfg.n_layers
    out["train_breakdown_attn_ms"] = round(attn_ms, 3)
    ffn_ms = timed(ffn_body, (x0, fblk)) * 1e3 * cfg.n_layers
    out["train_breakdown_ffn_ms"] = round(ffn_ms, 3)
    opt_ms = timed(opt_body, (state["params"], state["opt"])) * 1e3
    out["train_breakdown_opt_ms"] = round(opt_ms, 3)
    rest_ms = step_ms - attn_ms - ffn_ms - opt_ms
    out["train_breakdown_rest_ms"] = round(rest_ms, 3)
    for name, ms in (("attn", attn_ms), ("ffn", ffn_ms),
                     ("opt", opt_ms), ("rest", rest_ms)):
        out[f"train_breakdown_{name}_pct"] = round(
            100.0 * ms / step_ms, 1) if step_ms > 0 else None
    return out


def measure_long_context(seq: int = 8192, d_model: int = 1024,
                         n_heads: int = 8, n_layers: int = 4,
                         d_ff: int = 4096, vocab: int = 8192,
                         batch: int = 1, short: int = 1, long: int = 5
                         ) -> dict:
    """Long-sequence train step: seq 8k, block remat, flash attention —
    the single-chip long-context configuration (multi-chip sequence
    parallelism is exercised by the dryrun's zigzag-flash leg, which has
    no real multi-chip hardware to measure on). Same differenced-scan
    timing as the headline."""
    r = measure_train_step(d_model=d_model, n_layers=n_layers,
                           n_heads=n_heads, d_ff=d_ff, vocab=vocab,
                           batch=batch, seq=seq, short=short, long=long,
                           remat=True)
    out = {
        "long_ctx_seq": seq,
        "long_ctx_step_ms": r["train_step_ms"],
        "long_ctx_tokens_per_s": r["train_tokens_per_s"],
        "long_ctx_mfu_pct": r["mfu_pct"],
        "long_ctx_remat": True,
        "long_ctx_timing_method": r["timing_method"],
    }
    if "flash_block_q" in r:
        out["long_ctx_flash_blocks"] = (f"{r['flash_block_q']}x"
                                        f"{r['flash_block_k']}")
    return out


def measure_decode(d_model: int = 1024, n_layers: int = 8, n_heads: int = 8,
                   d_ff: int = 4096, vocab: int = 8192, batch: int = 8,
                   prompt_len: int = 128, short: int = 16, long: int = 128,
                   int8: bool = False) -> dict:
    """Inference throughput: greedy KV-cache decode of the flagship model
    (models/generate.py — prefill then one ``lax.scan`` over decode
    steps, all compiled). Per-token time differences a ``long``- and
    ``short``-token generate program so fixed dispatch/host-sync latency
    cancels, same method as the train-step timing. Reports decoded
    tokens/s across the batch — the serving-side twin of the training
    headline (no reference analogue; btracey/mpi has no models).

    ``int8=True`` serves weight-only int8 quantized params
    (models/quant.py): decode is HBM-bound, so the smaller weight reads
    are a direct tokens/s lever; keys gain an ``_int8`` suffix."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_tpu.models import (TransformerConfig, generate, init_params,
                                quantize_params)

    cfg = TransformerConfig(
        vocab=vocab, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        d_ff=d_ff, max_seq=prompt_len + long, dtype=jnp.bfloat16,
        attention_impl="dense")  # decode attends via the cache, not flash
    params = init_params(jax.random.PRNGKey(0), cfg)
    if int8:
        params = jax.jit(quantize_params)(params)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, vocab, (batch, prompt_len)),
        dtype=jnp.int32)

    def run(n):
        return jax.jit(lambda p: generate(params, p, cfg, n)[:, -1].sum())

    run_short, run_long = run(short), run(long)
    int(run_short(prompt)); int(run_long(prompt))  # compile + warm
    per_tok, timing_method = _differenced(
        lambda: int(run_short(prompt)),
        lambda: int(run_long(prompt)), short, long)
    sfx = "_int8" if int8 else ""
    return {
        f"decode{sfx}_ms_per_token": round(per_tok * 1e3, 3),
        f"decode{sfx}_tokens_per_s": round(batch / per_tok),
        f"decode{sfx}_batch": batch,
        f"decode{sfx}_prompt_len": prompt_len,
        f"decode{sfx}_timing_method": timing_method,
    }


# --------------------------------------------------------------------------
# Allreduce north star (BASELINE.json:5)
# --------------------------------------------------------------------------

def _size_label(size_bytes: int) -> str:
    if size_bytes >= 1 << 20 and size_bytes % (1 << 20) == 0:
        return f"{size_bytes >> 20}MiB"
    if size_bytes >= 1 << 10 and size_bytes % (1 << 10) == 0:
        return f"{size_bytes >> 10}KiB"
    return f"{size_bytes}B"


def measure_ssm(d_model: int = 1024, n_layers: int = 8,
                d_state: int = 256, d_ff: int = 4096, vocab: int = 8192,
                batch: int = 8, seq: int = 1024, prompt_len: int = 128,
                short: int = 16, long: int = 128,
                train_short: int = 2, train_long: int = 6) -> dict:
    """The state-space LM at flagship scale: train-step time (the
    associative-scan recurrence instead of attention) and greedy decode
    tokens/s (O(1) recurrent state — per-token cost independent of
    context, the structural contrast with the KV-cache decode leg).
    Same differenced-scan timing as every other leg."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from mpi_tpu.models import (SsmConfig, make_ssm_train_step,
                                ssm_decode)

    cfg = SsmConfig(vocab=vocab, d_model=d_model, n_layers=n_layers,
                    d_state=d_state, d_ff=d_ff,
                    dtype=jnp.bfloat16
                    if jax.default_backend() == "tpu" else jnp.float32)
    init_state, step_body = make_ssm_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, vocab, (batch, seq + 1)),
        jnp.int32)

    def steps(k):
        @jax.jit
        def run(st):
            st, losses = lax.scan(lambda s, _: step_body(s, toks),
                                  st, None, length=k)
            return st, losses[-1]
        return run

    rs, rl = steps(train_short), steps(train_long)
    loss_v = float(rs(state)[1])  # compile + warm
    float(rl(state)[1])
    if not math.isfinite(loss_v):
        raise RuntimeError(f"bench ssm train step diverged: "
                           f"loss={loss_v}")
    per_step, train_method = _differenced(
        lambda: float(rs(state)[1]), lambda: float(rl(state)[1]),
        train_short, train_long)

    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, vocab, (batch, prompt_len)),
        jnp.int32)
    params = state["params"]

    def dec(k):
        return jax.jit(lambda p: ssm_decode(cfg, params, p, k)
                       [:, -1].sum())

    ds, dl = dec(short), dec(long)
    int(ds(prompt)); int(dl(prompt))  # compile + warm
    per_tok, dec_method = _differenced(
        lambda: int(ds(prompt)), lambda: int(dl(prompt)), short, long)
    if dec_method != "differenced":
        # The O(1)-state decode is so cheap that long-short tokens of
        # work can sit below dispatch jitter (round-4 artifact:
        # ssm_decode fell back while every other leg differenced).
        # Escalate once: 4x the long program widens the delta past the
        # noise floor instead of silently degrading the method — the
        # fixed host-sync latency does NOT cancel under the fallback,
        # so the retry is what keeps this leg honest.
        long4 = long * 4
        dl4 = dec(long4)
        int(dl4(prompt))  # compile + warm
        per_tok, dec_method = _differenced(
            lambda: int(ds(prompt)), lambda: int(dl4(prompt)),
            short, long4)
    return {
        "ssm_train_step_ms": round(per_step * 1e3, 3),
        "ssm_train_tokens_per_s": round(batch * seq / per_step),
        "ssm_train_timing_method": train_method,
        "ssm_decode_ms_per_token": round(per_tok * 1e3, 3),
        "ssm_decode_tokens_per_s": round(batch / per_tok),
        "ssm_decode_timing_method": dec_method,
        "ssm_loss_first_step": round(loss_v, 4),
        "ssm_model": {"d_model": d_model, "n_layers": n_layers,
                      "d_state": d_state, "d_ff": d_ff, "vocab": vocab,
                      "batch": batch, "seq": seq},
    }


def measure_allreduce(size_bytes: int = 256 << 20, chain: int = 5,
                      quantized: bool = False) -> dict:
    """float32 Allreduce over every visible device, GB/s (keys are
    labelled with the size actually measured).

    The buffer is created *on device* (jit with sharded output — nothing
    crosses from the host), and the op is timed by differencing a
    ``chain``-long program against a 1-long one, with
    ``optimization_barrier`` between links so XLA cannot fold the chain.
    With n devices the busbw convention scales algbw by 2(n-1)/n.

    **n == 1 is degenerate**: psum over a one-device axis IS the
    identity, so there is no bandwidth to measure — the GB/s keys are
    reported as null with a note, never as a latency artifact dressed up
    as bandwidth. (The driver's bench box has one chip; the multi-device
    collective is measured on a virtual mesh instead — see main().)"""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_tpu.parallel import collectives as C
    from mpi_tpu.parallel import make_mesh

    n = len(jax.devices())
    label = _size_label(size_bytes)
    prefix = "qallreduce" if quantized else "allreduce"
    if n == 1:
        return {
            f"{prefix}_{label}_gbps": None,
            f"{prefix}_{label}_busbw_gbps": None,
            f"{prefix}_devices": 1,
            f"{prefix}_note": "1-device axis: psum is the identity; "
                              "no bandwidth exists to measure",
        }
    mesh = make_mesh(n)
    elems = size_bytes // 4 // n
    sharding = NamedSharding(mesh, P("rank"))
    x = jax.jit(lambda: jnp.full((n, elems), 1.0, jnp.float32),
                out_shardings=sharding)()

    inv = 1.0 / n
    if quantized:
        from mpi_tpu.parallel import quantized_allreduce as _qar

        coll = lambda y: _qar(y, "rank")  # noqa: E731
    else:
        coll = lambda y: C.allreduce(y, "rank")  # noqa: E731

    def prog(k):
        def f(y):
            for _ in range(k):
                # *inv keeps values stable; the barrier pins each link of
                # the chain so the timing covers k real collectives.
                y = lax.optimization_barrier(coll(y) * inv)
            return y
        body = jax.shard_map(f, mesh=mesh, in_specs=P("rank"),
                             out_specs=P("rank"), check_vma=False)
        return jax.jit(lambda y: jnp.float32(body(y)[0, 0]))

    p1, pk = prog(1), prog(chain)
    float(p1(x)); float(pk(x))  # compile + warm
    t1 = _median_time(lambda: float(p1(x)))
    tk = _median_time(lambda: float(pk(x)))
    per_op = (tk - t1) / (chain - 1)
    timing_method = "differenced"
    if per_op <= 0:  # noise beat the delta; flag the degraded method
        per_op = tk / chain
        timing_method = "fallback_total_over_n"
    algbw = size_bytes / per_op / 1e9
    return {
        f"{prefix}_{label}_gbps": round(algbw, 2),
        f"{prefix}_{label}_busbw_gbps": round(algbw * 2 * (n - 1) / n, 2),
        f"{prefix}_{label}_p50_us": round(per_op * 1e6, 1),
        f"{prefix}_devices": n,
        f"{prefix}_timing_method": timing_method,
    }


def _hybrid_allreduce_child() -> int:
    """Subprocess leg: the TWO-TIER hierarchical allreduce at BASELINE
    config-5 scale — 4 in-process "hosts" x 8 local ranks = 32 global
    ranks (local xla leg + loopback-TCP leader leg, the exact engine a
    multi-host deployment runs). Reports the 1 MiB p50 per-op latency
    and algorithmic bandwidth as JSON. Numbers measure the engine on
    one machine (threads + loopback), not a network fabric."""
    from mpi_tpu.utils.platform import force_platform

    force_platform("cpu", 1)
    import socket as socketmod
    import threading

    import numpy as np

    from mpi_tpu.backends.hybrid import HybridNetwork, run_spmd_hybrid
    from mpi_tpu.backends.tcp import TcpNetwork
    from mpi_tpu.observe import metrics
    from mpi_tpu.utils import trace

    # Tier spans (VERDICT r3 item 5): the engine's allreduce records
    # local_reduce / leader_exchange / local_bcast wall-clock per call,
    # so the leg reports WHERE the two-tier latency lives instead of
    # one opaque number.
    trace.enable()

    hosts, local = 4, 8
    size_bytes = 1 << 20
    reps, warmup = 12, 3
    # A/B the chunk-pipelined leader leg (ships gate-closed; see
    # backends/hybrid.py): same engine, same ranks, pipeline forced on
    # via the env threshold vs the default serial leg. times_by[label]
    # collects rank-0 per-op wall clocks per variant.
    variants = [("1MiB", 1 << 20, None),
                ("8MiB_pipelined", 8 << 20, str(4 << 20)),
                ("8MiB_serial", 8 << 20, None)]
    times_by: dict = {label: [] for label, _, _ in variants}

    socks = []
    for _ in range(hosts):
        s = socketmod.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    addrs = sorted(f"127.0.0.1:{s.getsockname()[1]:05d}" for s in socks)
    for s in socks:
        s.close()

    tier_evs: list = []   # spans from the 1 MiB variant ONLY
    skew_rows: list = []  # (name, skew_us, slowest) — 1 MiB rounds

    def fn_for(net):
        def main():
            net.init()
            for vi, (label, size, pipeline_min) in enumerate(variants):
                # Env toggle is process-global: fence it with barriers
                # so every rank of every variant sees one setting.
                net.barrier()
                if net.rank() == 0:
                    if vi == 1:
                        # The per-tier keys are labelled 1MiB: snapshot
                        # before the 8 MiB variants pollute the buffer.
                        tier_evs.extend(trace.events())
                        trace.clear()
                        # Arrival-skew rows accumulate in the metrics
                        # module (one process, one clock): the slice
                        # recorded so far is the 1 MiB variant's.
                        skew_rows.extend(metrics.session_skews())
                    if pipeline_min is None:
                        os.environ.pop("MPI_TPU_HYBRID_PIPELINE_MIN",
                                       None)
                    else:
                        os.environ["MPI_TPU_HYBRID_PIPELINE_MIN"] = \
                            pipeline_min
                net.barrier()
                n_reps = reps if size <= (1 << 20) else 6
                x = np.full(size // 4, float(net.rank()), np.float32)
                for i in range(warmup + n_reps):
                    t0 = time.perf_counter()
                    r = net.allreduce(x)
                    dt = time.perf_counter() - t0
                    if net.rank() == 0:
                        if i >= warmup:
                            times_by[label].append(dt)
                        if i == 0 and not np.allclose(
                                np.asarray(r)[:4], 31 * 32 / 2):
                            raise RuntimeError(
                                f"hybrid allreduce wrong sum ({label})")
            net.finalize()
        return main

    nets = [HybridNetwork(
        local_ranks=local,
        tcp=TcpNetwork(addr=a, addrs=list(addrs), timeout=60.0,
                       proto="tcp")) for a in addrs]
    errs: list = []

    def host_main(net):
        try:
            run_spmd_hybrid(fn_for(net), net, register_facade=False)
        except BaseException as exc:  # noqa: BLE001 - join + surface
            errs.append(exc)

    threads = [threading.Thread(target=host_main, args=(n,), daemon=True)
               for n in nets]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    if errs:
        raise errs[0]
    if any(t.is_alive() for t in threads):
        # A hung host past the join deadline means the world is broken:
        # an empty `times` would raise a bare StatisticsError and a
        # partial one would print a normal-looking line measured
        # against a wedged engine — fail explicitly instead.
        raise RuntimeError(
            "hybrid allreduce: host thread(s) still running after 300s")
    p50 = statistics.median(times_by["1MiB"])
    rec = {
        "hybrid_allreduce_1MiB_p50_us_4x8": round(p50 * 1e6, 1),
        "hybrid_allreduce_1MiB_gbps_4x8": round(size_bytes / p50 / 1e9, 3),
        "hybrid_allreduce_world": hosts * local,
    }
    # The pipelined leader leg vs forced serial at 8 MiB (same engine,
    # same run): the delta is the overlap of the exchange and bcast
    # tiers (backends/hybrid.py _pipelined_leader_leg).
    p_pipe = statistics.median(times_by["8MiB_pipelined"])
    p_ser = statistics.median(times_by["8MiB_serial"])
    rec["hybrid_allreduce_8MiB_pipelined_p50_us_4x8"] = round(
        p_pipe * 1e6, 1)
    rec["hybrid_allreduce_8MiB_serial_p50_us_4x8"] = round(
        p_ser * 1e6, 1)
    rec["hybrid_allreduce_8MiB_pipeline_speedup"] = round(
        p_ser / p_pipe, 2)
    # Per-tier medians over the 1 MiB variant's spans (all ranks
    # record local_reduce; only the 4 leaders record leader_exchange
    # and local_bcast — a non-leader's bcast entry blocks on its
    # leader's exchange, so its wait is recorded separately as
    # follower_wait instead of polluting the bcast cost. Warmup
    # iterations included — the median is robust to their
    # compile/connect cost).
    evs = tier_evs
    for tier in ("local_reduce", "leader_exchange", "local_bcast",
                 "follower_wait"):
        durs = sorted(e["dur_us"] for e in evs
                      if e["name"] == f"hybrid.allreduce.{tier}")
        if durs:
            rec[f"hybrid_allreduce_1MiB_tier_{tier}_p50_us"] = round(
                statistics.median(durs), 1)
            rec[f"hybrid_allreduce_tier_{tier}_spans"] = len(durs)
    # Straggler table over the 1 MiB rounds: per-round arrival skew of
    # the 32 rank threads at the collective's entry barrier (recorded by
    # the xla session while the tracer is on). Thread-scheduling jitter,
    # not an engine signal — the _skew_ keys are excluded from the
    # regression check.
    ar_rows = [r for r in skew_rows if "allreduce" in r[0]] or skew_rows
    if ar_rows:
        skews = sorted(s for _, s, _ in ar_rows)
        worst = max(ar_rows, key=lambda r: r[1])
        rec["hybrid_allreduce_1MiB_skew_p50_us"] = round(
            statistics.median(skews), 1)
        rec["hybrid_allreduce_1MiB_skew_max_us"] = round(worst[1], 1)
        rec["hybrid_allreduce_1MiB_skew_slowest_rank"] = worst[2]
        rec["hybrid_allreduce_1MiB_skew_rounds"] = len(ar_rows)
        rec["hybrid_allreduce_1MiB_stragglers"] = [
            {"collective": n, "skew_us": round(s, 1),
             "slowest_rank": sl}
            for n, s, sl in sorted(ar_rows, key=lambda r: -r[1])[:5]]
    print(json.dumps(rec))
    return 0


def measure_hybrid_allreduce() -> dict:
    """Run the 32-rank two-tier allreduce in a subprocess (it pins the
    CPU platform and spawns 32 threads) and return its keys."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--_hybrid-allreduce-child"],
        capture_output=True, text=True, timeout=420)
    if proc.returncode != 0:
        raise RuntimeError(f"hybrid allreduce child failed: "
                           f"{proc.stderr[-500:]}")
    rec = _last_json(proc.stdout)
    if rec is None:
        raise RuntimeError("hybrid allreduce child printed no JSON")
    return rec


def _host_membw_probe() -> dict:
    """Single-core copy bandwidth (read+write GB/s) at a cache-resident
    and a DRAM-resident block size, plus the L3 size and core count —
    the context that makes the cpu8mesh allreduce curve interpretable.

    Round-4 verdict (weak #2): busbw collapsed 3.5x from 32 MiB to
    256 MiB at the north-star size and nothing in the artifact said
    why. Root cause (measured, round 5): the virtual 8-device mesh is
    ONE physical core sharing ONE L3 (105 MiB on the bench box). Up to
    ~32 MiB payload the whole working set (inputs + outputs) is
    L3-resident; past it every link of the chain streams from DRAM,
    and XLA's CPU all-reduce moves ~4-6x the payload (gather +
    reduce + replicated results across 8 time-sliced device runtimes).
    An algorithm A/B at 32/64/256 MiB confirmed psum is already the
    fastest path at every size on this fabric (ppermute ring 1.7-2.1x
    slower, binomial tree ~3x, chunked psum worse — bounding the
    working set cannot avoid the compulsory DRAM streams). See
    docs/PERF_NOTES.md for the full table. These keys let the artifact
    carry that diagnosis: busbw at sizes whose working set exceeds
    ``host_l3_mib`` is bounded by ``host_membw_copy_dram_gbps`` /
    traffic-multiple, not by the collective algorithm."""
    import numpy as np

    def copy_gbps(mib: int) -> float:
        a = np.ones(mib << 18, np.float32)
        b = np.empty_like(a)
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            b[:] = a
            ts.append(time.perf_counter() - t0)
        return round(2 * a.nbytes / float(np.median(ts)) / 1e9, 2)

    l3_mib = None
    try:
        with open("/sys/devices/system/cpu/cpu0/cache/index3/size") as f:
            txt = f.read().strip()
        if txt.endswith("K"):
            l3_mib = round(int(txt[:-1]) / 1024, 1)
        elif txt.endswith("M"):
            l3_mib = float(txt[:-1])
    except (OSError, ValueError):
        pass  # unexpected sysfs content: report null, not a dead leg
    return {
        "host_membw_copy_cached_gbps": copy_gbps(8),
        "host_membw_copy_dram_gbps": copy_gbps(256),
        "host_l3_mib": l3_mib,
        "host_cores": os.cpu_count(),
    }


def _allreduce_child(sizes_csv: str) -> int:
    """Subprocess leg: the same measurement on an 8-device virtual CPU
    mesh — exercises the real multi-device collective path (GSPMD
    all-reduce over 8 shards) when the parent's chip count is 1. CPU
    numbers measure the collective's code path, not ICI — the keys are
    suffixed accordingly by main(). ``sizes_csv`` is a comma-separated
    byte-size list; all sizes' keys merge into one JSON line so the
    default bench emits the BASELINE config-3 curve, not one point."""
    from mpi_tpu.utils.platform import force_platform

    force_platform("cpu", 8)
    merged: dict = {}
    for s in sizes_csv.split(","):
        merged.update(measure_allreduce(int(s), chain=3))
        # Flush after every size: the parent keeps the LAST complete
        # JSON line, so a mid-curve kill (leg budget) still yields
        # every size that finished instead of nothing.
        print(json.dumps(merged), flush=True)
    # One int8-compressed point alongside the float curve: the wire
    # moves ~4x fewer bytes (parallel/quantized.py) — on a real
    # interconnect that is the headline; on the virtual CPU mesh it
    # proves the compiled path and gives a same-box ratio. This point
    # is FORCED past the dispatch gate; the gate keys beside it record
    # that the recommended path (allreduce_compressed) would NOT use
    # quantization here (measured: 3-10x slower than plain at every
    # size on this fabric, QUANTIZED_MIN_BYTES["cpu"] = never).
    import jax

    from mpi_tpu.parallel import QUANTIZED_MIN_BYTES, quantized_eligible

    # Curve diagnosis (round-4 verdict weak #2): record the host's
    # memory hierarchy beside the curve, and per-size implied DRAM
    # traffic (per_op * dram_copy_bw / payload). On the 1-core virtual
    # mesh the busbw "cliff" past 32 MiB is the L3 -> DRAM transition,
    # not an algorithm defect — see _host_membw_probe's docstring.
    merged.update(_host_membw_probe())
    dram = merged.get("host_membw_copy_dram_gbps") or 0.0
    if dram:
        for s in (int(v) for v in sizes_csv.split(",")):
            us = merged.get(f"allreduce_{_size_label(s)}_p50_us")
            if us:
                merged[f"allreduce_{_size_label(s)}_dram_traffic_x"] = \
                    round((us / 1e6) * dram * 1e9 / s, 2)
        merged["allreduce_curve_note"] = (
            "virtual 8-device mesh = 1 physical core + shared "
            f"{merged.get('host_l3_mib')} MiB L3; busbw above the L3 "
            "working-set boundary is DRAM-bound (see "
            "host_membw_copy_dram_gbps and the per-size "
            "_dram_traffic_x keys); psum measured fastest at every "
            "size vs ring/tree/chunked (docs/PERF_NOTES.md)")
    print(json.dumps(merged), flush=True)
    merged.update(measure_allreduce(1 << 20, chain=3, quantized=True))
    merged["qallreduce_forced"] = True
    # The dispatcher judges the PER-RANK vector it sees inside
    # shard_map — the 1 MiB label counts all 8 ranks' contributions,
    # so the gate's verdict is recorded for 1 MiB / 8.
    merged["qallreduce_eligible_1MiB"] = quantized_eligible(
        (1 << 20) // 8)
    merged["qallreduce_crossover_bytes"] = QUANTIZED_MIN_BYTES.get(
        jax.default_backend())
    print(json.dumps(merged))
    return 0


def allreduce_sweep(min_bytes: int = 1 << 10, max_bytes: int = 256 << 20,
                    ) -> None:
    """BASELINE.json config 3: bandwidth table 1 KiB → 256 MiB, stderr."""
    import jax

    n = len(jax.devices())
    print(f"# allreduce float32 sweep, {n} device(s)", file=sys.stderr)
    print(f"{'bytes':>12}  {'p50 us':>10}  {'algbw GB/s':>10}  "
          f"{'busbw GB/s':>10}", file=sys.stderr)
    size = min_bytes
    while size <= max_bytes:
        r = measure_allreduce(size)
        lb = _size_label(size)
        print(f"{size:>12}  {r.get(f'allreduce_{lb}_p50_us', '-'):>10}  "
              f"{r[f'allreduce_{lb}_gbps'] or '-':>10}  "
              f"{r[f'allreduce_{lb}_busbw_gbps'] or '-':>10}",
              file=sys.stderr)
        size *= 4


# --------------------------------------------------------------------------
# Bounce: the reference's method, both backends measured in THIS run
# --------------------------------------------------------------------------

def _bounce_pingpong(rank: int, msg) -> list:
    """The reference's even/odd ping-pong (bounce.go:85-112), shared by
    every transport leg: rank 0 times WARMUP+REPS round-trips and
    integrity-checks each echo; rank 1 echoes. Returns rank 0's
    post-warmup round-trip seconds ([] on rank 1)."""
    import mpi_tpu

    times: list = []
    for i in range(BOUNCE_WARMUP + BOUNCE_REPS):
        if rank == 0:
            t0 = time.perf_counter()
            mpi_tpu.send(msg, 1, i)
            echo = mpi_tpu.receive(source=1, tag=i)
            dt = time.perf_counter() - t0
            if echo != msg:
                raise RuntimeError("bounce echo mismatch")
            if i >= BOUNCE_WARMUP:
                times.append(dt)
        else:
            got = mpi_tpu.receive(source=0, tag=i)
            mpi_tpu.send(got, 0, i)
    return times


def bounce_xla(size: int = BOUNCE_SIZE) -> float:
    """Mean round-trip µs, 2 xla-driver ranks in one process (in-process
    rendezvous; the intra-host fast path, not a device transfer)."""
    import mpi_tpu
    from mpi_tpu.backends.xla import XlaNetwork, run_spmd

    msg = os.urandom(size)
    times: list = []

    def main():
        mpi_tpu.init()
        times.extend(_bounce_pingpong(mpi_tpu.rank(), msg))
        mpi_tpu.finalize()

    net = XlaNetwork(n=2, oversubscribe=True)
    run_spmd(main, net=net)
    return 1e6 * sum(times) / len(times)


def _bounce_device_child(size: int = BOUNCE_SIZE) -> int:
    """Subprocess leg: device-array ping-pong between 2 ranks on 2
    *distinct* devices of a virtual 8-device CPU mesh. The payload is a
    committed single-device jax.Array, so the facade's send() lowers to
    the DevicePipe's compiled ppermute program (parallel/p2p.py) — the
    tagged-p2p data path with no host round-trip of the payload — and
    each round-trip is two compiled ICI hops plus the rendezvous
    handshake. Prints mean round-trip µs as JSON."""
    from mpi_tpu.utils.platform import force_platform

    force_platform("cpu", 8)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi_tpu
    from mpi_tpu.backends.xla import XlaNetwork, run_spmd

    elems = max(1, size // 4)
    base = jnp.asarray(
        np.random.default_rng(7).standard_normal(elems), jnp.float32)
    times: list = []

    def main():
        mpi_tpu.init()
        r = mpi_tpu.rank()
        msg = jax.device_put(base, jax.devices()[0]) if r == 0 else None
        for i in range(BOUNCE_WARMUP + BOUNCE_REPS):
            if r == 0:
                t0 = time.perf_counter()
                mpi_tpu.send(msg, 1, i)
                echo = mpi_tpu.receive(source=1, tag=i)
                dt = time.perf_counter() - t0
                if not isinstance(echo, jax.Array) or \
                        not bool(jnp.array_equal(echo, msg)):
                    raise RuntimeError("device bounce echo mismatch")
                if i >= BOUNCE_WARMUP:
                    times.append(dt)
            else:
                got = mpi_tpu.receive(source=0, tag=i)
                mpi_tpu.send(got, 0, i)
        mpi_tpu.finalize()

    run_spmd(main, net=XlaNetwork(n=2))
    print(json.dumps(
        {"bounce_device_us": round(1e6 * sum(times) / len(times), 1),
         "bounce_device_bytes": elems * 4}))
    return 0


def bounce_device(size: int = BOUNCE_SIZE) -> dict:
    """Run the device-array bounce in a subprocess (it needs a multi-
    device platform pinned before JAX initializes) and return its keys."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--_bounce-device-child", str(size)],
        capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        raise RuntimeError(f"device bounce child failed: "
                           f"{proc.stderr[-500:]}")
    rec = _last_json(proc.stdout)
    if rec is None:
        raise RuntimeError("device bounce child printed no JSON")
    return rec


def _bounce_tcp_child() -> int:
    """Child rank of the TCP bounce (spawned via the real launcher ABI:
    --mpi-addr/--mpi-alladdr flags injected by launch()).
    MPI_TPU_BOUNCE_SIZE overrides the payload (the large-payload leg
    that evidences the zero-copy send path uses 64 MiB)."""
    import mpi_tpu

    try:
        size = int(os.environ.get("MPI_TPU_BOUNCE_SIZE", BOUNCE_SIZE))
    except ValueError:
        size = BOUNCE_SIZE
    mpi_tpu.init()
    r = mpi_tpu.rank()
    times = _bounce_pingpong(r, os.urandom(size) if r == 0 else None)
    mpi_tpu.finalize()
    if r == 0:
        out = os.environ.get("MPI_TPU_BENCH_OUT")
        if out:
            with open(out, "w") as f:
                f.write(str(1e6 * sum(times) / len(times)))
    return 0


def bounce_tcp(proto: str = "tcp", port_base: int = 6200,
               timeout: float = 30.0,
               size: Optional[int] = None,
               metrics_out: Optional[str] = None) -> float:
    """Mean round-trip µs for the socket driver, 2 real processes —
    the reference's own transport method (bounce.go:85-112),
    re-measured every run so the headline's comparison can never go
    stale (VERDICT round-1 item 8). ``proto="shm"`` runs the identical
    two-process ping-pong over the native shared-memory rings instead
    of loopback TCP (the launcher's port-derived addresses become
    opaque ring ids)."""
    import tempfile
    import uuid

    from mpi_tpu.launch.mpirun import launch

    with tempfile.NamedTemporaryFile("r", suffix=".bounce") as f:
        env = dict(os.environ)
        env["MPI_TPU_BENCH_OUT"] = f.name
        if size is not None:
            # Per-child env, never global os.environ: a process-wide
            # mutation would leak the large size into the SMALL bounce
            # legs' children (and clobber a user's own setting).
            env["MPI_TPU_BOUNCE_SIZE"] = str(size)
        # Children never touch the accelerator — keep them off the chip
        # the parent is benchmarking.
        env["JAX_PLATFORMS"] = "cpu"
        if metrics_out is not None:
            # Observe-layer artifact (docs/OBSERVABILITY.md): each rank
            # writes its --mpi-metrics-out JSON at finalize; the caller
            # digests it into the BENCH record. Tracing rides along so
            # the artifact carries the per-peer wire byte counters —
            # this launch is SEPARATE from the timed bounce legs, so
            # the span overhead never touches the committed latencies.
            env["MPI_TPU_METRICS_OUT"] = metrics_out
            env["MPI_TPU_TRACE"] = "1"
        args = ["--_bounce-child"]
        kwargs = {}
        if proto != "tcp":
            args += ["--mpi-protocol", proto]
            # Unique password → unique shm session key: concurrent
            # bench/test runs on one box can't collide on ring names.
            kwargs["password"] = uuid.uuid4().hex
        rc = launch(2, os.path.abspath(__file__), args,
                    port_base=port_base, timeout=timeout, env=env,
                    **kwargs)
        if rc != 0:
            raise RuntimeError(f"{proto} bounce children failed rc={rc}")
        return float(f.read() or "nan")


def bounce_metrics_digest(port_base: int = 6420) -> dict:
    """One extra small-message TCP bounce with ``--mpi-metrics-out``
    live; digests rank 0's artifact (facade op p50/p99, per-peer wire
    rate) into BENCH keys — the observe layer's machine-readable
    output folded into the round, per ISSUE 8."""
    import tempfile

    from mpi_tpu.observe import metrics as obs_metrics

    with tempfile.TemporaryDirectory() as td:
        pattern = os.path.join(td, "metrics-{rank}.json")
        bounce_tcp(port_base=port_base, metrics_out=pattern)
        with open(os.path.join(td, "metrics-0.json")) as f:
            doc = json.load(f)
        obs_metrics.validate(doc)
        keys = {}
        for op in ("send", "receive"):
            st = doc["ops"].get(op)
            if st:
                keys[f"bounce_metrics_{op}_p50_us"] = round(
                    st["p50_us"], 1)
                keys[f"bounce_metrics_{op}_p99_us"] = round(
                    st["p99_us"], 1)
        tx = sum(p.get("tx_bytes", 0) for p in doc["peers"].values())
        keys["bounce_metrics_tx_bytes_rank0"] = int(tx)
        return keys


# --------------------------------------------------------------------------
# Entry
# --------------------------------------------------------------------------

def _suffix_allreduce_keys(rec: dict) -> dict:
    """Measurement keys get the ``_cpu8mesh`` provenance suffix; the
    dispatch-gate verdicts and the host/curve diagnosis keys (r4 weak
    #2) ride along unsuffixed (they describe the fabric and the box,
    not a cpu8mesh measurement)."""
    out = {f"{k}_cpu8mesh": v for k, v in rec.items()
           if not k.startswith("host_")
           and (k.endswith("_gbps") or k.endswith("_p50_us")
                or k.endswith("_dram_traffic_x"))}
    for k in ("qallreduce_forced", "qallreduce_eligible_1MiB",
              "qallreduce_crossover_bytes", "allreduce_curve_note",
              "host_membw_copy_cached_gbps",
              "host_membw_copy_dram_gbps", "host_l3_mib", "host_cores"):
        if k in rec:
            out[k] = rec[k]
    return out


def _allreduce_on_virtual_mesh(sizes) -> dict:
    """Run the allreduce measurement (one or many sizes) in a subprocess
    pinned to an 8-device virtual CPU mesh and return its keys suffixed
    with ``_cpu8mesh`` — the multi-device collective path, measured even
    when this process owns a single chip.

    The child flushes a cumulative JSON line after every size; each is
    re-emitted (suffixed) on THIS process's stdout as it arrives, so
    when the leg parent SIGKILLs the whole process group on a blown
    budget, its last-JSON salvage still recovers every size that had
    completed — the flush would be dead weight if the lines only
    reached this pipe. stderr is inherited (it flows up into the leg
    parent's captured stderr), which also avoids a second-pipe
    deadlock while stdout is being streamed."""
    import subprocess

    if isinstance(sizes, int):
        sizes = [sizes]
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--_allreduce-child", ",".join(str(s) for s in sizes)],
        stdout=subprocess.PIPE, stderr=None, text=True)
    last: Optional[dict] = None
    assert proc.stdout is not None
    for line in proc.stdout:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        last = _suffix_allreduce_keys(rec)
        print(json.dumps(last), flush=True)
    try:
        rc = proc.wait(timeout=60)  # stdout hit EOF: child is exiting
    except subprocess.TimeoutExpired:
        # Slow teardown (mesh runtime threads). The measurements are
        # already streamed — keep them rather than crashing the leg.
        proc.kill()
        proc.wait()
        rc = 0 if last is not None else -1
    if rc != 0:
        raise RuntimeError(f"allreduce child failed (rc={rc})")
    if last is None:
        raise RuntimeError("allreduce child printed no JSON")
    return last


# Tiny-shape kwargs for --smoke runs (CI exercises the full harness
# path in seconds; the smoke key marks the line).
_SMOKE_TRAIN = dict(d_model=64, n_layers=2, n_heads=4, d_ff=128,
                    vocab=128, batch=2, seq=64, short=1, long=3)
_SMOKE_LONGCTX = dict(seq=128, d_model=64, n_heads=4, n_layers=2,
                      d_ff=128, vocab=128, short=1, long=3)
_SMOKE_DECODE = dict(d_model=64, n_layers=2, n_heads=4, d_ff=128,
                     vocab=128, batch=2, prompt_len=16, short=4, long=12)
_SMOKE_SSM = dict(d_model=48, n_layers=1, d_state=16, d_ff=96,
                  vocab=128, batch=2, seq=32, prompt_len=4, short=2,
                  long=5, train_short=1, train_long=2)


def _device_leg_impl(name: str, smoke: bool) -> dict:
    """One named device leg, run to completion in THIS process (the
    ``--_device-leg`` child entry). Returns the leg's result keys."""
    if name == "train":
        return measure_train_step(**(_SMOKE_TRAIN if smoke else {}))
    if name == "long_ctx":
        return measure_long_context(**(_SMOKE_LONGCTX if smoke else {}))
    if name == "decode":
        return measure_decode(**(_SMOKE_DECODE if smoke else {}))
    if name == "decode_int8":
        return measure_decode(int8=True,
                              **(_SMOKE_DECODE if smoke else {}))
    if name == "ssm":
        return measure_ssm(**(_SMOKE_SSM if smoke else {}))
    if name == "allreduce":
        ar_size = (1 << 20) if smoke else (256 << 20)
        # VERDICT r3 item 6: the BASELINE config-3 curve (1 KiB →
        # 256 MiB) is recorded IN FULL even on smoke runs — the
        # large-payload behavior must be visible in every round's
        # committed artifact.
        # (Three rounds of smoke lines capped at 1 MiB hid it. The
        # former 32 MiB ring/tree crossover is gone — ring dispatch
        # defaults off since round 5, collectives_generic.py.)
        curve_sizes = [1 << 10, 32 << 10, 1 << 20, 8 << 20, 32 << 20,
                       64 << 20, 256 << 20]
        ar = measure_allreduce(ar_size)
        if ar.get("allreduce_devices") == 1:
            # Single chip: the in-process collective is the identity
            # (keys are null); measure the real multi-device path on a
            # virtual 8-device mesh instead — the full compact curve.
            ar.update(_allreduce_on_virtual_mesh(curve_sizes))
        else:
            for s in curve_sizes:
                if s != ar_size:
                    ar.update(measure_allreduce(s))
        return ar
    raise ValueError(f"unknown device leg {name!r}")


def _run_device_leg(name: str, timeout_s: float, smoke: bool,
                    platform: Optional[str]) -> dict:
    """Run one device leg in a SUBPROCESS with its own deadline.

    Why a subprocess: a jax call stuck on an unresponsive device
    blocks in C — uninterruptible from Python. Isolating each leg means
    a hang costs one leg's budget, not every remaining measurement,
    and the parent stays off JAX so each child in turn can hold the
    chip. The persistent compile cache (placed in main) keeps
    per-process recompiles cheap."""
    import signal
    import subprocess

    cmd = [sys.executable, os.path.abspath(__file__),
           "--_device-leg", name]
    if smoke:
        cmd.append("--smoke")
    if platform:
        cmd += ["--platform", platform]
    # start_new_session: the leg child may spawn its own children (the
    # allreduce leg's virtual-mesh subprocess); a timeout must kill the
    # whole process GROUP or an orphaned grandchild keeps saturating
    # the CPU under the later host-side timing legs.
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:  # raced its own exit
            pass
        out, err = proc.communicate()
        if err:
            sys.stderr.write(err)  # full traceback into the round log
        lines = (err or "").strip().splitlines()
        tail = lines[-1][:200] if lines else ""
        rec = {f"{name}_error":
               f"leg timed out after {timeout_s:.0f}s (device hang); "
               f"killed. last stderr: {tail}"}
        # Salvage anything the child banked before hanging — the train
        # leg flushes its headline keys before the breakdown's extra
        # compiles, so a mid-breakdown hang still yields the MFU.
        banked = _last_json(out)
        if banked is not None:
            rec.update(banked)
        return rec
    if err:
        sys.stderr.write(err)  # leg logs flow into the round log
    if proc.returncode != 0:
        lines = (err or "").strip().splitlines()
        return {f"{name}_error":
                f"leg child rc={proc.returncode}: "
                f"{lines[-1][:250] if lines else 'no stderr'}"}
    rec = _last_json(out)
    if rec is None:
        return {f"{name}_error": "leg child printed no JSON"}
    return rec


# Measurements already completed this run — the watchdog ships them in
# its error line so a late device hang doesn't discard the host-side
# legs that did finish.
_PARTIALS: dict = {}


# Stdout-line whitelist, importance-ordered. The driver parses the one
# stdout JSON line from a bounded capture window: BENCH_r03's 65-key
# ~4 KB line overflowed it and the round recorded `parsed: null`
# (VERDICT r3 weak#6). The compact line carries the headline +
# per-leg representative numbers and stays under _LINE_BUDGET bytes;
# every key (curves, tune tables, model shapes, tier splits) lands in
# the committed BENCH_FULL.json instead.
_COMPACT_KEYS = (
    "metric", "value", "unit", "vs_baseline", "smoke",
    "platform", "device_kind",
    "train_step_ms", "train_tokens_per_s", "train_achieved_tflops",
    "peak_tflops", "flash_block_q", "flash_block_k",
    "train_breakdown_attn_pct", "train_breakdown_ffn_pct",
    "train_breakdown_opt_pct", "train_breakdown_rest_pct",
    "allreduce_256MiB_gbps", "allreduce_256MiB_busbw_gbps",
    "allreduce_1MiB_busbw_gbps", "allreduce_32MiB_busbw_gbps",
    "allreduce_1MiB_busbw_gbps_cpu8mesh",
    "allreduce_32MiB_busbw_gbps_cpu8mesh",
    "qallreduce_crossover_bytes",
    "long_ctx_tokens_per_s", "long_ctx_mfu_pct",
    "decode_tokens_per_s", "decode_int8_tokens_per_s",
    "ssm_train_tokens_per_s", "ssm_decode_tokens_per_s",
    "bounce_tcp_us", "bounce_shm_us", "bounce_xla_us",
    "bounce_speedup", "bounce_device_us",
    "bounce64m_tcp_gbps", "bounce64m_shm_gbps",
    "hybrid_allreduce_1MiB_p50_us_4x8",
    "regressions_count",
    "timing_method", "loss_first_step", "error",
)
_LINE_BUDGET = 1600  # bytes; safely inside the driver's capture tail

# --compare BASE.json: explicit baseline artifact for the regression
# check, overriding the committed-HEAD default (tools/bench_gate.py and
# the nightly workflow diff two arbitrary rounds this way).
_COMPARE_BASE: Optional[str] = None


def _regression_check(full: dict, prior: dict) -> None:
    """Mutate ``full`` with a self-regression verdict against the last
    committed artifact (round-4 verdict item 3: shm silently went
    1.48x -> 1.0x and nothing flagged it).

    Like-for-like only: platform and smoke flag must match, else the
    comparison is recorded as incomparable. Direction is derived from
    the key name (throughput-like keys regress downward, latency-like
    keys upward); diagnostic constants (peak tables, provenance, the
    train_breakdown_* split) are skipped. Threshold is
    MPI_TPU_BENCH_REGRESS_PCT (default 30% — the 1-core bench box
    shows >25% rerun noise on loaded legs, so a tighter bar would cry
    wolf; a flagged key means "rerun before trusting", not proof of a
    code regression).

    Materiality floor (non-TPU lines): a key is only compared when the
    time it measures is >= 2 ms — calibrated by rerunning the bench on
    an unchanged tree, where every spurious flag was a sub-2 ms
    micro-timing (32 KiB allreduce hops, smoke-shape per-token times)
    on the time-sliced 1-core box. Throughput keys borrow the
    magnitude of their latency sibling (same key prefix:
    decode_tokens_per_s -> decode_ms_per_token, allreduce_X_gbps ->
    allreduce_X_p50_us); a throughput key with no sibling is always
    compared. TPU lines skip the floor: differenced on-chip timings
    are stable, and tpu-vs-tpu comparisons are too rare to suppress."""
    if (prior.get("platform") != full.get("platform")
            or bool(prior.get("smoke")) != bool(full.get("smoke"))):
        full["regressions_vs"] = (
            f"incomparable: prior platform={prior.get('platform')}/"
            f"smoke={prior.get('smoke')}")
        return
    try:
        thresh = float(
            os.environ.get("MPI_TPU_BENCH_REGRESS_PCT", "30")) / 100
    except ValueError:
        thresh = 0.30  # malformed env must not disable the check
    floor_ms = 0.0 if full.get("platform") == "tpu" else 2.0

    def _base(k):
        """Key with provenance suffixes stripped, so classification
        sees the measurement name (allreduce_8MiB_p50_us_cpu8mesh is
        a latency key; hybrid_*_p50_us_4x8 likewise)."""
        for suf in ("_cpu8mesh", "_4x8"):
            if k.endswith(suf):
                k = k[: -len(suf)]
        return k

    def _magnitude_ms(k, v):
        """Milliseconds measured by a latency-like key, else None."""
        k = _base(k)
        if k.endswith("_us"):
            return v / 1e3
        if k.endswith("_ms") or "ms_per" in k:
            return v
        return None

    def _material(k, prev, now):
        mag = _magnitude_ms(k, max(prev, now))
        if mag is not None:
            return mag >= floor_ms
        bk = _base(k)
        # A ratio (speedup) is only trustworthy when EVERY component
        # timing is macro — bounce_speedup's denominator is a ~50 us
        # xla ping, pure jitter — while a plain throughput key needs
        # just its own latency partner to qualify. "speedup" is
        # matched as a substring: bounce_shm_speedup_vs_tcp ends in
        # "_vs_tcp", not "_speedup".
        if "_speedup" in bk:
            pref, agg = bk.split("_speedup")[0], min
        else:
            for suf in ("_tokens_per_s", "_busbw_gbps", "_gbps"):
                if bk.endswith(suf):
                    pref, agg = bk[: -len(suf)], max
                    break
            else:
                return True  # no time sibling: always compare
        sibs = [_magnitude_ms(kk, max(prior[kk], full[kk]))
                for kk in full
                if _base(kk).startswith(pref)
                and not _base(kk).endswith("_spread_us")  # diagnostic
                and isinstance(full.get(kk), (int, float))
                and isinstance(prior.get(kk), (int, float))
                and _magnitude_ms(kk, 1) is not None]
        if sibs:
            return agg(sibs) >= floor_ms
        return True

    regs, suppressed = [], []
    for k, now in list(full.items()):
        if isinstance(now, bool) or not isinstance(now, (int, float)):
            continue
        prev = prior.get(k)
        if isinstance(prev, bool) or not isinstance(prev, (int, float)):
            continue
        if prev <= 0 or now <= 0:
            continue
        b = _base(k)
        if ("peak" in b or "last_tpu" in b or b.endswith("_regressed")
                or b.startswith("train_breakdown_")
                or b.startswith("host_")  # box diagnosis, not a result
                or b.endswith("_dram_traffic_x")
                or b.endswith("_spread_us")
                or "_skew_" in b  # straggler diagnostics, not results
                # A/B of the DEMOTED pipeline lever: measured
                # noise-dominated on this box (PERF_NOTES.md) — its
                # swing is not a regression signal.
                or "_pipeline" in b):
            continue
        if ("mfu" in b or any(t in b for t in
                              ("tokens_per_s", "gbps", "speedup",
                               "tflops"))):
            worse = now < prev * (1 - thresh)
        elif (b.endswith("_us") or b.endswith("_ms")
              or "ms_per_token" in b):
            worse = now > prev * (1 + thresh)
        else:
            continue
        if not worse:
            continue
        if _material(k, prev, now):
            regs.append({"key": k, "prev": prev, "now": now,
                         "ratio": round(now / prev, 3)})
            full[k + "_regressed"] = True
        else:
            # Sub-floor drifts are noise-dominated on this box (the
            # floor's calibration data is in the docstring), but they
            # must stay VISIBLE — round 4's lesson was a silent shm
            # drift, and a suppressed entry with the spread context
            # beats an absent one.
            suppressed.append({"key": k, "prev": prev, "now": now,
                               "ratio": round(now / prev, 3),
                               "reason": "sub-floor magnitude "
                                         "(noise-dominated)"})
    full["regressions"] = regs
    full["regressions_count"] = len(regs)
    full["regressions_suppressed"] = suppressed
    full["regressions_vs"] = "committed BENCH_FULL.json (git HEAD)"


def _committed_artifact(repo_dir: str) -> Optional[dict]:
    """The LAST COMMITTED ``BENCH_FULL.json`` (git HEAD), the stable
    baseline for :func:`_regression_check`. The on-disk file is wrong
    for this: _emit itself overwrites it every run, so
    comparing against disk would reset the baseline on every rerun and
    launder exactly the cross-round drifts the check exists to catch.
    None when git or the committed file is unavailable (fresh clone,
    first round): then there is nothing trustworthy to compare
    against, and no verdict is recorded rather than a misleading
    one."""
    import subprocess

    try:
        proc = subprocess.run(
            ["git", "-C", repo_dir, "show", "HEAD:BENCH_FULL.json"],
            capture_output=True, text=True, timeout=20)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if proc.returncode != 0:
        return None
    try:
        rec = json.loads(proc.stdout)
    except ValueError:
        return None
    return rec if isinstance(rec, dict) else None


def _emit(full: dict) -> None:
    """Write the complete result dict to ``BENCH_FULL.json`` and print
    the compact headline-first JSON line to stdout (the one-line driver
    contract). Key order in the compact line IS importance order, so if
    a reader's window truncates anything it is the tail, never the
    headline."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_FULL.json")
    prior: Optional[dict] = None
    if _COMPARE_BASE is not None:
        try:
            with open(_COMPARE_BASE) as f:
                rec = json.load(f)
            prior = rec if isinstance(rec, dict) else None
        except (OSError, ValueError):
            prior = None
        if prior is None:
            full["regressions_vs"] = (
                f"unreadable --compare base: {_COMPARE_BASE}")
    else:
        prior = _committed_artifact(os.path.dirname(path))
    if prior is not None:
        _regression_check(full, prior)
        if _COMPARE_BASE is not None and "regressions" in full:
            # The incomparable early-return keeps its own verdict; only
            # a completed check gets relabelled with the explicit base.
            full["regressions_vs"] = f"--compare {_COMPARE_BASE}"
    try:
        with open(path, "w") as f:
            json.dump(full, f, indent=1)
            f.write("\n")
        full_note = os.path.basename(path)
    except OSError as exc:  # compact line still appears
        full_note = f"unwritable: {str(exc)[:80]}"
    # The full-file pointer sits inside the protected head so trimming
    # can never drop it (or push the line back over budget by
    # re-adding it).
    compact = {k: full[k] for k in _COMPACT_KEYS[:5] if k in full}
    compact["full_results"] = full_note
    for k in _COMPACT_KEYS[5:]:
        if k in full:
            compact[k] = full[k]
    # Leg errors always surface (truncated) — they explain absent keys.
    for k, v in full.items():
        if k.endswith("_error") and k not in compact:
            compact[k] = str(v)[:90]
    s = json.dumps(compact)
    if len(s) > _LINE_BUDGET:
        # Trim tail-first (insertion order = importance order), but
        # never the headline quadruple + provenance head.
        keys = list(compact)
        while len(s) > _LINE_BUDGET and len(keys) > 8:
            compact.pop(keys.pop())
            compact["truncated"] = True
            s = json.dumps(compact)
    print(s, flush=True)


def _install_watchdog(seconds: float) -> threading.Timer:
    """Guarantee the one-JSON-line stdout contract even if the device
    hangs: a jax call stuck on an unresponsive TPU blocks forever
    and cannot be interrupted from Python, so after ``seconds`` this
    prints an error-marked JSON line (carrying any measurements that DID
    complete) and hard-exits (``os._exit`` — the stuck runtime threads
    cannot be joined). Tune/disable with ``MPI_TPU_BENCH_DEADLINE_S``
    (0 disables)."""
    def fire() -> None:
        line = {
            "metric": "train_step_mfu", "value": None, "unit": "pct",
            "vs_baseline": None,
            "error": f"bench watchdog fired after {seconds:.0f}s — "
                     f"device unresponsive",
        }
        line.update(_PARTIALS)
        _emit(line)
        os._exit(3)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def main() -> int:
    if "--_bounce-child" in sys.argv:
        return _bounce_tcp_child()
    if "--_bounce-device-child" in sys.argv:
        idx = sys.argv.index("--_bounce-device-child")
        return _bounce_device_child(int(sys.argv[idx + 1]))
    if "--_allreduce-child" in sys.argv:
        idx = sys.argv.index("--_allreduce-child")
        return _allreduce_child(sys.argv[idx + 1])
    if "--_hybrid-allreduce-child" in sys.argv:
        return _hybrid_allreduce_child()
    global _COMPARE_BASE
    if "--compare" in sys.argv:
        idx = sys.argv.index("--compare")
        if idx + 1 >= len(sys.argv):
            print("usage: bench.py [--compare BASE.json] ...",
                  file=sys.stderr)
            return 2
        _COMPARE_BASE = sys.argv[idx + 1]
    # --platform cpu[:N] pins the JAX platform before any device query
    # (the tests' path); with no flag the device legs need the real chip.
    from mpi_tpu.utils.platform import compile_cache_dir, force_platform

    platform_arg: Optional[str] = None
    if "--platform" in sys.argv:
        idx = sys.argv.index("--platform")
        if idx + 1 >= len(sys.argv):
            print("usage: bench.py [--platform NAME[:NUM_DEVICES]]"
                  " [--suite] [--smoke]",
                  file=sys.stderr)
            return 2
        platform_arg = sys.argv[idx + 1]
        name, _, count = platform_arg.partition(":")
        if not force_platform(name, int(count) if count else None):
            raise RuntimeError(
                f"--platform {name} requested but a JAX backend is already "
                f"initialized on another platform")

    # --smoke: tiny shapes so CI can exercise the full harness path on
    # CPU in seconds; the real run uses the defaults on the real chip.
    smoke = "--smoke" in sys.argv
    if "--_device-leg" in sys.argv:
        # Child entry for one isolated device leg (after --platform so
        # the parent can pin the child's platform explicitly).
        idx = sys.argv.index("--_device-leg")
        if platform_arg is None:
            import jax

            dev = jax.devices()[0]
            if dev.platform != "tpu":
                print(f"bench: device legs need a TPU; JAX found "
                      f"{dev.platform!r}. --platform cpu --smoke runs the "
                      f"harness on the CPU.", file=sys.stderr)
                return 1
        print(json.dumps(_device_leg_impl(sys.argv[idx + 1], smoke)))
        return 0

    deadline = float(os.environ.get("MPI_TPU_BENCH_DEADLINE_S", "2400"))

    watchdog = _install_watchdog(deadline) if deadline > 0 else None
    deadline_end = time.monotonic() + deadline if deadline > 0 else None

    # Subprocess legs (device legs + virtual-mesh allreduce) share one
    # persistent compilation cache, so per-process isolation doesn't
    # pay per-process compiles.
    compile_cache_dir()

    # Every leg runs under _leg(): a completed leg lands in _PARTIALS
    # immediately (the watchdog's error line carries whatever finished
    # before a hang), and a FAILED leg records a `<leg>_error` key and
    # the remaining legs still run, so the one JSON line always appears
    # with everything that did measure — and the exit code says a
    # device leg failed.
    result: dict = {}

    def _leg(label, fn):
        t0 = time.monotonic()
        try:
            r = fn()
        except BaseException as exc:  # noqa: BLE001 - line must appear
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                raise
            r = {f"{label}_error":
                 f"{type(exc).__name__}: {str(exc)[:300]}"}
            print(f"bench: {label} leg failed: {exc}", file=sys.stderr)
        # Leg-by-leg wall clock on stderr: when a run blows the
        # watchdog, the log shows exactly where the time went.
        print(f"bench: leg {label} finished in "
              f"{time.monotonic() - t0:.1f}s", file=sys.stderr)
        result.update(r)
        _PARTIALS.update(r)
        return r

    def bounce_legs():
        # Each sub-leg flushes to _PARTIALS as it completes, so a later
        # sub-leg failing cannot discard numbers already measured.
        #
        # Median of 3 LAUNCHES per transport, with the spread recorded:
        # on the 1-core bench box a two-process ping-pong is scheduler-
        # dominated and a single launch varies ~1.8x run-to-run
        # (measured: shm 1375-2421 us, tcp 1604-2243 us across 8
        # identical runs — round 4's "shm regressed to 1.0x" was this
        # noise, not code). The median launch makes the committed key
        # stable enough for _regression_check to be meaningful, and
        # the _spread_us keys let a reader judge any residual flag.
        try:
            launches = max(1, int(os.environ.get(
                "MPI_TPU_BENCH_BOUNCE_LAUNCHES", "3")))
        except ValueError:
            launches = 3  # malformed env must not cost the whole leg

        def median_bounce(proto, base):
            runs = sorted(
                bounce_tcp(proto=proto, port_base=base + 10 * i)
                for i in range(launches))
            return runs[len(runs) // 2], runs[-1] - runs[0]

        tcp_us, tcp_spread = median_bounce("tcp", 6200)
        keys = {"bounce_tcp_us": round(tcp_us, 1),
                "bounce_tcp_spread_us": round(tcp_spread, 1)}
        _PARTIALS.update(keys)
        try:
            shm_us, shm_spread = median_bounce("shm", 6300)
            # Same two-OS-process ping-pong as the TCP leg, frames
            # riding the native shared-memory rings: the like-for-like
            # transport comparison (codec + rendezvous on both sides).
            keys["bounce_shm_us"] = round(shm_us, 1)
            keys["bounce_shm_spread_us"] = round(shm_spread, 1)
            keys["bounce_shm_speedup_vs_tcp"] = round(tcp_us / shm_us, 1)
        except Exception as exc:  # noqa: BLE001 - leg optional
            keys["bounce_shm_error"] = str(exc)[:200]
        _PARTIALS.update(keys)
        try:
            xla_us = bounce_xla()
            keys["bounce_xla_us"] = round(xla_us, 1)
            keys["bounce_speedup"] = round(tcp_us / xla_us, 1)
        except Exception as exc:  # noqa: BLE001 - keep earlier numbers
            keys["bounce_xla_error"] = str(exc)[:200]
        _PARTIALS.update(keys)
        # Large-payload leg (round 5): one 64 MiB ping-pong per socket
        # protocol, tracking the zero-copy send path across rounds.
        # Like the config-3 curve, it runs FULL SIZE even on smoke.
        # NB the ABSOLUTE GB/s on the 1-core bench box is scheduler-
        # bound well below the path's measured one-way throughput
        # (PERF_NOTES: p2p tcp ~1.0, shm ~1.35 GB/s) — the cross-round
        # TREND of these keys is the signal, not the level. Effective
        # GB/s counts both directions of the round trip.
        big = 64 << 20
        for proto, base in (("tcp", 6360), ("shm", 6380)):
            try:
                us = bounce_tcp(proto=proto, port_base=base,
                                timeout=120.0, size=big)
                keys[f"bounce64m_{proto}_us"] = round(us, 1)
                keys[f"bounce64m_{proto}_gbps"] = round(
                    2 * big / (us / 1e6) / 1e9, 2)
            except Exception as exc:  # noqa: BLE001 - leg optional
                keys[f"bounce64m_{proto}_error"] = str(exc)[:200]
            _PARTIALS.update(keys)
        # Observe fold: the --mpi-metrics-out artifact of one extra
        # small-message launch, digested into the round (facade op
        # p50/p99 as the flight recorder measures them).
        try:
            keys.update(bounce_metrics_digest(port_base=6420))
        except Exception as exc:  # noqa: BLE001 - leg optional
            keys["bounce_metrics_error"] = str(exc)[:200]
        _PARTIALS.update(keys)
        return keys

    # Headline first: if anything later blows the watchdog, the
    # partial line must already carry the MFU (round-2 lesson: the
    # bounce legs ran first and a late hang would have left the
    # flagship number unmeasured). Each device leg runs in its own
    # subprocess with its own deadline (see _run_device_leg) and never
    # outlives the remaining watchdog budget — the one-line contract
    # holds even if every leg hangs. The allreduce leg carries the
    # BASELINE config-3 curve (1 KiB → 256 MiB, full even on smoke
    # runs — see _device_leg_impl) in the DEFAULT line — the driver
    # never passes --suite.
    # Leg ORDER is the degradation order: worst-case budgets sum past
    # the watchdog, and the skip logic sacrifices the tail — so the
    # headline (train MFU) and the north-star (allreduce curve,
    # BASELINE.json:5) run first, and the newest/most-optional legs
    # (int8 decode, ssm) absorb a slow run.
    budgets = {"train": 900.0, "allreduce": 600.0, "long_ctx": 650.0,
               "decode": 400.0, "decode_int8": 350.0, "ssm": 450.0}
    if smoke:
        budgets = {k: min(v, 200.0) for k, v in budgets.items()}
        # The full config-3 curve runs even in smoke (see the
        # allreduce leg) — give it room for the 256 MiB sizes.
        budgets["allreduce"] = 400.0
    leg_names = ("train", "allreduce", "long_ctx", "decode",
                 "decode_int8", "ssm")
    for leg_name in leg_names:
        if deadline_end is not None:
            remaining = deadline_end - time.monotonic() - 120.0
            if remaining < 45.0:
                rec = {f"{leg_name}_error":
                       "skipped: watchdog budget exhausted"}
                result.update(rec)
                _PARTIALS.update(rec)
                print(f"bench: leg {leg_name} skipped (watchdog budget "
                      f"exhausted)", file=sys.stderr)
                continue
            budget = min(budgets[leg_name], remaining)
        else:
            budget = budgets[leg_name]
        _leg(leg_name, lambda n=leg_name, b=budget:
             _run_device_leg(n, b, smoke, platform_arg))

    # Host-side legs: the parent never touches the real accelerator
    # (every device measurement above is a subprocess that needs the
    # chip to itself), so pin it to CPU before anything below can
    # lazily initialize a backend. The provenance key marks it:
    # bounce_xla/bounce_device always measure the host-side rendezvous
    # on the virtual CPU mesh.
    if platform_arg is None:
        force_platform("cpu", 8)
        rec = {"host_legs_platform": "cpu:8"}
        result.update(rec)
        _PARTIALS.update(rec)
    _leg("bounce", bounce_legs)
    _leg("bounce_device",
         lambda: bounce_device((1 << 14) if smoke else BOUNCE_SIZE))
    # BASELINE config 5: the hierarchical two-tier engine at 32
    # ranks (4 hosts x 8 locals), in the default line.
    _leg("hybrid_allreduce", measure_hybrid_allreduce)
    if "--suite" in sys.argv:
        _leg("sweep", lambda: allreduce_sweep() or {})

    # No MFU (a failed train leg, or the CPU path with no peak) is null,
    # never a number.
    mfu = result.pop("mfu_pct", None)
    line = {"metric": "train_step_mfu", "value": mfu, "unit": "pct",
            "vs_baseline": None if mfu is None
            else round(mfu / MFU_BASELINE_PCT, 3),
            # A smoke line measures the harness at tiny shapes, not the
            # framework — mark it unambiguously.
            "smoke": bool(smoke)}
    line.update(result)
    if watchdog is not None:
        watchdog.cancel()
    _emit(line)
    failed = [n for n in leg_names if f"{n}_error" in result]
    if failed:
        print(f"bench: device leg(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
