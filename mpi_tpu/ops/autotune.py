"""Flash-attention block-size autotuner.

The Pallas flash kernel's throughput on a given chip is dominated by
its ``(block_q, block_k)`` grid shape — the shipped 1024x1024 default
came from a sweep on v5e at s=4096 (``ops/attention.py`` has the
table), but the best shape shifts with sequence length, head count, head dim, and chip
generation. :func:`tune_flash_blocks` measures the real kernel
(forward or forward+backward) over a candidate grid ON THE CURRENT
BACKEND, registers the winner for the exact tuned shape
(:func:`mpi_tpu.ops.attention.register_tuned_blocks` — consulted at
trace time before the global default, so tuning one shape never
degrades another), and returns the full timing table so benchmarks can
report the kernel-level breakdown.

No reference analogue (btracey/mpi has no kernels); the method is the
bounce harness's discipline (/root/reference/examples/bounce/
bounce.go:85-152 — warm up, repeat, report the representative time)
applied to kernel configs.
"""

from __future__ import annotations

import json
import os
import statistics
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .attention import (_pick_block, flash_attention,
                        register_tuned_blocks)

__all__ = ["tune_flash_blocks", "DEFAULT_CANDIDATES"]

# Pallas TPU wants the trailing dims MXU/VPU-tileable: multiples of 128
# in both block axes. The grid covers skinny-q (decode-ish), square,
# and wide-k (long-context) shapes.
DEFAULT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (128, 128), (128, 256), (128, 512),
    (256, 256), (256, 512), (256, 1024),
    (512, 256), (512, 512), (512, 1024),
    (1024, 512),
)

# (shape key, backend) -> chosen (block_q, block_k); one sweep per
# distinct shape per process.
_cache: Dict[tuple, Tuple[int, int]] = {}


# Committed with the package: winners tuned on real hardware survive
# not just across processes but across checkouts/rounds, so a short
# device window spends its minutes measuring, never re-tuning.
_DEFAULT_CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "flash_tune_cache.json")


def _disk_cache_path() -> Optional[str]:
    """Cross-process winner cache. Defaults to the committed
    ``flash_tune_cache.json`` next to this module; override with
    ``MPI_TPU_TUNE_CACHE=path`` or disable with ``MPI_TPU_TUNE_CACHE=``
    (empty). A TPU sweep costs one kernel compile per candidate;
    persisting winners makes every later run free."""
    if "MPI_TPU_TUNE_CACHE" in os.environ:
        return os.environ["MPI_TPU_TUNE_CACHE"] or None
    return _DEFAULT_CACHE


def _disk_cache_load(key: tuple) -> Optional[Tuple[int, int]]:
    path = _disk_cache_path()
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            rec = json.load(f).get(repr(key))
        return (int(rec[0]), int(rec[1])) if rec else None
    except (OSError, ValueError, TypeError, KeyError, IndexError,
            AttributeError):
        # Any malformed cache content — wrong JSON shape included —
        # degrades to a re-sweep, never a crash.
        return None


def _disk_cache_store(key: tuple, best: Tuple[int, int]) -> None:
    path = _disk_cache_path()
    if not path:
        return
    try:
        data = {}
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
        data[repr(key)] = list(best)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f)
        os.replace(tmp, path)
    except (OSError, ValueError):
        pass  # best-effort; the in-process sweep result still applies


def _time_once(fn, *args) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def tune_flash_blocks(batch: int, seq: int, heads: int, head_dim: int,
                      *, kv_heads: Optional[int] = None,
                      seq_k: Optional[int] = None, causal: bool = True,
                      dtype=jnp.bfloat16,
                      candidates: Optional[Sequence[Tuple[int, int]]]
                      = None,
                      reps: int = 3, include_bwd: bool = True,
                      set_default: bool = True,
                      interpret: Optional[bool] = None):
    """Sweep flash block configs at the given attention shape; return
    ``(best_blocks, table)``.

    ``table`` is ``[{"block_q", "block_k", "ms"}, ...]`` sorted
    fastest-first (median of ``reps`` post-warmup runs of the jitted
    kernel — forward+backward when ``include_bwd``, the training
    shape). With ``set_default`` (the default) the winner is registered
    for the EXACT tuned ``(seq, seq_k)`` shape
    (:func:`mpi_tpu.ops.attention.register_tuned_blocks`), so
    default-block ``flash_attention`` calls at that shape — the
    transformer stack at the tuned sequence length — use it, while
    calls at other shapes keep the shipped global default (a winner
    shrunk to fit a short sequence must not degrade longer ones).
    Results are cached per (shape, candidates, backend): repeat calls
    are free.
    """
    kv = heads if kv_heads is None else kv_heads
    tk = seq if seq_k is None else seq_k
    cands = tuple(candidates) if candidates else DEFAULT_CANDIDATES
    # device_kind, not just the backend name: a persisted winner tuned
    # on one TPU generation must not be reused on another (the best
    # grid shifts with the chip — module doc).
    key = (batch, seq, tk, heads, kv, head_dim, causal, include_bwd,
           str(jnp.dtype(dtype)), jax.default_backend(),
           jax.devices()[0].device_kind, cands)
    if key not in _cache:
        disk = _disk_cache_load(key)
        if disk is not None:
            _cache[key] = disk
    if key in _cache:
        best = _cache[key]
        if set_default:
            register_tuned_blocks(seq, tk, *best)
        return best, []

    rng = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (batch, seq, heads, head_dim), dtype)
    k = jax.random.normal(kk, (batch, tk, kv, head_dim), dtype)
    v = jax.random.normal(kv_, (batch, tk, kv, head_dim), dtype)

    # Distinct preferences can collapse onto one effective grid at
    # short sequences (_pick_block shrinks to divide s) — dedupe on the
    # effective blocks so no config is compiled twice.
    effective: List[Tuple[int, int]] = []
    seen = set()
    for bq, bk in cands:
        eff = (_pick_block(seq, bq), _pick_block(tk, bk))
        if eff not in seen:
            seen.add(eff)
            effective.append(eff)

    def build(bq: int, bk: int):
        if include_bwd:
            def loss(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, causal, bq, bk,
                                    interpret).astype(jnp.float32))
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal, bq, bk, interpret))

    # Each candidate costs a kernel compile. A sweep deadline
    # (MPI_TPU_TUNE_DEADLINE_S, 0 disables) stops after the candidate
    # in flight and takes the best
    # so far, so the caller's own budget (e.g. the bench train leg's
    # subprocess timeout) is never blown by tuning alone; the truncated
    # marker in the table records which configs went unmeasured.
    deadline_s = float(os.environ.get("MPI_TPU_TUNE_DEADLINE_S", "300"))
    t_start = time.monotonic()
    table = []
    for bq, bk in effective:
        # Truncate only once something actually TIMED — a prefix of
        # failed candidates (VMEM misfits) must not cut off the
        # still-viable rest, however long their failed compiles took.
        if deadline_s > 0 and any("ms" in t for t in table) \
                and time.monotonic() - t_start > deadline_s:
            table.append({"block_q": bq, "block_k": bk,
                          "error": "untried: tune deadline "
                                   f"({deadline_s:.0f}s) reached"})
            continue
        fn = build(bq, bk)
        try:
            _time_once(fn, q, k, v)  # compile + warm
            ms = statistics.median(
                _time_once(fn, q, k, v) for _ in range(reps)) * 1e3
        except Exception as exc:  # noqa: BLE001 - config may not fit VMEM
            table.append({"block_q": bq, "block_k": bk,
                          "error": str(exc)[:120]})
            continue
        table.append({"block_q": bq, "block_k": bk, "ms": round(ms, 3)})

    timed = [t for t in table if "ms" in t]
    if not timed:
        raise RuntimeError(
            f"mpi_tpu: flash autotune: no candidate compiled/ran "
            f"({[t.get('error') for t in table][:3]})")
    timed.sort(key=lambda t: t["ms"])
    best = (timed[0]["block_q"], timed[0]["block_k"])
    truncated = any("untried" in str(t.get("error", "")) for t in table)
    # A truncated winner serves THIS process (re-tuning now would blow
    # the same deadline again) but is never persisted: the next run —
    # with time to finish the sweep — must not inherit a
    # first-candidates-only result as if it were the full verdict.
    _cache[key] = best
    if not truncated:
        _disk_cache_store(key, best)
    if set_default:
        register_tuned_blocks(seq, tk, *best)
    return best, timed + [t for t in table if "ms" not in t]
