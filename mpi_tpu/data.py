"""Sharded, prefetching data pipeline for the training workloads.

The reference has no data subsystem (it moves opaque payloads); this is
the rebuild's tpu-native loader: deterministic step-indexed batches
(checkpoint/resume replays the exact stream — pairs with
:mod:`mpi_tpu.utils.checkpoint`), dp-sharded placement onto the mesh, a
host-side prefetch thread that overlaps batch construction and
host→device transfer with the previous step's compute, and multi-host
slicing (each process materialises only its ``process_index`` share, the
``jax.distributed`` convention).

Sources are pluggable: :class:`SyntheticLM` (seeded token stream, used by
benchmarks/examples) or :func:`from_token_array` over a memory-mapped /
in-memory corpus.
"""

from __future__ import annotations

import os
import queue
import threading
from collections import OrderedDict
from typing import Any, Callable, Iterator, Optional, Union

import numpy as np

from .utils import trace

__all__ = ["SyntheticLM", "from_token_array", "from_token_file",
           "ShardedLoader"]

# dtypes the native gather kernel understands (widened to int32)
_NATIVE_GATHER_DTYPES = {
    np.dtype(np.uint8): 1,
    np.dtype(np.uint16): 2,
    np.dtype(np.int32): 4,
    np.dtype(np.uint32): 4,
}


def _gather_windows(tokens: np.ndarray, picks: np.ndarray,
                    seq: int) -> np.ndarray:
    """(batch, seq) int32 batch from window indices ``picks``.

    Uses the native gather+widen kernel (native/dataloader.cpp) when
    available — one GIL-free call, threaded on multi-core hosts — so
    batch assembly genuinely overlaps with device compute under the
    prefetch thread; otherwise a NumPy fallback with identical output."""
    from . import native as _native

    batch = len(picks)
    lib = _native.dataloader()
    dt = tokens.dtype
    if lib is not None and dt in _NATIVE_GATHER_DTYPES \
            and tokens.flags.c_contiguous and batch:
        import ctypes

        out = np.empty((batch, seq), dtype=np.int32)
        idx = np.ascontiguousarray(picks, dtype=np.int64)
        # Threads only pay off when the copy dwarfs thread create/join
        # (~tens of µs): gate on output size, not just core count.
        ncpu = os.cpu_count() or 1
        nthreads = min(4, ncpu) if batch * seq >= (1 << 16) else 1
        rc = lib.dl_gather(
            tokens.ctypes.data_as(ctypes.c_void_p), tokens.size,
            _NATIVE_GATHER_DTYPES[dt],
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            batch, seq, out.ctypes.data_as(ctypes.c_void_p), nthreads)
        if rc == 0:
            return out
        # fall through on -EINVAL (shouldn't happen: indices validated)
    return np.stack(
        [tokens[w * seq:(w + 1) * seq] for w in picks]).astype(np.int32)


class SyntheticLM:
    """Deterministic synthetic token source: ``sample(step) -> (B, S)``
    int32, a pure function of (seed, step) — the stream is identical
    across restarts, hosts, and prefetch depths."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int = 0):
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed

    def __call__(self, step: int) -> np.ndarray:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, step]))
        return rng.integers(0, self.vocab, (self.batch, self.seq),
                            dtype=np.int32)


def from_token_array(tokens: np.ndarray, batch: int, seq: int,
                     shuffle_seed: Optional[int] = 0
                     ) -> Callable[[int], np.ndarray]:
    """Batch source over a flat token array (e.g. np.memmap of a corpus).

    Step ``t`` yields ``batch`` windows of ``seq`` tokens. With
    ``shuffle_seed`` the window order is a seeded permutation per epoch
    (deterministic, resumable); ``None`` reads sequentially."""
    tokens = np.asarray(tokens)
    n_windows = len(tokens) // seq
    if n_windows < 1:
        raise ValueError(
            f"mpi_tpu: corpus of {len(tokens)} tokens is shorter than one "
            f"sequence ({seq})")
    if n_windows < batch:
        raise ValueError(
            f"mpi_tpu: corpus has {n_windows} windows of {seq} tokens — "
            f"fewer than one batch of {batch}")
    windows_per_epoch = n_windows // batch * batch
    perm_cache: "OrderedDict[int, np.ndarray]" = OrderedDict()
    perm_lock = threading.Lock()

    def _order(epoch: int) -> np.ndarray:
        if shuffle_seed is None:
            return np.arange(n_windows)
        # One O(n_windows) permutation per *epoch*, not per step — at
        # memmap-corpus scale the per-step cost must stay O(batch). The
        # two most-recently-*used* epochs are kept (not one) so iterators
        # straddling an epoch boundary — or a lagging iterator sharing
        # the source — don't thrash the permutation; the lock keeps
        # concurrent callers coherent.
        with perm_lock:
            if epoch in perm_cache:
                perm_cache.move_to_end(epoch)
            else:
                rng = np.random.default_rng(
                    np.random.SeedSequence([shuffle_seed, epoch]))
                perm_cache[epoch] = rng.permutation(n_windows)
                while len(perm_cache) > 2:
                    perm_cache.popitem(last=False)
            return perm_cache[epoch]

    def sample(step: int) -> np.ndarray:
        idx0 = step * batch
        epoch, offset = divmod(idx0, windows_per_epoch)
        order = _order(epoch)
        picks = order[(offset + np.arange(batch)) % n_windows]
        return _gather_windows(tokens, picks, seq)

    return sample


def from_token_file(path: Union[str, os.PathLike], batch: int, seq: int,
                    dtype: Any = np.uint16,
                    shuffle_seed: Optional[int] = 0
                    ) -> Callable[[int], np.ndarray]:
    """Batch source over a raw binary token file (the flat-corpus
    format: tokens back to back, no header). The file is memory-mapped
    read-only, so corpora far larger than RAM stream through the page
    cache, and the per-step gather runs in the native kernel when
    available. ``dtype`` is the on-disk token width (``uint16`` for
    vocabularies < 64K, the common LM corpus format)."""
    mm = np.memmap(os.fspath(path), dtype=np.dtype(dtype), mode="r")
    if mm.size == 0:
        raise ValueError(f"mpi_tpu: token file {os.fspath(path)!r} is empty")
    return from_token_array(mm, batch, seq, shuffle_seed=shuffle_seed)


class ShardedLoader:
    """Iterate device-resident, dp-sharded batches with prefetch.

    ``source(step) -> (B, S)`` is the *global* batch; each process keeps
    its contiguous per-process row slice (the
    ``jax.make_array_from_process_local_data`` layout convention), then
    commits the result to ``P('dp', None)`` over ``mesh`` (sanitized, so
    meshes without a ``dp`` axis get replication).

    Resumable: construct with ``start_step`` (e.g. the restored
    checkpoint step) and the stream continues exactly where it left off.
    """

    def __init__(self, source: Callable[[int], np.ndarray],
                 mesh: Optional[Any] = None, start_step: int = 0,
                 prefetch: int = 2):
        self.source = source
        self.mesh = mesh
        self.start_step = start_step
        self.prefetch = max(0, prefetch)
        self._sharding = None
        trace.listen_compiles()     # the placement may compile
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .models import sanitize_spec

            self._sharding = NamedSharding(
                mesh, sanitize_spec(P("dp", None), mesh))

    # -- single-batch path ----------------------------------------------------

    def batch_at(self, step: int):
        """The device-placed batch for ``step`` (pure, thread-safe)."""
        import jax

        with trace.span("data.source", step=step):
            host = self._process_slice(self.source(step))
        with trace.span("data.device_put", step=step, bytes=host.nbytes):
            if self._sharding is not None:
                if jax.process_count() > 1:
                    # Each process holds only its slice; assemble the
                    # global array from per-process local data (device_put
                    # with a global sharding would misread the slice as
                    # the whole).
                    return jax.make_array_from_process_local_data(
                        self._sharding, host)
                return jax.device_put(host, self._sharding)
            return jax.device_put(host)

    def _process_slice(self, global_batch: np.ndarray) -> np.ndarray:
        import jax

        nproc = jax.process_count()
        if nproc == 1:
            return global_batch
        b = global_batch.shape[0]
        if b % nproc:
            raise ValueError(
                f"mpi_tpu: global batch {b} not divisible by "
                f"{nproc} processes")
        share = b // nproc
        i = jax.process_index()
        return global_batch[i * share:(i + 1) * share]

    # -- prefetching iterator -------------------------------------------------

    def __iter__(self) -> Iterator[Any]:
        if self.prefetch == 0:
            step = self.start_step
            while True:
                with trace.span("data.batch", step=step):
                    batch = self.batch_at(step)
                yield batch
                step += 1
            return

        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def producer() -> None:
            step = self.start_step
            def put(entry) -> bool:
                # Bounded put that stays responsive to stop().
                while not stop.is_set():
                    try:
                        q.put(entry, timeout=0.2)
                        return True
                    except queue.Full:
                        continue
                return False

            while not stop.is_set():
                try:
                    with trace.span("data.batch", step=step):
                        item = self.batch_at(step)
                except BaseException as exc:  # noqa: BLE001 - handed to consumer
                    put(("error", exc))
                    return
                put(("ok", item))
                step += 1

        t = threading.Thread(target=producer, daemon=True,
                             name="mpi-data-prefetch")
        t.start()
        try:
            while True:
                with trace.span("data.wait"):
                    kind, item = q.get()
                if kind == "error":
                    raise item
                yield item
        finally:
            stop.set()
            # A daemon thread still inside a jax call when the interpreter
            # exits aborts the process ("exception not rethrown").
            t.join()
