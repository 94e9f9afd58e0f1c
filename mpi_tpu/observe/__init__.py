"""mpi_tpu.observe — job-wide observability layer.

Three pillars on top of the process-local tracer
(:mod:`mpi_tpu.utils.trace`):

  * **distributed trace collection** (:mod:`.collect`) — every rank
    records spans/counters locally (the facade and the tcp/shm/xla/
    hybrid wire paths are instrumented); at Finalize rank 0 gathers all
    buffers over the existing transport, estimates per-rank clock
    offsets with a ping exchange, and merges one Perfetto/chrome-trace
    JSON with one track per rank (``--mpi-trace-out`` /
    ``MPI_TPU_TRACE_OUT``, with ``MPI_TPU_TRACE=1``);
  * **flight recorder** (:mod:`.flight`) — a bounded ring of the last N
    operations per rank, dumped to a per-rank postmortem file on fatal
    typed errors and chaos crashes (``--mpi-postmortem`` /
    ``MPI_TPU_POSTMORTEM_DIR``); ``mpirun`` folds survivors' dumps into
    one job report;
  * **streaming trace spooling** (:mod:`.stream`) — with
    ``--mpi-trace-stream DIR`` (``MPI_TPU_TRACE_STREAM``) each rank
    flushes bounded span chunks to a per-rank spool file continuously,
    keeping tracer memory O(chunk) and making everything already
    flushed crash-durable: the Finalize gather reads spools back, rank
    0 reconstructs dead ranks' tracks from their spool files, and
    ``mpirun`` can rebuild a merged trace from spools alone;
  * **live metrics + straggler detection** (:mod:`.metrics`) —
    per-collective arrival skew, an ``observe top`` text summary on
    SIGUSR1 or at Finalize (``MPI_TPU_OBSERVE_SUMMARY=1``), and a
    machine-readable ``--mpi-metrics-out`` JSON artifact
    (``MPI_TPU_METRICS_OUT``).

The facade (:mod:`mpi_tpu.api`) calls :func:`on_init` after a
successful ``init()`` and :func:`on_finalize` at the top of
``finalize()``; both are defensive — observability must never take a
job down. See docs/OBSERVABILITY.md for the operator's guide and the
overhead budget.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Any, Optional, Set, Tuple

from . import flight, metrics  # noqa: F401 - re-exported submodules

__all__ = ["flight", "metrics", "on_init", "on_finalize",
           "postmortem_dir", "trace_out_path", "metrics_out_path",
           "trace_stream_dir", "summary_enabled", "fatal_error_hook",
           "reset_for_testing"]

# Fatal typed failures that trigger a flight-recorder postmortem (by
# class name: the backends that define them import lazily, and a name
# match avoids the import cycle at error time).
_FATAL_NAMES = frozenset({
    "RemoteAbortError", "DeadlineError", "PeerDeadError", "ChecksumError",
})

_cfg_lock = threading.Lock()
_cfg: Optional[dict] = None
_collected: Set[Tuple[int, int]] = set()
_metrics_written: Set[Tuple[int, int]] = set()
_spooler: Optional[Any] = None


def _flag_or_env(flag: str, env: str) -> Optional[str]:
    from .. import flags as flagmod

    found = flagmod.scan_argv({flag})
    return found.get(flag) or os.environ.get(env) or None


def _config() -> dict:
    """Resolve the observe flags once per process (same precedence as
    the core ``-mpi-*`` flags: argv > env)."""
    global _cfg
    with _cfg_lock:
        if _cfg is None:
            from .. import flags as flagmod

            _cfg = {
                "trace_out": _flag_or_env(flagmod.FLAG_TRACE_OUT,
                                          flagmod.ENV_TRACE_OUT),
                "metrics_out": _flag_or_env(flagmod.FLAG_METRICS_OUT,
                                            flagmod.ENV_METRICS_OUT),
                "postmortem": _flag_or_env(flagmod.FLAG_POSTMORTEM,
                                           flagmod.ENV_POSTMORTEM),
                "trace_stream": _flag_or_env(flagmod.FLAG_TRACE_STREAM,
                                             flagmod.ENV_TRACE_STREAM),
            }
        return _cfg


def postmortem_dir() -> Optional[str]:
    return _config()["postmortem"]


def trace_out_path() -> Optional[str]:
    return _config()["trace_out"]


def metrics_out_path() -> Optional[str]:
    return _config()["metrics_out"]


def trace_stream_dir() -> Optional[str]:
    return _config()["trace_stream"]


def summary_enabled() -> bool:
    return os.environ.get("MPI_TPU_OBSERVE_SUMMARY", "").strip() not in (
        "", "0")


def on_init(impl: Any) -> None:
    """Post-``init()`` hook: bind the flight recorder to this rank,
    install the SIGUSR1 top handler (main thread only), and implicitly
    enable span recording when a trace sink is configured."""
    try:
        flight.set_rank(impl.rank())
    except Exception:  # noqa: BLE001 - never take init down
        pass
    try:
        from ..utils import trace

        if (trace_out_path() or trace_stream_dir()) and not trace.enabled():
            trace.enable()
        _install_spooler(impl)
        metrics.install_sigusr1(rank_fn=impl.rank)
    except Exception:  # noqa: BLE001
        pass


def _install_spooler(impl: Any) -> None:
    """Start streaming this process's tracer to a per-rank spool file.
    One spooler per process: under the hybrid driver every local rank
    thread shares the process tracer, so they share the spool too (the
    file is labelled with the first rank to init)."""
    global _spooler
    directory = trace_stream_dir()
    if not directory:
        return
    from ..utils import trace

    with _cfg_lock:
        if _spooler is not None:
            return
        from . import stream

        _spooler = stream.SpoolWriter(directory)
    try:
        _spooler.set_rank(impl.rank())
    except Exception:  # noqa: BLE001
        pass
    trace.set_stream(_spooler)


def on_finalize(impl: Any) -> None:
    """Pre-teardown hook, called from the facade's ``finalize()`` while
    the transport is still up. Collective when trace collection is
    configured (every rank's finalize participates in the gather); each
    step runs once per (backend, rank) even if finalize is re-entered."""
    try:
        rank, size = impl.rank(), impl.size()
    except Exception:  # noqa: BLE001 - backend already down
        return
    key = (id(impl), rank)

    cfg = _config()
    from ..utils import trace

    if cfg["trace_stream"]:
        # Push the resident tail out and stamp the footer BEFORE the
        # gather, so the spool is a complete standalone record and the
        # gather's spool read-back sees every span.
        try:
            trace.flush_stream()
            st = trace.stream()
            if st is not None:
                st.write_footer()
        except Exception:  # noqa: BLE001
            pass

    if cfg["trace_out"] and trace.enabled():
        with _cfg_lock:
            fresh = key not in _collected
            _collected.add(key)
        if fresh:
            try:
                from . import collect

                path = collect.collect_and_merge(impl, cfg["trace_out"])
                if path:
                    print(f"mpi_tpu: observe: merged trace written to "
                          f"{path}", file=sys.stderr)
            except Exception as exc:  # noqa: BLE001
                print(f"mpi_tpu: observe: trace collection failed: "
                      f"{exc}", file=sys.stderr)

    if cfg["metrics_out"]:
        with _cfg_lock:
            fresh = key not in _metrics_written
            _metrics_written.add(key)
        if fresh:
            try:
                metrics.write(cfg["metrics_out"], rank=rank, size=size)
            except Exception as exc:  # noqa: BLE001
                print(f"mpi_tpu: observe: metrics write failed: {exc}",
                      file=sys.stderr)

    if summary_enabled():
        try:
            print(metrics.summary_text(rank=rank, size=size),
                  file=sys.stderr, flush=True)
        except Exception:  # noqa: BLE001
            pass


def fatal_error_hook(exc: BaseException) -> None:
    """Called by the facade's error dispatch for every MpiError: the
    first FATAL typed failure (abort/deadline/peer-death/corruption)
    dumps this rank's flight-recorder postmortem."""
    if type(exc).__name__ not in _FATAL_NAMES:
        return
    try:
        # Make this rank's last spans durable before anything else: the
        # process may be about to die without reaching finalize.
        from ..utils import trace

        trace.flush_stream()
    except Exception:  # noqa: BLE001
        pass
    try:
        path = flight.dump(f"{type(exc).__name__}: {exc}")
        if path:
            print(f"mpi_tpu: observe: flight-recorder postmortem "
                  f"written to {path}", file=sys.stderr)
    except Exception:  # noqa: BLE001 - never mask the real error
        pass


def reset_for_testing() -> None:
    global _cfg, _spooler
    from ..utils import trace

    trace.set_stream(None)
    with _cfg_lock:
        _cfg = None
        _collected.clear()
        _metrics_written.clear()
        if _spooler is not None:
            _spooler.close()
            _spooler = None
    flight.reset_for_testing()
    metrics.reset_for_testing()
