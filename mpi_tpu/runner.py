"""Backend-selecting program entry — ``mpi_tpu.run_main``.

The reference selects a backend by calling ``mpi.Register`` in code
(mpi.go:61-67); everything else (addresses, timeouts) arrives via flags so
the same binary runs anywhere. ``run_main`` extends that flag surface with
backend selection so one program runs unmodified on either driver:

    python prog.py --mpi-addr :6000 --mpi-alladdr :6000,:6001   # TCP ranks
    python prog.py --mpi-backend xla --mpi-ranks 8              # mesh ranks
    python prog.py --mpi-backend hybrid --mpi-ranks 4 \
        --mpi-addr :6000 --mpi-alladdr :6000,:6001   # 2 hosts x 4 locals

``--mpi-backend`` (env ``MPI_TPU_BACKEND``): ``tcp`` (default), ``xla``,
or ``hybrid`` (xla ranks within this host + TCP between hosts; the TCP
flags address the *host*, ``--mpi-ranks`` counts this host's local ranks).
``--mpi-ranks`` (env ``MPI_TPU_RANKS``): rank count for the xla/hybrid
drivers (default: every visible device).
"""

from __future__ import annotations

import os
from typing import Any, Callable, List, Optional, Sequence

from . import api

__all__ = ["run_main", "selected_backend"]

FLAG_BACKEND = "mpi-backend"
FLAG_RANKS = "mpi-ranks"
ENV_BACKEND = "MPI_TPU_BACKEND"
ENV_RANKS = "MPI_TPU_RANKS"


def _scan_runner_flags(argv: Optional[Sequence[str]]) -> dict:
    from .flags import scan_argv

    return scan_argv({FLAG_BACKEND, FLAG_RANKS}, argv)


def selected_backend(argv: Optional[Sequence[str]] = None) -> str:
    found = _scan_runner_flags(argv)
    choice = (found.get(FLAG_BACKEND) or os.environ.get(ENV_BACKEND)
              or "tcp").lower()
    if choice not in ("tcp", "xla", "hybrid"):
        raise api.MpiError(
            f"mpi_tpu: unknown --{FLAG_BACKEND} {choice!r} "
            f"(tcp, xla, or hybrid)")
    return choice


def run_main(main: Callable[[], Any],
             argv: Optional[Sequence[str]] = None) -> List[Any]:
    """Run a reference-style program under the configured backend.

    ``tcp``: this process is one rank; ``main()`` runs once (the launcher
    started N processes). ``xla``: this process hosts *all* ranks;
    ``main()`` runs SPMD, one thread per mesh device. Returns the per-rank
    results (single-element list under tcp)."""
    backend = selected_backend(argv)

    def ranks() -> Optional[int]:
        ranks_s = (_scan_runner_flags(argv).get(FLAG_RANKS)
                   or os.environ.get(ENV_RANKS))
        if not ranks_s:
            return None
        try:
            return int(ranks_s)
        except ValueError as exc:
            raise api.MpiError(
                f"mpi_tpu: --{FLAG_RANKS} must be an integer, "
                f"got {ranks_s!r}") from exc

    if backend in ("xla", "hybrid") \
            and os.environ.get("JAX_PLATFORMS"):
        # JAX_PLATFORMS passes through whole (JAX's own comma-list
        # fallback semantics), pinned via jax.config before any device
        # query; when cpu leads it, --mpi-ranks also sizes the virtual
        # device mesh — so
        # `JAX_PLATFORMS=cpu prog --mpi-backend xla --mpi-ranks 8`
        # works with no XLA_FLAGS incantation.
        from .utils.platform import force_platform

        platforms = os.environ["JAX_PLATFORMS"]
        n = ranks()
        cpu_n = n if platforms.split(",")[0] == "cpu" else None
        if not force_platform(platforms, num_cpu_devices=cpu_n):
            import warnings

            warnings.warn(
                "mpi_tpu: JAX_PLATFORMS is set but a JAX backend is "
                "already initialized — the platform pin was skipped "
                "and device queries will use the live backend",
                RuntimeWarning, stacklevel=2)
    if backend == "xla":
        from .backends.xla import run_spmd

        return run_spmd(main, n=ranks())
    if backend == "hybrid":
        from .backends.hybrid import HybridNetwork, run_spmd_hybrid

        # TCP identity (addr/alladdr/timeout/password) comes from the
        # -mpi-* flags, exactly like the tcp driver (flags.go:44-50).
        return run_spmd_hybrid(main, HybridNetwork(local_ranks=ranks()))
    return [main()]
