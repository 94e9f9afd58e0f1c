"""JAX platform and compile-cache placement shared by the entry points.

:func:`force_platform` pins the platform (and the virtual CPU device
count) through :func:`jax.config.update`, which must happen before the
first device query; :func:`compile_cache_dir` decides where the
persistent compilation cache lives. Centralized here so
``chip_smoke.py``, ``benchmark/run.py``, the examples and
``__graft_entry__`` agree.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

__all__ = ["force_platform", "compile_cache_dir"]


def force_platform(name: str, num_cpu_devices: Optional[int] = None) -> bool:
    """Pin the JAX platform (and optionally the virtual CPU device count).

    Returns False (instead of raising) if a backend is already live —
    then the existing devices must suffice.
    """
    import jax

    try:
        if num_cpu_devices is not None:
            jax.config.update("jax_num_cpu_devices", num_cpu_devices)
        jax.config.update("jax_platforms", name)
    except RuntimeError:
        return False
    return True


def compile_cache_dir() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the only place the cache
    goes and nothing is set in code. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of the cache key and a directory that moves never hits. The choice
    is written to the environment, which JAX reads at import: call this
    before the first ``import jax``; child processes inherit it.
    """
    checkout = Path(__file__).resolve().parents[2]
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 str(checkout / ".jax_cache"))
