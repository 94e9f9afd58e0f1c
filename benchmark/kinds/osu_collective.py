"""Kind ``osu_collective``: one collective of the message-passing API,
called by rank programs, size by size, in the manner of the OSU
micro-benchmarks.

The rank program is ``chip_smoke.py``'s ``exchange_main`` cut to one
collective: ``ranks`` threads under ``run_spmd(fn, net=XlaNetwork(n=ranks))``,
each ``mpi_tpu.init()`` -> payload on ``net.device()`` -> warm-up calls ->
timed ``y = mpi_tpu.<collective>(x); jax.block_until_ready(y)`` -> ``barrier``
-> ``finalize``. A barrier before each size's calls and none between them,
as in OSU; only rank 0's times are judged.

Two things differ from OSU, both because of what a rank program on a chip
is. (1) Every call gets a *new* committed ``jax.Array``, copied on the
device from the rank's resident buffers before its size's calls start: a
rank program reduces what it has just computed, and jax keeps a host copy
of an array it has once read, so a buffer reused as OSU reuses it would
hide the device-to-host read that every real call pays (measured once
with one buffer for all calls of a block: PERF.md, Findings).
(2) Each rank holds ``resident_bytes`` of payload on its chip, in buffers
of the largest size (one random buffer from the seed, the others scaled
copies of it; large calls go round them): the gradient of the
data-parallel replica the configuration names, of which each call reduces
one message, so that the collective runs on a chip as full as that
deployment's.

Configuration file: ``collective`` (a function of ``mpi_tpu``), ``op``,
``ranks``, ``dtype``, ``deterministic``, ``resident_bytes``, ``reference``
(a file of the benchmark with a function named as the collective),
``rtol``, ``atol``. Traffic file: ``sizes_bytes``, ``iters_per_pass``,
``judged_small``, ``judged_large``, ``warmup_calls``, ``traced_calls``.

Only the calls are timed, as in OSU: the copies and the barrier before a
size's calls are outside every sample. Rank 0's samples of the two judged
sizes are printed in order, in whole microseconds, on lines of their own.
"""

from __future__ import annotations

import contextlib
import threading
import time

WAIT_S = 300.0  # a rank that died must not hang the others for ever


class Exchange:
    """Host-side meeting point of the rank threads, for the oracle only."""

    def __init__(self, n: int):
        self.slots = [None] * n
        self.barrier = threading.Barrier(n)

    def all(self, rank: int, value):
        self.slots[rank] = value
        self.barrier.wait(WAIT_S)
        got = list(self.slots)
        self.barrier.wait(WAIT_S)
        return got


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi_tpu
    from mpi_tpu.backends.xla import XlaNetwork, run_spmd

    conf, traffic, say = ctx.config, ctx.traffic, ctx.say
    n = conf["ranks"]
    dtype = np.dtype(conf["dtype"])
    sizes = list(traffic["sizes_bytes"])
    iters = list(traffic["iters_per_pass"])
    largest = max(sizes)
    elems = largest // dtype.itemsize
    n_buffers = max(1, conf["resident_bytes"] // largest)
    kwargs = {"op": conf["op"]} if "op" in conf else {}
    oracle = getattr(ctx.load(conf["reference"]), conf["collective"])
    meet = Exchange(n)

    def rank_main():
        mpi_tpu.init()
        try:
            net = mpi_tpu.api.registered()
            rank, dev = mpi_tpu.rank(), net.device()
            call = getattr(mpi_tpu, conf["collective"])

            t0 = time.perf_counter()
            key = jax.device_put(jax.random.key_data(jax.random.fold_in(
                jax.random.key(ctx.seed), rank)), dev)
            base = jax.jit(lambda k: jax.random.uniform(
                jax.random.wrap_key_data(k), (elems,), dtype, -1.0, 1.0))(key)
            scaled = jax.jit(lambda a, i: a * (1 - i / (4 * n_buffers)))
            pool = [base] + [scaled(base, jnp.asarray(i, dtype))
                             for i in range(1, n_buffers)]
            jax.block_until_ready(pool)
            assert pool[0].committed and pool[0].devices() == {dev}
            one = jnp.ones((), dtype)
            fresh = {s: jax.jit(lambda a, m=s // dtype.itemsize:
                                jax.lax.slice(a, (0,), (m,)) * one)
                     for s in sizes}
            if rank == 0:
                say(f"osu_collective: {n} ranks, {n_buffers} resident "
                    f"buffer(s) of {largest} B a rank on device in "
                    f"{time.perf_counter() - t0:.1f} s")
            turn = 0

            def payloads(size, count):
                """``count`` new committed arrays of ``size`` bytes, ready."""
                nonlocal turn
                turn += count
                return jax.block_until_ready([
                    fresh[size](pool[(turn + i) % n_buffers])
                    for i in range(count)])

            def block(size, count, times=None):
                """OSU's inner loop: the payloads first, a barrier, then
                ``count`` calls back to back, each timed."""
                xs = payloads(size, count)
                mpi_tpu.barrier()
                for x in xs:
                    with ctx.span(conf["collective"]):
                        t0 = time.perf_counter()
                        y = call(x, **kwargs)
                        jax.block_until_ready(y)
                        dt = time.perf_counter() - t0
                    if times is not None:
                        times[size].append(dt)

            def checked(size) -> bool:
                """One call against the plain reference over the ranks'
                payloads, each read back from its own chip."""
                x, = payloads(size, 1)
                y = call(x, **kwargs)
                want = oracle(meet.all(rank, np.asarray(x)), **kwargs)
                got = np.asarray(y)
                return got.shape == want.shape and bool(np.allclose(
                    got, want, rtol=conf["rtol"], atol=conf["atol"]))

            # Warm-up: every size of this cell once against the oracle,
            # then a few untimed calls; nothing compiles after this.
            checks = []
            for size in sizes:
                t0 = time.perf_counter()
                checks.append(checked(size))
                block(size, traffic["warmup_calls"])
                if rank == 0:
                    say(f"osu_collective: warmed {size} B in "
                        f"{time.perf_counter() - t0:.2f} s, result "
                        f"{'ok' if checks[-1] else 'WRONG'}")

            times = {s: [] for s in sizes}
            mpi_tpu.barrier()
            if ctx.trace:
                # Rank 0 holds the profiler; the others wait for it at the
                # block's barrier, and its last call ends when theirs do.
                with ctx.profile() if rank == 0 else contextlib.nullcontext():
                    block(traffic["judged_large"], traffic["traced_calls"],
                          times)
            else:
                opened = time.perf_counter()
                if rank == 0:
                    ctx.setup_done()
                go_on = True
                while go_on:
                    for size, count in zip(sizes, iters):
                        block(size, count, times)
                    go_on = mpi_tpu.bcast(
                        time.perf_counter() - opened < ctx.seconds
                        if rank == 0 else None, root=0)
            checks += [checked(size) for size in sizes]
            mpi_tpu.barrier()
            return {"rank": rank, "times": times, "checks": checks,
                    "device": str(dev),
                    "programs": sorted(map(repr, net._world_coll._jit_cache))
                    if net.mesh is not None else None}
        finally:
            mpi_tpu.finalize()

    seen = run_spmd(rank_main, net=XlaNetwork(
        n=n, deterministic_collectives=conf["deterministic"]))
    zero = seen[0]
    calls = sum(len(v) for v in zero["times"].values())
    wrong = sum(not ok for s in seen for ok in s["checks"])
    on_device = zero["programs"] is not None and len(
        {s["device"] for s in seen}) == n
    say(f"osu_collective: rank 0 timed {calls} calls; "
        f"{sum(len(s['checks']) for s in seen)} results checked against "
        f"the numpy reference, {wrong} wrong; devices "
        f"{[s['device'] for s in seen]}; compiled collectives "
        f"{zero['programs']}")
    for size, samples in zero["times"].items():
        if samples:
            ordered = sorted(samples)
            say(f"osu_collective: {size:>9} B  n {len(samples):>5}  median "
                f"{ordered[len(ordered) // 2] * 1e6:12.1f} us  min "
                f"{ordered[0] * 1e6:12.1f} us  max {ordered[-1] * 1e6:12.1f} us")
    for size in (traffic["judged_small"], traffic["judged_large"]):
        say(f"osu_collective: call_us {size} "
            + " ".join(f"{t * 1e6:.0f}" for t in zero["times"][size]))
    return {
        "correct": wrong == 0 and on_device,
        "attempted": calls, "failed": wrong,
        "record": {
            "call_s": {str(s): v for s, v in zero["times"].items()},
            "ranks": n, "judged_small": traffic["judged_small"],
            "judged_large": traffic["judged_large"],
        },
        "notes": {
            "calls": {str(s): len(v) for s, v in zero["times"].items()},
            "median_us": {str(s): sorted(v)[len(v) // 2] * 1e6
                          for s, v in zero["times"].items() if v},
            "mean_us": {str(s): sum(v) / len(v) * 1e6
                        for s, v in zero["times"].items() if v},
            "compiled_collectives": zero["programs"],
            "resident_buffers": n_buffers,
        },
    }
