"""Kind ``train_step``: optimizer steps of a language model on one mesh.

The set-up is the path a user of the model stack meets, copied from
``chip_smoke.py``'s ``train_phase`` and ``examples/train.py``:
``make_mesh_nd(chips)`` -> ``make_train_step(cfg, mesh, learning_rate)`` ->
``init_state(key)`` -> ``iter(ShardedLoader(SyntheticLM(...), mesh))``.
Nothing of it is worked around: what the program compiles twice, the
benchmark pays for twice, in ``setup_s``.

Configuration file: ``model`` (passed to ``TransformerConfig(**model)`` as it
is, ``dtype`` by name), ``optimizer``, ``learning_rate``, ``reference``
(a file of the benchmark with ``sequence_loss``), ``loss_tolerance``,
``grad_block`` and ``grad_tolerance`` (which block's gradient is compared
with the reference's, and how far each leaf may lie from it), ``loss_falls``
(whether the last step's loss must lie under the first's).
Traffic file: ``batch``, ``seq``, ``warmup_steps``, ``traced_steps``.

The window is a closed loop with one step in flight: take the next batch
from the loader, dispatch step i, then wait for the loss of step i - 1 and
stamp the clock. It opens, with nothing in flight, just before its first
step is dispatched, and closes at the completion stamp of the last step
dispatched before ``seconds`` ran out, so every step and every second
between the two stamps counts.
"""

from __future__ import annotations

import contextlib
import math
import time


def run(ctx):
    import jax
    import jax.numpy as jnp

    from mpi_tpu.data import ShardedLoader, SyntheticLM
    from mpi_tpu.models import TransformerConfig, make_mesh_nd, make_train_step
    from mpi_tpu.models.transformer import loss_fn

    conf, traffic, say = ctx.config, ctx.traffic, ctx.say
    batch, seq = traffic["batch"], traffic["seq"]
    model = dict(conf["model"])
    cfg = TransformerConfig(**dict(
        model, dtype=jnp.dtype(model.get("dtype", "float32")),
        max_seq=max(model.get("max_seq", 0), seq + 1)))
    mesh = make_mesh_nd(ctx.chips)

    t0 = time.perf_counter()
    init_state, step = make_train_step(
        cfg, mesh=mesh, learning_rate=conf["learning_rate"],
        optimizer=conf.get("optimizer", "adamw"))
    state = init_state(jax.random.key_data(jax.random.key(ctx.seed)))
    loader = iter(ShardedLoader(
        SyntheticLM(cfg.vocab, batch, seq + 1, seed=ctx.seed), mesh=mesh))
    first = next(loader)
    jax.block_until_ready((state, first))
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    say(f"train_step: {n_params / 1e6:.1f} M parameters, mesh "
        f"{dict(mesh.shape)}, batch {first.shape}, state on device in "
        f"{time.perf_counter() - t0:.1f} s")

    # Correctness, outside the window and before the first step donates
    # the state: the system's loss and its gradient with respect to one
    # block (its own attention kernels, forward and both backward ones in
    # every block above, its compute dtype) against ``jax.value_and_grad``
    # of the plain float32 reference, same parameters, one sequence of the
    # first batch. One block's leaves, because two whole float32 gradients
    # do not fit beside the optimizer state.
    reference = ctx.load(conf["reference"])
    one, at = first[:1], conf["grad_block"]

    def of_block(loss):
        def f(blk, params, tokens):
            blocks = list(params["blocks"])
            blocks[at] = blk
            return loss(dict(params, blocks=blocks), tokens)
        return jax.jit(jax.value_and_grad(f))

    t0 = time.perf_counter()
    args = (state["params"]["blocks"][at], state["params"], one)
    loss_sys, grad_sys = of_block(lambda p, t: loss_fn(p, t, cfg, mesh))(*args)
    loss_sys = float(loss_sys)
    t1 = time.perf_counter()
    loss_ref, grad_ref = of_block(
        lambda p, t: reference.sequence_loss(p, t[0], model))(*args)
    loss_ref = float(loss_ref)
    far = jax.jit(lambda got, want: jax.tree.map(
        lambda a, b: jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(
            b.ravel()), got, want))(grad_sys, grad_ref)
    grad_err = {"/".join(str(k.key) for k in path): float(e)
                for path, e in jax.tree.leaves_with_path(far)}
    del grad_sys, grad_ref, args
    tol, grad_tol = conf["loss_tolerance"], conf["grad_tolerance"]
    worst = max(grad_err, key=grad_err.get)
    parity = (abs(loss_sys - loss_ref) <= tol
              and all(e <= grad_tol for e in grad_err.values()))
    say(f"train_step: step-0 loss of one sequence: system {loss_sys:.5f} "
        f"({t1 - t0:.1f} s with its gradient), plain reference "
        f"{loss_ref:.5f} ({time.perf_counter() - t1:.1f} s); difference "
        f"{loss_sys - loss_ref:+.5f}, tolerance {tol}; gradient of block "
        f"{at}, |system - reference| / |reference| a leaf: "
        + ", ".join(f"{k} {e:.4f}" for k, e in grad_err.items())
        + f"; worst {worst}, tolerance {grad_tol}: "
        f"{'ok' if parity else 'FAILED'}")

    # Warm-up: this cell's one shape, until the step stops compiling.
    tokens, losses = first, []
    for i in range(traffic["warmup_steps"]):
        t0, programs = time.perf_counter(), step._cache_size()
        state, loss = step(state, tokens)
        losses.append(float(loss))
        say(f"train_step: warm-up step {i} loss {losses[-1]:.5f} "
            f"({time.perf_counter() - t0:.2f} s"
            f"{', compiled' if step._cache_size() > programs else ''})")
        tokens = next(loader)

    stamps, waits, pending = [], [], []
    programs = step._cache_size()

    def one_step(tokens):
        nonlocal state
        with ctx.span("dispatch"):
            state, loss = step(state, tokens)
        pending.append(loss)
        if len(pending) > 1:
            with ctx.span("wait_step"):
                losses.append(float(pending.pop(0)))
            stamps.append(time.perf_counter())

    def next_batch():
        t0 = time.perf_counter()
        with ctx.span("loader_wait"):
            tokens = next(loader)
        waits.append(time.perf_counter() - t0)
        return tokens

    with (ctx.profile() if ctx.trace else contextlib.nullcontext()):
        ctx.setup_done()
        stamps.append(time.perf_counter())      # the window opens
        one_step(tokens)                        # one step in flight
        if ctx.trace:
            for _ in range(traffic["traced_steps"] - 1):
                one_step(next_batch())
        else:
            while time.perf_counter() - stamps[0] < ctx.seconds:
                one_step(next_batch())
        with ctx.span("wait_step"):
            losses.append(float(pending.pop(0)))  # the step in flight
        stamps.append(time.perf_counter())      # the window closes
    loader.close()
    compiles = step._cache_size() - programs

    steps = len(stamps) - 1
    failed = sum(not math.isfinite(x) for x in losses)
    window = stamps[-1] - stamps[0]
    # Completion to completion (the first also holds the pipeline's fill).
    # A run that reads far off shows here whether one stall did it.
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    longest = max(range(steps), key=gaps.__getitem__)
    step_ms = {"min": min(gaps) * 1e3,
               "median": sorted(gaps)[steps // 2] * 1e3,
               "max": gaps[longest] * 1e3, "max_at_step": longest,
               "max_at_s": stamps[longest] - stamps[0]}
    say(f"train_step: {steps} steps in {window:.3f} s (between completions "
        f"{step_ms['min']:.1f} / {step_ms['median']:.1f} / "
        f"{step_ms['max']:.1f} ms min / median / max), "
        f"{compiles} compilation(s) inside the window, loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}, {failed} not finite")
    # The optimizer is compared with no reference (PERF.md, Open
    # questions); where the configuration says the loss falls on this
    # traffic, an update that does not lower it fails the run.
    learned = losses[-1] < losses[0] or not conf.get("loss_falls", False)
    return {
        "correct": parity and failed == 0 and compiles == 0 and learned,
        "attempted": steps, "failed": failed,
        "record": {
            "tokens_per_step": batch * seq, "batch": batch, "seq": seq,
            "model": model, "step_stamps": stamps, "loader_wait_s": waits,
            "compiles_in_window": compiles,
        },
        "notes": {
            "parameters": n_params, "loss_system": loss_sys,
            "loss_reference": loss_ref, "grad_rel_err": grad_err,
            "loss_first": losses[0],
            "loss_last": losses[-1], "steps": steps, "window_s": window,
            "step_ms": step_ms,
            "loader_wait_ms_max": max(waits, default=0.0) * 1e3,
            "compiles_in_window": compiles,
            "step_programs": step._cache_size(),
        },
    }
