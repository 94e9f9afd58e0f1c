"""Kind ``train_step_routed``: kind ``train_step`` for a model whose layers
route tokens to experts, with a comparison that can tell a lower precision
from a routing flip.

Set-up, warm-up and window are ``kinds/train_step.py``'s, line for line
(that file is an accepted cell's and is not edited; see its docstring for
the loop). What differs is the check against the plain reference. A model
with top-k experts that runs on a bfloat16 residual stream chooses other
experts than a float32 reference for some percent of its tokens, where two
scores lie closer than the stream's rounding, and those flips move every
gradient by 7 to 12% (PERF.md, PR 36): a limit above that catches no
precision. So the comparison is taken apart:

  * **the choices** (``routing_tolerance``): for every routed layer the
    program's own ``routed_choices`` gives the router's input and the
    experts chosen, and the reference scores that same input in float32:
    the share of the program's choices that are not among the reference's
    ``top_k`` largest. A router in a lower precision fails here.
  * **loss and gradient on those choices** (``loss_tolerance``,
    ``grad_tolerance``: one number, or a number a leaf with ``"*"`` for
    the rest): the reference's ``sequence_loss(..., routing=choices)``
    uses the experts the program chose, with its own scores and weights,
    so what is left between the two gradients is rounding.
  * **the scan alone** (``scan_tolerance``): the program's ``ssd_scan``
    against the reference's token-by-token recurrence, both in float32 on
    the host's CPU, on what enters the recurrence of block ``grad_block``
    (``reference.scan_inputs``; an ``M`` block) for the same sequence.
    With bfloat16 operands the operands' own rounding hides what the
    carried state's precision does, and in float32 on a v5e the sound
    program already reads 3e-5 to 5e-5 where a bfloat16 state reads 1.1e-4
    to 1.3e-4 (PERF.md, PR 36); in IEEE float32 nothing but the chunk
    algebra and the carried state is left, and the two read 6e-7 and 1e-4.

The process keeps nothing in the persistent compile cache: this kind's
first cell compiles more than the cache of the machine it was measured on
holds (``JAX_COMPILATION_CACHE_MAX_SIZE`` 192 MiB, least recently used
out first), so its entries were gone before its next run wanted them and
took the other cells' with them (PERF.md, PR 36).

Configuration file: as for ``train_step``, and ``routing_tolerance``,
``scan_tolerance``; the reference also has ``choices_outside_top_k``,
``scan_inputs`` and ``selective_scan``. Traffic file: as for
``train_step``.
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

    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

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
    say(f"train_step_routed: {n_params / 1e6:.1f} M parameters, mesh "
        f"{dict(mesh.shape)}, batch {first.shape}, state on device in "
        f"{time.perf_counter() - t0:.1f} s")

    # Correctness, outside the window and before the first step donates
    # the state (the module's docstring), for one sequence of the first
    # batch.
    parity, compared = compare(state["params"], first[:1], cfg, mesh, conf,
                               ctx.load(conf["reference"]), say)

    # Warm-up: this cell's one shape, until the step stops compiling.
    tokens, losses = first, []
    for i in range(traffic["warmup_steps"]):
        t0, programs = time.perf_counter(), step._cache_size()
        state, loss = step(state, tokens)
        losses.append(float(loss))
        say(f"train_step_routed: warm-up step {i} loss {losses[-1]:.5f} "
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
    say(f"train_step_routed: {steps} steps in {window:.3f} s (between "
        f"completions {step_ms['min']:.1f} / {step_ms['median']:.1f} / "
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
            "parameters": n_params, **compared, "loss_first": losses[0],
            "loss_last": losses[-1], "steps": steps, "window_s": window,
            "step_ms": step_ms,
            "loader_wait_ms_max": max(waits, default=0.0) * 1e3,
            "compiles_in_window": compiles,
            "step_programs": step._cache_size(),
        },
    }


def _limit(limits, leaf):
    return limits.get(leaf, limits["*"]) if isinstance(limits, dict) \
        else limits


def compare(params, one, cfg, mesh, conf, reference, say):
    """The three comparisons of the module's docstring for the sequence
    ``one`` (1, seq + 1). Returns whether every number lies within its
    limit, and the numbers."""
    import jax
    import jax.numpy as jnp

    from mpi_tpu.models.transformer import loss_fn, routed_choices
    from mpi_tpu.ops.ssd import ssd_scan

    model, at = conf["model"], conf["grad_block"]
    routed = [blk for blk, kind in zip(params["blocks"], cfg.layer_pattern)
              if kind == "E"]

    t0 = time.perf_counter()
    choices = jax.jit(lambda p, t: routed_choices(p, t, cfg, mesh))(
        params, one[:, :-1])
    outside = [float(jax.jit(
        lambda h, idx, blk: reference.choices_outside_top_k(
            h, idx, blk, model))(h, idx, blk))
        for (h, idx), blk in zip(choices, routed)]
    routing = [idx for _, idx in choices]
    del choices
    say(f"train_step_routed: of the program's choices in the "
        f"{len(routed)} routed layers, not among the reference's top "
        f"{cfg.moe_top_k} for the same input: "
        + ", ".join(f"{x:.2e}" for x in outside)
        + f" ({time.perf_counter() - t0:.1f} s); tolerance "
        f"{conf['routing_tolerance']}")

    t0 = time.perf_counter()
    recurrence = jax.device_put(
        jax.jit(lambda p, t: reference.scan_inputs(t, p, model, at))(
            params, one[0]), jax.devices("cpu")[0])
    got = jax.jit(lambda x, dt, A, B, C, D: ssd_scan(
        x[None], dt[None], A, B[None], C[None], D, cfg.ssm_chunk)[0])(
            *recurrence)
    want = jax.jit(reference.selective_scan)(*recurrence)
    scan_err = float(jnp.linalg.norm((got - want).ravel())
                     / jnp.linalg.norm(want.ravel()))
    del recurrence, got, want
    say(f"train_step_routed: the scan of block {at} in float32 on the "
        f"host, |ssd_scan - recurrence| / |recurrence|: {scan_err:.2e} "
        f"({time.perf_counter() - t0:.1f} s); tolerance "
        f"{conf['scan_tolerance']}")

    def of_block(loss):
        def f(blk, params, *args):
            blocks = list(params["blocks"])
            blocks[at] = blk
            return loss(dict(params, blocks=blocks), *args)
        return jax.jit(jax.value_and_grad(f))

    t0 = time.perf_counter()
    args = (params["blocks"][at], params)
    loss_sys, grad_sys = of_block(lambda p, t: loss_fn(p, t, cfg, mesh))(
        *args, one)
    loss_sys = float(loss_sys)
    t1 = time.perf_counter()
    loss_ref, grad_ref = of_block(
        lambda p, t, chosen: reference.sequence_loss(p, t, model, chosen))(
            *args, one[0], routing)
    loss_ref = float(loss_ref)
    far = jax.jit(lambda got, want: jax.tree.map(
        lambda a, b: jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(
            b.ravel()), got, want))(grad_sys, grad_ref)
    grad_err = {"/".join(str(k.key) for k in path): float(e)
                for path, e in jax.tree.leaves_with_path(far)}
    del grad_sys, grad_ref, args
    tol, grad_tol = conf["loss_tolerance"], conf["grad_tolerance"]
    over = [k for k, e in grad_err.items() if not e <= _limit(grad_tol, k)]
    parity = (abs(loss_sys - loss_ref) <= tol and not over
              and all(x <= conf["routing_tolerance"] for x in outside)
              and scan_err <= conf["scan_tolerance"])
    say(f"train_step_routed: step-0 loss of one sequence: system "
        f"{loss_sys:.5f} ({t1 - t0:.1f} s with its gradient), plain "
        f"reference on the program's choices {loss_ref:.5f} "
        f"({time.perf_counter() - t1:.1f} s); difference "
        f"{loss_sys - loss_ref:+.5f}, tolerance {tol}; gradient of block "
        f"{at}, |system - reference| / |reference| a leaf (its limit): "
        + ", ".join(f"{k} {e:.4f} ({_limit(grad_tol, k)})"
                    for k, e in grad_err.items())
        + f"; over: {over or 'none'}: {'ok' if parity else 'FAILED'}")
    return parity, {
        "loss_system": loss_sys, "loss_reference": loss_ref,
        "grad_rel_err": grad_err, "routing_outside_top_k": outside,
        "scan_rel_err": scan_err}
