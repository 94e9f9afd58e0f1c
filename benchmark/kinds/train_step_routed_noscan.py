"""Kind ``train_step_routed_noscan``: kind ``train_step_routed`` for a
routed model with no Mamba-2 scan, whose comparison is that kind's less
the scan, and a check of the first step's update.

Set-up, warm-up, the window and the compile cache (off) are
``kinds/train_step_routed.py``'s: that file is loaded and its ``run`` is
run as it is, with this kind's comparison in place of its own (the file
is an accepted cell's and is not edited; PERF.md, Open question 15a).
Outside the window:

  * **the choices** (``routing_tolerance``): for every routed layer the
    program's own ``routed_choices`` gives the router's input and the
    experts chosen, and the reference scores that same input in float32
    (``choices_outside_top_k``, over whatever selects there: the scores,
    or the scores plus a selection bias): the share of the program's
    choices that are not among the reference's ``top_k``. A router in a
    lower precision fails here.
  * **loss and gradient on those choices** (``loss_tolerance``,
    ``grad_tolerance``: one number, or a number a leaf with ``"*"`` for
    the rest), for the first sequence of the first batch: the reference's
    ``sequence_loss(..., routing=choices)`` uses the experts the program
    chose, with its own scores and weights, so what is left between the
    two gradients of block ``grad_block`` is rounding.
  * **the first step's update** (``update_tolerance``, a number or a
    number a leaf): the first warm-up step is the program's step on the
    whole first batch from the state the comparison read. Block
    ``grad_block``'s change in that step is held against AdamW's first
    step in float32 (:func:`adamw_first_step`) on the reference's gradient
    of the whole batch, each sequence on the program's own choices:
    ``|change - expected| / |expected|`` a leaf. A step that leaves the
    state as it was reads 1; one on part of the batch, or a broken
    optimizer, reads far from 0. AdamW's first step is ``lr (sign(g) +
    decay p)`` wherever ``|g|`` dwarfs its eps, so an entry whose
    gradient's sign a rounding turns moves by ``2 lr``: the sound
    program's reading is twice the root of the share of such entries.
    The program's step is reached by wrapping ``make_train_step`` of
    ``mpi_tpu.models`` for the length of the run (the routed kind imports
    it there).

Configuration file: as for ``train_step_routed`` without
``scan_tolerance`` and with ``update_tolerance``, optimizer ``adamw`` at a
constant ``learning_rate``; the reference has ``sequence_loss`` and
``choices_outside_top_k``. Traffic file: as for ``train_step``.
"""

from __future__ import annotations

import time

# optax.adamw's defaults, which mpi_tpu.models.make_optimizer keeps.
ADAMW = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}


def run(ctx):
    import mpi_tpu.models as models

    routed = ctx.load("kinds/train_step_routed.py")
    check = Check(routed._limit, ctx.config["learning_rate"])
    routed.compare = check.compare
    make = models.make_train_step

    def make_checked(*args, **kw):
        init_state, step = make(*args, **kw)
        return init_state, FirstStepChecked(step, check)

    models.make_train_step = make_checked
    try:
        result = routed.run(ctx)
    finally:
        models.make_train_step = make
    result["correct"] = result["correct"] and check.update_ok
    result["notes"].update(check.update_numbers)
    return result


def adamw_first_step(grad, param, learning_rate):
    """AdamW's change of ``param`` in its first step, from zero moments,
    in float32: ``m = (1 - b1) g``, ``v = (1 - b2) g^2``, each divided by
    its bias correction, ``-lr (m / (sqrt(v) + eps) + decay p)``."""
    import jax.numpy as jnp

    b1, b2, eps, decay = (ADAMW[k] for k in ("b1", "b2", "eps",
                                               "weight_decay"))
    g, p = grad.astype(jnp.float32), param.astype(jnp.float32)
    m = (1 - b1) * g / (1 - b1)
    v = (1 - b2) * g * g / (1 - b2)
    return -learning_rate * (m / (jnp.sqrt(v) + eps) + decay * p)


class FirstStepChecked:
    """The program's step, whose first call runs :meth:`Check.step`."""

    def __init__(self, step, check):
        self._step, self._check = step, check

    def __getattr__(self, name):        # _cache_size, for the routed loop
        return getattr(self._step, name)

    def __call__(self, state, tokens):
        check, self._check = self._check, None
        if check is None:
            return self._step(state, tokens)
        return check.step(self._step, state, tokens)


class Check:
    """The module's three comparisons: :meth:`compare` (the routed kind's
    ``compare``) before the first step, :meth:`step` around it."""

    def __init__(self, limit, learning_rate):
        self.limit, self.learning_rate = limit, learning_rate
        self.update_ok, self.update_numbers = False, {}
        self._seen = None

    def compare(self, params, one, cfg, mesh, conf, reference, say):
        """Routing, loss and gradient for the sequence ``one`` (1, seq +
        1). Returns whether every number lies within its limit, and the
        numbers."""
        import jax
        import jax.numpy as jnp

        from mpi_tpu.models.transformer import loss_fn, routed_choices

        model, at = conf["model"], conf["grad_block"]
        routed = [blk for blk, kind in zip(params["blocks"],
                                           cfg.layer_pattern) if kind == "E"]
        choose = jax.jit(lambda p, t: routed_choices(p, t, cfg, mesh))

        def of_block(loss):
            def f(blk, params, *args):
                blocks = list(params["blocks"])
                blocks[at] = blk
                return loss(dict(params, blocks=blocks), *args)
            return jax.jit(jax.value_and_grad(f))

        by_reference = of_block(
            lambda p, t, chosen: reference.sequence_loss(p, t, model, chosen))

        t0 = time.perf_counter()
        choices = choose(params, one[:, :-1])
        outside_of = jax.jit(                # one program for the layers
            lambda h, idx, blk: reference.choices_outside_top_k(
                h, idx, blk, model))
        outside = [float(outside_of(h, idx, blk))
                   for (h, idx), blk in zip(choices, routed)]
        routing = [idx for _, idx in choices]
        del choices
        say(f"train_step_routed_noscan: of the program's choices in the "
            f"{len(routed)} routed layers, not among the reference's top "
            f"{cfg.moe_top_k} for the same input: "
            + ", ".join(f"{x:.2e}" for x in outside)
            + f" ({time.perf_counter() - t0:.1f} s); tolerance "
            f"{conf['routing_tolerance']}")

        t0 = time.perf_counter()
        args = (params["blocks"][at], params)
        loss_sys, grad_sys = of_block(lambda p, t: loss_fn(p, t, cfg, mesh))(
            *args, one)
        loss_sys = float(loss_sys)
        t1 = time.perf_counter()
        loss_ref, grad_ref = by_reference(*args, one[0], routing)
        loss_ref = float(loss_ref)
        far = jax.jit(lambda got, want: jax.tree.map(
            lambda a, b: jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(
                b.ravel()), got, want))(grad_sys, grad_ref)
        grad_err = {"/".join(str(k.key) for k in path): float(e)
                    for path, e in jax.tree.leaves_with_path(far)}
        del grad_sys, args
        tol, grad_tol = conf["loss_tolerance"], conf["grad_tolerance"]
        over = [k for k, e in grad_err.items()
                if not e <= self.limit(grad_tol, k)]
        parity = (abs(loss_sys - loss_ref) <= tol and not over
                  and all(x <= conf["routing_tolerance"] for x in outside))
        say(f"train_step_routed_noscan: step-0 loss of one sequence: system "
            f"{loss_sys:.5f} ({t1 - t0:.1f} s with its gradient), plain "
            f"reference on the program's choices {loss_ref:.5f} "
            f"({time.perf_counter() - t1:.1f} s); difference "
            f"{loss_sys - loss_ref:+.5f}, tolerance {tol}; gradient of block "
            f"{at}, |system - reference| / |reference| a leaf (its limit): "
            + ", ".join(f"{k} {e:.4f} ({self.limit(grad_tol, k)})"
                        for k, e in grad_err.items())
            + f"; over: {over or 'none'}: {'ok' if parity else 'FAILED'}")
        # What step() needs to extend the reference's gradient to the
        # whole batch without compiling anything again.
        self._seen = dict(one=one, grad=grad_ref, choose=choose,
                          by_reference=by_reference, conf=conf, say=say)
        return parity, {
            "loss_system": loss_sys, "loss_reference": loss_ref,
            "grad_rel_err": grad_err, "routing_outside_top_k": outside}

    def step(self, step, state, tokens):
        """``step(state, tokens)``, the program's first, with block
        ``grad_block``'s change held against :func:`adamw_first_step` on
        the reference's gradient of the whole batch ``tokens``."""
        import jax
        import jax.numpy as jnp

        seen, self._seen = self._seen, None
        conf, say = seen["conf"], seen["say"]
        at, params = conf["grad_block"], state["params"]
        if conf.get("optimizer", "adamw") != "adamw" or not bool(
                jnp.array_equal(tokens[:1], seen["one"])):
            raise ValueError(
                "train_step_routed_noscan: the first step is to be AdamW's "
                "on the batch whose first sequence the comparison read")
        t0 = time.perf_counter()
        grads = [seen["grad"]]
        for i in range(1, tokens.shape[0]):
            one = tokens[i:i + 1]
            routing = [idx for _, idx in seen["choose"](params, one[:, :-1])]
            grads.append(seen["by_reference"](
                params["blocks"][at], params, one[0], routing)[1])
        batch = jax.tree.map(lambda *g: sum(g) / len(g), *grads)
        expect = jax.jit(lambda g, p: jax.tree.map(
            lambda g, p: adamw_first_step(g, p, self.learning_rate), g, p))
        before = jax.tree.map(jnp.copy, params["blocks"][at])
        want = expect(batch, before)
        first_only = expect(grads[0], before)   # as if the rest were lost
        del grads, batch
        t1 = time.perf_counter()

        state, loss = step(state, tokens)

        far = jax.jit(lambda got, want: jax.tree.map(
            lambda a, b: jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(
                b.ravel()), got, want))
        got = jax.tree.map(lambda a, b: a - b, state["params"]["blocks"][at],
                           before)

        def named(tree):
            return {"/".join(str(k.key) for k in path): float(e)
                    for path, e in jax.tree.leaves_with_path(tree)}
        update_err = named(far(got, want))
        one_sequence = named(far(first_only, want))
        tol = conf["update_tolerance"]
        over = [k for k, e in update_err.items()
                if not e <= self.limit(tol, k)]
        self.update_ok = not over
        self.update_numbers = {"update_rel_err": update_err,
                               "update_rel_err_one_sequence": one_sequence}
        say(f"train_step_routed_noscan: block {at}'s change in the first "
            f"step against float32 AdamW on the reference's gradient of the "
            f"{tokens.shape[0]} sequences ({t1 - t0:.1f} s), |change - "
            f"expected| / |expected| a leaf (its limit): "
            + ", ".join(f"{k} {e:.4f} ({self.limit(tol, k)})"
                        for k, e in update_err.items())
            + "; the first sequence's alone would read "
            + ", ".join(f"{k} {e:.4f}" for k, e in one_sequence.items())
            + f"; over: {over or 'none'}: "
            f"{'ok' if self.update_ok else 'FAILED'}")
        return state, loss
