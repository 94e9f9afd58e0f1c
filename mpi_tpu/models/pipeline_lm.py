"""The flagship LM trained under pipeline parallelism (pp).

Completes the parallelism matrix *in the flagship*: dp/sp/tp/ep run
through ``TransformerConfig`` shardings; this module runs the same
blocks over a ``pp`` mesh axis using :func:`mpi_tpu.parallel.pipeline.
pipeline_sharded` — each device owns a contiguous *stage* of
``n_layers/pp`` blocks, microbatches stream around the ICI ring, and
the whole schedule (embed → pipeline scan → logits → loss) is one
differentiable jitted program.

Design constraints (and why they're fine):

  * stage activations must keep one shape, which transformer blocks
    satisfy by construction ((b, s, d) → (b, s, d));
  * the embedding/unembedding and final layernorm run replicated on
    every device (they are O(vocab·d) FLOPs vs the stages' O(L·d²) —
    negligible at depth, and it keeps stage 0 / stage n-1 from needing
    special param placement);
  * attention inside a stage must be a per-device impl (dense / flash /
    blockwise) — the sp family reshards globally and MoE routes over
    ``ep``, both of which belong to the sharded (non-pp) path;
    combinations are rejected loudly.

The reference has no model execution at all (SURVEY.md §2); like the
rest of ``models/``, this is new tpu-native capability.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.pipeline import pipeline_sharded
from .transformer import (TransformerConfig, _layernorm, block_body,
                          init_params, token_xent)

__all__ = ["stack_block_params", "init_pipelined_params",
           "forward_pipelined", "pipeline_loss_fn",
           "make_pipelined_train_step"]


def _pp_size(mesh: Mesh, axis_name: str) -> int:
    if axis_name not in mesh.axis_names:
        raise ValueError(
            f"mpi_tpu: mesh {mesh.axis_names} has no {axis_name!r} axis "
            f"for the pipelined flagship")
    return mesh.shape[axis_name]


def _check_cfg(cfg: TransformerConfig, pp: int) -> None:
    if cfg.n_layers % pp:
        raise ValueError(
            f"mpi_tpu: n_layers={cfg.n_layers} must divide into pp={pp} "
            f"stages")
    if cfg.n_experts > 0:
        raise ValueError(
            "mpi_tpu: MoE routes over the 'ep' axis — use the sharded "
            "(non-pp) path for expert parallelism")
    beyond = cfg.beyond_classic_block()
    if beyond:
        # The pipeline has its own embedding, final norm and tied logits
        # round block_body: it would norm, sum and project the classic
        # way whatever the configuration says.
        raise ValueError(
            f"mpi_tpu: the pipelined train step does not handle "
            f"{', '.join(beyond)} (its embedding, final norm and logits "
            f"are the classic block's); train such a model with "
            f"make_train_step")
    if cfg.attention_impl not in ("dense", "flash", "blockwise"):
        raise ValueError(
            f"mpi_tpu: pipeline stages need a per-device attention impl "
            f"(dense|flash|blockwise), got {cfg.attention_impl!r}")


def stack_block_params(params: Dict[str, Any], pp: int) -> Dict[str, Any]:
    """Restack ``init_params``'s per-block list into pipeline layout:
    every leaf of ``blocks`` gains leading axes ``(pp, layers_per_stage)``
    — stage i's slice lands on pipeline device i. embed/pos/final_ln
    stay as-is (replicated)."""
    blocks = params["blocks"]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    lps = len(blocks) // pp
    stacked = jax.tree.map(
        lambda x: x.reshape(pp, lps, *x.shape[1:]), stacked)
    out = {k: v for k, v in params.items() if k != "blocks"}
    out["stages"] = stacked
    return out


def init_pipelined_params(key: jax.Array, cfg: TransformerConfig,
                          mesh: Mesh, axis_name: str = "pp"
                          ) -> Dict[str, Any]:
    """Initialise and commit: stages sharded ``P('pp')`` on their leading
    axis (one stage per pipeline device), everything else replicated."""
    pp = _pp_size(mesh, axis_name)
    _check_cfg(cfg, pp)
    params = stack_block_params(init_params(key, cfg), pp)

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    params["stages"] = jax.tree.map(
        lambda x: put(x, P(axis_name)), params["stages"])
    for k in ("embed", "pos", "final_ln"):
        if k in params:
            params[k] = jax.tree.map(lambda x: put(x, P()), params[k])
    return params


def forward_pipelined(params: Dict[str, Any], tokens: jax.Array,
                      cfg: TransformerConfig, mesh: Mesh,
                      microbatches: int = 4, axis_name: str = "pp",
                      remat_stage: bool = False) -> jax.Array:
    """tokens (batch, seq) int32 → logits (batch, seq, vocab), with the
    block stack executed as a ``pp``-stage pipeline over ``microbatches``
    microbatches (batch must divide)."""
    pp = _pp_size(mesh, axis_name)
    _check_cfg(cfg, pp)
    b, s = tokens.shape
    if b % microbatches:
        raise ValueError(
            f"mpi_tpu: batch {b} not divisible by microbatches="
            f"{microbatches}")

    x = params["embed"].astype(cfg.dtype)[tokens]
    if not cfg.rope:
        x = x + params["pos"].astype(cfg.dtype)[:s][None]
    xs = x.reshape(microbatches, b // microbatches, s, -1)

    def stage_fn(stage_params, mx):
        # One stage = layers_per_stage blocks, scanned over the stacked
        # leading axis; the block math is transformer.block_body — ONE
        # definition shared with the sequential stack (aux dropped:
        # _check_cfg rejects MoE on the pp path).
        def block(h, blk):
            h, _ = block_body(h, blk, cfg, None)
            return h, None

        out, _ = lax.scan(block, mx, stage_params)
        return out

    ys = pipeline_sharded(stage_fn, params["stages"], xs, mesh,
                          axis_name=axis_name,
                          remat_stage=remat_stage or cfg.remat)
    x = ys.reshape(b, s, -1)
    x = _layernorm(x, params["final_ln"]["scale"].astype(x.dtype),
                   params["final_ln"]["bias"].astype(x.dtype))
    return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype))


def pipeline_loss_fn(params, tokens, cfg: TransformerConfig, mesh: Mesh,
                     microbatches: int = 4,
                     remat_stage: bool = False) -> jax.Array:
    """Next-token cross-entropy through the pipelined forward — the same
    logsumexp-minus-target form as :func:`transformer.loss_fn`."""
    logits = forward_pipelined(params, tokens[:, :-1], cfg, mesh,
                               microbatches=microbatches,
                               remat_stage=remat_stage)
    return token_xent(logits, tokens[:, 1:])


def make_pipelined_train_step(cfg: TransformerConfig, mesh: Mesh,
                              microbatches: int = 4,
                              learning_rate: float = 1e-3,
                              optimizer: str = "adamw",
                              axis_name: str = "pp",
                              remat_stage: bool = False
                              ) -> Tuple[Any, Any]:
    """(init_state, step) for the pp flagship; same shape as
    :func:`transformer.make_train_step` (one jitted optimizer step)."""
    from .transformer import make_optimizer

    opt = make_optimizer(optimizer, learning_rate)

    def init_state(key: jax.Array):
        params = init_pipelined_params(key, cfg, mesh, axis_name)
        return {"params": params, "opt": jax.jit(opt.init)(params)}

    def step(state, tokens):
        loss, grads = jax.value_and_grad(pipeline_loss_fn)(
            state["params"], tokens, cfg, mesh,
            microbatches=microbatches, remat_stage=remat_stage)
        import optax

        updates, new_opt = opt.update(grads, state["opt"], state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return {"params": new_params, "opt": new_opt}, loss

    # Donate the incoming state (params + opt alias their outputs — see
    # make_train_step); callers rebind state each step.
    return init_state, jax.jit(step, donate_argnums=(0,))
