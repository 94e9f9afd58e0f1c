"""Vision Transformer — the encoder (bidirectional) model family.

Same blocks, same shardings, same kernels as the flagship LM
(:func:`mpi_tpu.models.transformer.block_body` with
``TransformerConfig(causal=False)`` — the flash kernel runs its
non-causal grid), with the image-side pieces on top: patchify + linear
projection in, learned position table, mean-pool + linear
classification head out. Proves the framework's model layer is a
family, not a single decoder: dp/tp sharding, bf16 compute, remat,
and the autotuned flash blocks all apply unchanged.

No reference analogue (btracey/mpi has no models; SURVEY.md §2) —
beyond-parity breadth like the MoE/LoRA/quant variants.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .transformer import (TransformerConfig, _act_constraint, _dense_init,
                          _layernorm, checkpointed_block, init_params,
                          make_optimizer, param_specs, sanitize_spec,
                          token_xent)

__all__ = ["ViTConfig", "init_vit_params", "forward_vit",
           "make_vit_train_step"]


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 32
    patch_size: int = 4
    channels: int = 3
    n_classes: int = 10
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention_impl: str = "dense"      # dense | flash | blockwise
    remat: bool = False
    n_kv_heads: Optional[int] = None

    def __post_init__(self):
        if self.image_size % self.patch_size:
            raise ValueError(
                f"mpi_tpu: image_size {self.image_size} not divisible "
                f"by patch_size {self.patch_size}")

    @property
    def n_patches(self) -> int:
        side = self.image_size // self.patch_size
        return side * side

    @property
    def inner(self) -> TransformerConfig:
        """The encoder-stack config the shared blocks run under."""
        return TransformerConfig(
            vocab=1,                       # unused (no token embedding)
            d_model=self.d_model, n_heads=self.n_heads,
            n_layers=self.n_layers, d_ff=self.d_ff,
            max_seq=self.n_patches, dtype=self.dtype,
            param_dtype=self.param_dtype,
            attention_impl=self.attention_impl, remat=self.remat,
            n_kv_heads=self.n_kv_heads, causal=False)


def init_vit_params(key: jax.Array, cfg: ViTConfig) -> Dict[str, Any]:
    """Parameter pytree: shared encoder blocks + final_ln from the
    transformer init (its token embedding is dropped; its position
    table, sized ``n_patches``, becomes the patch-position table), plus
    the patch projection and the classification head."""
    k_inner, k_patch, k_head = jax.random.split(key, 3)
    params = init_params(k_inner, cfg.inner)
    del params["embed"]                 # images enter via the patch proj
    pd = cfg.param_dtype
    pdim = cfg.patch_size * cfg.patch_size * cfg.channels
    params["patch"] = _dense_init(k_patch, (pdim, cfg.d_model), pd, pdim)
    params["head"] = {
        "w": _dense_init(k_head, (cfg.d_model, cfg.n_classes), pd,
                         cfg.d_model),
        "b": jnp.zeros((cfg.n_classes,), pd),
    }
    return params


def _patchify(images: jax.Array, cfg: ViTConfig) -> jax.Array:
    """(b, H, W, C) -> (b, n_patches, p*p*C), row-major patch order."""
    b, H, W, C = images.shape
    if (H, W, C) != (cfg.image_size, cfg.image_size, cfg.channels):
        raise ValueError(
            f"mpi_tpu: expected {cfg.image_size}x{cfg.image_size}x"
            f"{cfg.channels} images, got {H}x{W}x{C}")
    p = cfg.patch_size
    x = images.reshape(b, H // p, p, W // p, p, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, cfg.n_patches, p * p * C)


def forward_vit(params: Dict[str, Any], images: jax.Array,
                cfg: ViTConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    """Class logits ``(b, n_classes)`` for ``(b, H, W, C)`` images."""
    dt = cfg.dtype
    x = _patchify(images.astype(dt), cfg) @ params["patch"].astype(dt)
    x = x + params["pos"].astype(dt)[None]
    x = _act_constraint(x, mesh)
    body = checkpointed_block(cfg.inner, mesh)
    for blk in params["blocks"]:
        x, _ = body(x, blk)
    x = _layernorm(x, params["final_ln"]["scale"].astype(dt),
                   params["final_ln"]["bias"].astype(dt))
    pooled = jnp.mean(x, axis=1)        # mean-pool over patches
    logits = pooled @ params["head"]["w"].astype(dt) \
        + params["head"]["b"].astype(dt)
    return logits.astype(jnp.float32)


def vit_loss_fn(params, batch: Tuple[jax.Array, jax.Array],
                cfg: ViTConfig, mesh: Optional[Mesh] = None):
    """Mean softmax cross-entropy over (images, int labels)."""
    images, labels = batch
    logits = forward_vit(params, images, cfg, mesh)
    return token_xent(logits, labels.astype(jnp.int32))


def make_vit_train_step(cfg: ViTConfig, mesh: Optional[Mesh] = None,
                        learning_rate: float = 1e-3,
                        optimizer: str = "adamw"):
    """(init_state, step) for classifier training; with a mesh, params
    follow the transformer specs (tp on heads/ffn; patch/head
    replicated) and the batch shards over ``dp``."""
    import optax

    opt = make_optimizer(optimizer, learning_rate)

    def _specs(params):
        # Shared blocks reuse the LM's canonical specs (tp on heads and
        # d_ff); the ViT-only leaves (patch proj, head) replicate.
        specs = param_specs(cfg.inner)
        specs.pop("embed", None)
        specs["patch"] = P()
        specs["head"] = {"w": P(), "b": P()}
        sane = jax.tree.map(lambda s: sanitize_spec(s, mesh), specs,
                            is_leaf=lambda s: isinstance(s, P))
        # Structural agreement with the params tree is load-bearing —
        # fail loudly if the trees ever drift.
        jax.tree.map(lambda *_: None, params, sane)
        return sane

    def init_state(key: jax.Array):
        params = init_vit_params(key, cfg)
        if mesh is not None:
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params, _specs(params))
        opt_state = (jax.jit(opt.init)(params) if mesh is not None
                     else opt.init(params))
        return {"params": params, "opt": opt_state}

    def step_body(state, batch):
        if mesh is not None:
            images, labels = batch
            sb = NamedSharding(
                mesh, P(*(("dp",) + (None,) * (images.ndim - 1))))
            images = jax.lax.with_sharding_constraint(images, sb)
            labels = jax.lax.with_sharding_constraint(
                labels, NamedSharding(mesh, P("dp")))
            batch = (images, labels)
        loss, grads = jax.value_and_grad(vit_loss_fn)(
            state["params"], batch, cfg, mesh)
        updates, new_opt = opt.update(grads, state["opt"],
                                      state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return {"params": new_params, "opt": new_opt}, loss

    return init_state, jax.jit(step_body)
