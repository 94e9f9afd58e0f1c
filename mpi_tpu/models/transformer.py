"""Decoder-only Transformer LM, sharded tpu-first over a device mesh.

Design (the "pick a mesh, annotate shardings, let XLA insert collectives"
recipe):

  * parameters are a plain pytree; every leaf carries a
    :class:`jax.sharding.PartitionSpec` from :func:`param_specs` —
    tensor-parallel (``tp``) sharding on attention heads and the FFN hidden
    dimension (Megatron-style column/row split, so the only tp collective
    is one psum per block, inserted by GSPMD);
  * the batch axis is data-parallel (``dp``), the sequence axis is
    sequence-parallel (``sp``) — activations are constrained to
    ``P('dp', 'sp', None)`` between blocks so layernorm/FFN/elementwise
    work runs fully sharded and only attention gathers the sequence;
  * compute in bfloat16 on TPU (params kept float32), matmuls shaped to
    land on the MXU (head_dim / d_ff multiples of 128 at real sizes);
  * no data-dependent Python control flow — the whole step is one
    ``jit``-compiled program.

The reference contains no models (SURVEY.md §2); this module is the
framework's flagship workload, exercising the collectives the way the
reference's ``bounce`` example exercises Send/Receive
(/root/reference/examples/bounce/bounce.go:37-153).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import trace

__all__ = [
    "TransformerConfig",
    "init_params",
    "forward",
    "forward_with_aux",
    "routed_choices",
    "param_specs",
    "sanitize_spec",
    "apply_rope",
    "make_optimizer",
    "make_train_parts",
    "make_train_step",
    "make_mesh_nd",
]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_seq: int = 128
    dtype: Any = jnp.float32          # compute dtype (bfloat16 on TPU)
    param_dtype: Any = jnp.float32    # master params
    # "dense" | "flash" (Pallas kernel, mpi_tpu.ops) | "blockwise"
    # (checkpointed scan) | "ring" (kv ring over the sp axis,
    # parallel.ring_attention) | "ring_flash" (same ring, Pallas flash
    # kernel per chunk with the FA-2 Pallas backward) | "zigzag" (ring
    # with the work-balanced zigzag causal layout) | "zigzag_flash"
    # (zigzag layout + flash chunks) | "ulysses" (all-to-all head/seq
    # reshard, parallel.ulysses) | "ulysses_flash" (same, Pallas kernel
    # per head group). The ring/zigzag/ulysses family needs a mesh
    # with 'sp'; "flash" on a mesh runs per (dp, tp) shard and refuses
    # sp > 1. "eva" (ops/eva_attention.py) is chunked linear attention:
    # exact causal softmax inside aligned windows of ``eva_window``
    # positions plus one learned-pooled summary per ``eva_chunk``
    # positions of every earlier window, one softmax over both; its two
    # pooling vectors a head (``eva_phi``, ``eva_mu``) are block
    # parameters. Runs per (dp, tp) shard like "flash".
    attention_impl: str = "dense"
    eva_window: int = 2048
    eva_chunk: int = 16
    # Decode-time (KV-cache) attention: "dense" (jnp einsum chain, the
    # oracle) | "flash" (Pallas flash-decode kernel — one VMEM pass
    # over the cache per step, ops/decode_attention.py). Applies to
    # single-token decode steps only; prefill always uses the dense
    # cached path.
    decode_attention: str = "dense"
    # Mixture-of-Experts FFN (0 = dense). Experts shard over the 'ep'
    # mesh axis (mpi_tpu.models.moe); aux load-balance loss is added to
    # the training objective with coefficient moe_aux_coef. moe_top_k
    # selects routing (1 = Switch, 2 = GShard top-2).
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    moe_top_k: int = 1
    # Rematerialise each block in the backward pass (jax.checkpoint):
    # a block's backward recomputes in its forward what was not worth
    # holding, trading FLOPs for O(n_layers) less residual memory — the
    # switch that lets long sequences train on one chip's HBM. Held are
    # the few values that are cheap to hold and dear to redo
    # (``_REMAT_KEEPS``: the attention kernels' output and log-sum-exp,
    # so they run once a layer, the FFN's gate pre-activation and a
    # Mamba-2 block's in-projection).
    remat: bool = False
    # Grouped-query attention: number of k/v heads (None = n_heads,
    # plain MHA; 1 = MQA). Queries keep n_heads; k/v project to
    # n_kv_heads, shrinking k/v projection weights and the KV cache by
    # n_heads/n_kv_heads. The flash kernel and the decode path read
    # grouped heads natively; other impls repeat k/v before the kernel
    # (repeat_kv_heads). Must divide n_heads (and the tp axis size when
    # tensor-parallel).
    n_kv_heads: Optional[int] = None
    # Rotary position embeddings instead of the learned absolute table:
    # q/k are phase-rotated by their global positions before attention
    # (and before any sequence sharding, so ring/zigzag layouts carry
    # the already-encoded values). head_dim must be even.
    rope: bool = False
    rope_theta: float = 10000.0
    # Causal (autoregressive) masking. False gives a bidirectional
    # encoder stack (ViT, BERT-style) through the same blocks — the
    # dense/flash/blockwise kernels, the contiguous ring, and ulysses
    # all take it directly; only the ZIGZAG layouts are causal-only
    # (the work-balance trick assumes the triangular mask) and raise
    # at the ring layer.
    causal: bool = True
    # Normalisation: "layernorm" (scale and bias) | "rmsnorm_unit_offset"
    # (x * rsqrt(mean(x^2) + 1e-5) * (1 + scale), scale starting at
    # zero, no bias).
    norm: str = "layernorm"
    # Dense FFN: "gelu" (two matrices, w2(gelu(w1 x))) | "swiglu" (three,
    # w2(silu(w1 x) * (w3 x))) | "relu2" (two, w2(relu(w1 x)^2)).
    ffn: str = "gelu"
    # Output head: the embedding's transpose (tied), or an untied matrix
    # ``head`` of ``n_pred_heads * vocab`` rows whose logits are float32.
    # With ``n_pred_heads`` = P > 1, head p at position t predicts token
    # t + 1 + p (multi-token prediction): ``forward`` returns
    # (batch, seq, P, vocab) and the loss is the mean over the heads of
    # each head's mean cross-entropy over the positions whose target
    # exists.
    tie_embeddings: bool = True
    n_pred_heads: int = 1
    # dtype of the residual stream between blocks (a dtype or its name);
    # None = the compute dtype. "float32" keeps the sum x + f(norm(x)) in
    # float32 while matmuls and attention run in ``dtype``.
    residual_dtype: Any = None
    # Size of an attention head where ``n_heads`` of them do not make up
    # ``d_model`` (None = ``d_model // n_heads``): q projects to
    # ``n_heads x attn_head_dim``, ``wo`` back from it.
    attn_head_dim: Optional[int] = None
    # With ``rope`` off: the learned absolute table (True), or no position
    # term at all (False; a model whose mixers see order by themselves).
    position_table: bool = True
    # A norm over each head's values of q and of k (RMS, weight 1 + scale,
    # leaves ``q_norm`` / ``k_norm`` of ``head_dim``), before any position
    # term.
    qk_norm: bool = False
    # A block stack driven by a list of layer kinds, one letter a layer
    # (``n_layers`` = its length; None = the classic block throughout).
    # Block ``i`` is then ``x + f_i(norm(x))`` with ONE sub-layer, one norm
    # (``ln1``) and only its own leaves: ``M`` a Mamba-2 mixer
    # (models/mamba2.py; the ``ssm_*`` sizes below), ``C`` a gated short
    # conv (models/short_conv.py), ``*``
    # attention (the ``_attention`` of the classic block), ``F`` the dense
    # FFN of kind ``ffn`` at width ``dense_d_ff``, ``E`` one device's share
    # of a sigmoid-routed expert layer (models/moe.py ``routed_share_ffn``):
    # ``n_experts`` is the width of the router, ``moe_top_k`` the experts a
    # token, ``d_ff`` an expert's width and ``ffn`` its kind (relu2 or
    # swiglu), ``moe_experts_held`` contiguous experts from
    # ``moe_expert_offset`` live here (None = all), ``moe_shared_d_ff`` is
    # the shared expert's width (0 = none), ``moe_routed_scale`` multiplies
    # the normalised weights and ``moe_router_bias`` gives the router a
    # selection bias that no step updates, drawn N(0, std^2) at
    # ``moe_router_bias_std`` (0: zero, as released; above 0 a stand-in for
    # a bias that balancing has moved). ``M``, ``C`` and ``*`` run under
    # the scope ``attn``, ``E`` and ``F`` under ``ffn``.
    layer_pattern: Optional[str] = None
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    moe_experts_held: Optional[int] = None
    moe_expert_offset: int = 0
    moe_shared_d_ff: int = 0
    moe_routed_scale: float = 1.0
    moe_router_bias: bool = False
    moe_router_bias_std: float = 0.0
    dense_d_ff: int = 0

    def __post_init__(self):
        if self.norm not in ("layernorm", "rmsnorm_unit_offset"):
            raise ValueError(
                f"mpi_tpu: unknown norm {self.norm!r}: expected "
                f"layernorm|rmsnorm_unit_offset")
        if self.ffn not in ("gelu", "swiglu", "relu2"):
            raise ValueError(
                f"mpi_tpu: unknown ffn {self.ffn!r}: expected "
                f"gelu|swiglu|relu2")
        if self.layer_pattern is not None:
            self._check_layer_pattern()
        elif self.ffn != "gelu" and self.n_experts > 0:
            raise ValueError(
                f"mpi_tpu: ffn={self.ffn!r} is the dense FFN's; the "
                f"capacity-routed experts of models/moe.py are two-matrix "
                f"GELU")
        if self.rope and not self.position_table:
            raise ValueError(
                "mpi_tpu: position_table=False means no position term at "
                "all; rope=True is one")
        if self.n_pred_heads < 1 or (self.n_pred_heads > 1
                                     and self.tie_embeddings):
            raise ValueError(
                f"mpi_tpu: n_pred_heads={self.n_pred_heads} needs "
                f"tie_embeddings=False (each head has its own output rows)")
        if self.attention_impl == "eva" and not self.causal:
            raise ValueError("mpi_tpu: attention_impl='eva' is causal only")

    def _check_layer_pattern(self):
        pattern = self.layer_pattern
        if not pattern or set(pattern) - set("MC*EF") \
                or len(pattern) != self.n_layers:
            raise ValueError(
                f"mpi_tpu: layer_pattern={pattern!r} must be n_layers="
                f"{self.n_layers} letters of M (Mamba-2), C (gated short "
                f"conv), * (attention), E (routed experts), F (dense FFN)")
        if "F" in pattern and self.dense_d_ff < 1:
            raise ValueError(
                f"mpi_tpu: an F layer needs its width dense_d_ff (got "
                f"{self.dense_d_ff}); d_ff is an E layer's expert width")
        if "M" in pattern and (self.ssm_heads < 1
                               or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                f"mpi_tpu: an M layer needs ssm_heads (got {self.ssm_heads}) "
                f"in whole groups of ssm_groups={self.ssm_groups}")
        if "E" in pattern:
            if self.ffn not in ("relu2", "swiglu"):
                raise ValueError(
                    f"mpi_tpu: the experts of an E layer are relu2 (two "
                    f"matrices) or swiglu (three); ffn={self.ffn!r} experts "
                    f"are not implemented")
            if self.moe_router_bias_std and not self.moe_router_bias:
                raise ValueError(
                    f"mpi_tpu: moe_router_bias_std="
                    f"{self.moe_router_bias_std} draws a selection bias "
                    f"that moe_router_bias=False leaves out")
            if self.ffn == "swiglu" and self.moe_shared_d_ff:
                raise ValueError(
                    f"mpi_tpu: the shared expert of an E layer is relu2; "
                    f"beside swiglu experts moe_shared_d_ff must be 0 (got "
                    f"{self.moe_shared_d_ff})")
            held = self.experts_held
            if (self.n_experts < 1 or held < 1 or self.moe_shared_d_ff < 0
                    or self.moe_expert_offset < 0
                    or self.moe_expert_offset + held > self.n_experts):
                raise ValueError(
                    f"mpi_tpu: an E layer needs n_experts (got "
                    f"{self.n_experts}), a share moe_expert_offset="
                    f"{self.moe_expert_offset} + moe_experts_held={held} "
                    f"inside it and moe_shared_d_ff >= 0 (got "
                    f"{self.moe_shared_d_ff}; 0 = no shared expert)")

    @property
    def experts_held(self) -> int:
        return (self.n_experts if self.moe_experts_held is None
                else self.moe_experts_held)

    @property
    def stream_dtype(self):
        """dtype of the residual stream."""
        return jnp.dtype(self.dtype if self.residual_dtype is None
                         else self.residual_dtype)

    def beyond_classic_block(self) -> Tuple[str, ...]:
        """The settings, as ``name=value``, that leave the block this
        module began with (LayerNorm, two-matrix GELU FFN, tied embedding,
        one prediction head, one dtype throughout, attention over the
        whole prefix): what a caller with its own copy of the embedding,
        the norms or the logits (``generate``, ``pipeline_lm``) must
        refuse by name until it handles them."""
        classic = TransformerConfig()
        names = ["norm", "ffn", "tie_embeddings", "n_pred_heads",
                 "residual_dtype", "attn_head_dim", "position_table",
                 "layer_pattern", "qk_norm", "moe_router_bias",
                 "dense_d_ff"]
        out = [f"{n}={getattr(self, n)!r}" for n in names
               if getattr(self, n) != getattr(classic, n)]
        if self.attention_impl == "eva":
            out.append("attention_impl='eva'")
        return tuple(out)

    @property
    def head_dim(self) -> int:
        if self.attn_head_dim is not None:
            return self.attn_head_dim
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        kv = self.n_heads if self.n_kv_heads is None else self.n_kv_heads
        if not 1 <= kv <= self.n_heads or self.n_heads % kv:
            raise ValueError(
                f"mpi_tpu: n_kv_heads={kv} must divide n_heads="
                f"{self.n_heads}")
        return kv


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def _dense_init(key, shape, dtype, fan_in):
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def init_params(key: jax.Array, cfg: TransformerConfig) -> Dict[str, Any]:
    """Initialise the parameter pytree (plain dicts — easy to shard,
    checkpoint, and inspect)."""
    pd = cfg.param_dtype
    keys = jax.random.split(key, 2 + cfg.n_layers)
    params: Dict[str, Any] = {
        "embed": _dense_init(keys[0], (cfg.vocab, cfg.d_model), pd,
                             cfg.d_model),
        "final_ln": _norm_init(cfg),
        "blocks": [],
    }
    if not cfg.tie_embeddings:
        # Keys for the leaves newer than the classic block are folded in,
        # not split off, so a classic configuration draws what it drew.
        params["head"] = _dense_init(
            jax.random.fold_in(keys[0], 1),
            (cfg.n_pred_heads * cfg.vocab, cfg.d_model), pd, cfg.d_model)
    if _has_pos_table(cfg):  # rope needs no learned position table
        params["pos"] = _dense_init(keys[1], (cfg.max_seq, cfg.d_model),
                                    pd, cfg.d_model)
    if cfg.layer_pattern is not None:
        params["blocks"] = [_init_pattern_block(keys[2 + i], kind, cfg)
                            for i, kind in enumerate(cfg.layer_pattern)]
        return params
    for i in range(cfg.n_layers):
        ks = jax.random.split(keys[2 + i], 6)
        h, d, f = cfg.n_heads, cfg.d_model, cfg.d_ff
        hd, kv = cfg.head_dim, cfg.kv_heads
        blk = {
            "ln1": _norm_init(cfg),
            "ln2": _norm_init(cfg),
            "wq": _dense_init(ks[0], (d, h, hd), pd, d),
            "wk": _dense_init(ks[1], (d, kv, hd), pd, d),
            "wv": _dense_init(ks[2], (d, kv, hd), pd, d),
            "wo": _dense_init(ks[3], (h, hd, d), pd, d),
        }
        if cfg.n_experts > 0:
            from .moe import init_moe_params

            blk["moe"] = init_moe_params(ks[4], d, f, cfg.n_experts, pd)
        else:
            blk["w1"] = _dense_init(ks[4], (d, f), pd, d)
            blk["w2"] = _dense_init(ks[5], (f, d), pd, f)
            if cfg.ffn == "swiglu":
                blk["w3"] = _dense_init(jax.random.fold_in(keys[2 + i], 6),
                                        (d, f), pd, d)
        if cfg.qk_norm:
            blk.update(_qk_norm_init(cfg))
        if cfg.attention_impl == "eva":
            # Unit normal, not zero: with keys and queries of unit scale
            # the pooling weights are uneven and the summaries' offset
            # moves their scores, so both weigh in every comparison.
            blk["eva_phi"] = jax.random.normal(
                jax.random.fold_in(keys[2 + i], 7), (h, hd)).astype(pd)
            blk["eva_mu"] = jax.random.normal(
                jax.random.fold_in(keys[2 + i], 8), (h, hd)).astype(pd)
        params["blocks"].append(blk)
    return params


def _has_pos_table(cfg: TransformerConfig) -> bool:
    return cfg.position_table and not cfg.rope


def _init_pattern_block(key, kind: str, cfg: TransformerConfig):
    """One block of a ``layer_pattern`` stack: ``ln1`` and the leaves of
    its one sub-layer."""
    d, pd = cfg.d_model, cfg.param_dtype
    blk = {"ln1": _norm_init(cfg)}
    if kind == "M":
        from .mamba2 import init_mamba2_params

        blk.update(init_mamba2_params(key, cfg))
    elif kind == "C":
        from .short_conv import init_short_conv_params

        blk.update(init_short_conv_params(key, cfg))
    elif kind == "E":
        from .moe import init_routed_share_params

        blk.update(init_routed_share_params(
            key, d, cfg.d_ff, cfg.moe_shared_d_ff, cfg.n_experts,
            cfg.experts_held, pd, gated=cfg.ffn == "swiglu",
            select_bias=cfg.moe_router_bias,
            bias_std=cfg.moe_router_bias_std))
    elif kind == "F":
        ks, f = jax.random.split(key, 3), cfg.dense_d_ff
        blk.update(w1=_dense_init(ks[0], (d, f), pd, d),
                   w2=_dense_init(ks[1], (f, d), pd, f))
        if cfg.ffn == "swiglu":
            blk["w3"] = _dense_init(ks[2], (d, f), pd, d)
    else:
        ks = jax.random.split(key, 4)
        h, hd, kv = cfg.n_heads, cfg.head_dim, cfg.kv_heads
        blk.update(wq=_dense_init(ks[0], (d, h, hd), pd, d),
                   wk=_dense_init(ks[1], (d, kv, hd), pd, d),
                   wv=_dense_init(ks[2], (d, kv, hd), pd, d),
                   wo=_dense_init(ks[3], (h, hd, d), pd, h * hd))
        if cfg.qk_norm:
            blk.update(_qk_norm_init(cfg))
    return blk


def _pattern_block_specs(kind: str, norm, cfg: TransformerConfig
                         ) -> Dict[str, Any]:
    """Replicated throughout: a ``layer_pattern`` stack refuses a mesh
    that would split a layer (``_refuse_split_mesh``)."""
    if kind == "M":
        from .mamba2 import mamba2_specs

        leaves = mamba2_specs()
    elif kind == "C":
        from .short_conv import short_conv_specs

        leaves = short_conv_specs()
    elif kind == "E":
        from .moe import routed_share_specs

        leaves = routed_share_specs(
            gated=cfg.ffn == "swiglu", shared=cfg.moe_shared_d_ff > 0,
            select_bias=cfg.moe_router_bias)
    elif kind == "F":
        leaves = {name: P() for name in (
            ("w1", "w2", "w3") if cfg.ffn == "swiglu" else ("w1", "w2"))}
    else:
        leaves = {name: P() for name in ("wq", "wk", "wv", "wo")}
        if cfg.qk_norm:
            leaves.update(q_norm={"scale": P()}, k_norm={"scale": P()})
    return dict(leaves, ln1=dict(norm))


def _qk_norm_init(cfg: TransformerConfig) -> Dict[str, Any]:
    """The weights of the q and k norms, ``1 + scale`` with scale zero."""
    return {name: {"scale": jnp.zeros((cfg.head_dim,), cfg.param_dtype)}
            for name in ("q_norm", "k_norm")}


def _norm_init(cfg: TransformerConfig) -> Dict[str, Any]:
    d, pd = cfg.d_model, cfg.param_dtype
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)}
    return {"scale": jnp.zeros((d,), pd)}   # the weight is 1 + scale


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpecs mirroring :func:`init_params`'s tree.

    Megatron-style tp split: q/k/v column-parallel over heads, wo
    row-parallel; w1 column-, w2 row-parallel over d_ff. Everything small
    (layernorms, biases, positional table) is replicated. The embedding is
    vocab-sharded over tp (the logits matmul then reduces over tp)."""
    norm = ({"scale": P(), "bias": P()} if cfg.norm == "layernorm"
            else {"scale": P()})
    blk = {
        "ln1": dict(norm),
        "ln2": dict(norm),
        "wq": P(None, "tp", None),
        "wk": P(None, "tp", None),
        "wv": P(None, "tp", None),
        "wo": P("tp", None, None),
    }
    if cfg.n_experts > 0:
        from .moe import moe_specs

        blk["moe"] = moe_specs()
    else:
        blk["w1"] = P(None, "tp")
        blk["w2"] = P("tp", None)
        if cfg.ffn == "swiglu":
            blk["w3"] = P(None, "tp")
    if cfg.qk_norm:
        blk.update(q_norm={"scale": P()}, k_norm={"scale": P()})
    if cfg.attention_impl == "eva":
        blk["eva_phi"] = P("tp", None)
        blk["eva_mu"] = P("tp", None)
    specs = {
        "embed": P("tp", None),
        "final_ln": dict(norm),
        "blocks": ([dict(blk) for _ in range(cfg.n_layers)]
                   if cfg.layer_pattern is None else
                   [_pattern_block_specs(kind, norm, cfg)
                    for kind in cfg.layer_pattern]),
    }
    if not cfg.tie_embeddings:
        specs["head"] = P("tp", None)
    if _has_pos_table(cfg):
        specs["pos"] = P()
    return specs


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

_NORM_EPS = 1e-5   # both norms; every configuration run so far states it


def _layernorm(x, scale, bias, eps=_NORM_EPS):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def _norm(x, p, cfg: TransformerConfig):
    """The configured normalisation of the residual stream ``x``, computed
    in the stream's dtype and handed on in the compute dtype."""
    if cfg.norm == "layernorm":
        y = _layernorm(x, p["scale"].astype(x.dtype),
                       p["bias"].astype(x.dtype))
    else:
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        y = x * lax.rsqrt(ms + _NORM_EPS) * (1 + p["scale"].astype(x.dtype))
    return y.astype(cfg.dtype)


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0) -> jax.Array:
    """Rotary position embedding: rotate each half-dim pair of ``x``
    ``(b, s, h, hd)`` by its position's phase. ``positions`` is ``(s,)``
    int32 global positions (works for shifted windows — decode passes
    ``n_valid + arange``). Phases are computed in float32 and the result
    cast back to x's dtype."""
    hd = x.shape[-1]
    if hd % 2:
        raise ValueError(f"mpi_tpu: rope needs an even head_dim, got {hd}")
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs  # (s, half)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def repeat_kv_heads(k, v, cfg: TransformerConfig):
    """Expand GQA k/v ``(b, s, kv_heads, hd)`` to full ``n_heads`` for
    kernels that expect equal q/k head counts — every impl EXCEPT
    ``flash``, whose Pallas kernels read grouped heads natively through
    their index maps, and the decode path (generate._attend_cached),
    whose contraction stays grouped. Here the repeat MATERIALISES the
    group-times-larger k/v, so for these impls GQA saves projection
    weights but not attention activation memory."""
    group = cfg.n_heads // cfg.kv_heads
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    return k, v


def _attention(x, blk, cfg: TransformerConfig, mesh: Optional[Mesh] = None):
    """Causal multi-head attention; heads are the tp-sharded axis, so every
    einsum below is head-batched and GSPMD keeps it local to each tp shard
    until ``wo`` reduces back to d_model. The score/value kernel is
    selected by ``cfg.attention_impl`` (see :mod:`mpi_tpu.ops`)."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, blk["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, blk["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, blk["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = _head_norm(q, blk["q_norm"])
        k = _head_norm(k, blk["k_norm"])
    if cfg.rope:
        # Global positions, applied BEFORE any sequence sharding — the
        # ring/zigzag layouts then carry already-rotated values.
        pos = jnp.arange(s, dtype=jnp.int32)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    # Named before any consumer (EVA's pooling reads k, v too), for
    # checkpointed_block; outside a checkpoint a name is the identity.
    q = checkpoint_name(q, "attn_q")
    k = checkpoint_name(k, "attn_k")
    v = checkpoint_name(v, "attn_v")
    impl = cfg.attention_impl
    if impl != "flash":
        # The flash kernel reads grouped kv heads natively through its
        # index maps; every other impl expects equal head counts.
        k, v = repeat_kv_heads(k, v, cfg)
    if impl == "flash":
        from ..ops import flash_attention

        ctx = _kernel_per_shard(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, cfg.causal),
            mesh, impl, "needs ring_flash|zigzag_flash|ulysses_flash",
            (q, k, v))
    elif impl == "eva":
        from ..ops import eva_attention

        def eva(q_, k_, v_, phi, mu):
            return eva_attention(q_, k_, v_, phi.astype(q_.dtype),
                                 mu.astype(q_.dtype), cfg.eva_window,
                                 cfg.eva_chunk)

        ctx = _kernel_per_shard(
            eva, mesh, impl, "is not supported", (q, k, v),
            per_head=(blk["eva_phi"], blk["eva_mu"]))
    elif impl == "blockwise":
        from ..ops import blockwise_attention

        ctx = blockwise_attention(q, k, v, causal=cfg.causal)
    elif impl in ("ring", "zigzag", "ring_flash", "zigzag_flash"):
        from ..parallel.ring_attention import ring_attention_sharded

        if mesh is None:
            raise ValueError(
                f"attention_impl={impl!r} needs a mesh with an 'sp' axis")
        layout = "zigzag" if impl.startswith("zigzag") else "contiguous"
        chunk = "flash" if impl.endswith("_flash") else "fold"
        # causal=False works on the contiguous ring; the zigzag layout
        # is causal-only and ring_attention_sharded raises for it at
        # its own layer (the balance trick assumes the triangle).
        ctx = ring_attention_sharded(q, k, v, mesh, axis_name="sp",
                                     causal=cfg.causal, layout=layout,
                                     chunk_impl=chunk)
    elif impl in ("ulysses", "ulysses_flash"):
        from ..parallel.ulysses import ulysses_attention_sharded

        if mesh is None:
            raise ValueError(
                f"attention_impl={impl!r} needs a mesh with an 'sp' axis")
        kernel = "flash" if impl.endswith("_flash") else "blockwise"
        ctx = ulysses_attention_sharded(q, k, v, mesh, axis_name="sp",
                                        causal=cfg.causal,
                                        kernel_impl=kernel)
    elif impl == "dense":
        from ..ops import dense_attention

        ctx = dense_attention(q, k, v, causal=cfg.causal)
    else:
        raise ValueError(
            f"unknown attention_impl {impl!r}: expected dense|flash|"
            f"blockwise|ring|ring_flash|zigzag|zigzag_flash|ulysses|"
            f"ulysses_flash|eva")
    return jnp.einsum("bshk,hkd->bsd", ctx, blk["wo"].astype(x.dtype))


def _head_norm(x, p):
    """RMSNorm over the last axis (a head's values), weight ``1 + scale``,
    in float32; handed on in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(ms + _NORM_EPS)
            * (1 + p["scale"].astype(jnp.float32))).astype(x.dtype)


def _kernel_per_shard(fn, mesh: Optional[Mesh], impl: str, sp_advice: str,
                      qkv, per_head=()):
    """Run an attention kernel ``fn(q, k, v, *per_head)`` that attends
    within one device's sequence. GSPMD cannot partition a Mosaic kernel,
    so on a real mesh it runs per shard like the ring/ulysses family:
    batch over dp, heads (q, grouped kv and the ``(heads, hd)`` vectors in
    ``per_head`` alike) over tp; one chip and ``mesh=None`` call it
    directly."""
    if mesh is None or mesh.size == 1:
        return fn(*qkv, *per_head)
    if mesh.shape.get("sp", 1) > 1:
        raise ValueError(
            f"attention_impl={impl!r} attends within one device's "
            f"sequence; a mesh with sp > 1 {sp_advice}")
    spec = sanitize_spec(P("dp", None, "tp", None), mesh)
    vec = sanitize_spec(P("tp", None), mesh)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * 3 + (vec,) * len(per_head),
        out_specs=spec, check_vma=False)(*qkv, *per_head)


def sanitize_spec(spec: P, mesh: Optional[Mesh]) -> P:
    """Drop axis names the mesh doesn't have (→ replicated) so one set of
    canonical specs works on any mesh shape (e.g. a dp x ep MoE mesh has
    no 'tp'; a pure-tp mesh has no 'sp')."""
    if mesh is None:
        return spec
    names = set(mesh.axis_names)

    def keep(p):
        if p is None:
            return None
        if isinstance(p, tuple):
            kept = tuple(q for q in p if q in names)
            return kept if kept else None
        return p if p in names else None

    return P(*(keep(p) for p in spec))


def _act_constraint(x, mesh: Optional[Mesh]):
    """Keep activations dp-sharded on batch and sp-sharded on sequence
    between blocks; a no-op when tracing without a mesh (single chip)."""
    if mesh is None:
        return x
    return lax.with_sharding_constraint(
        x, NamedSharding(mesh, sanitize_spec(P("dp", "sp", None), mesh)))


def _ffn(x, blk, cfg: TransformerConfig, mesh: Optional[Mesh]):
    """Position-wise FFN: Megatron-split dense (default) or top-1 routed
    MoE over the 'ep' axis. Returns (y, aux_loss)."""
    if cfg.n_experts > 0:
        from .moe import moe_ffn

        return moe_ffn(x, blk["moe"], cfg.n_experts,
                       capacity_factor=cfg.capacity_factor, mesh=mesh,
                       top_k=cfg.moe_top_k)
    return _dense_ffn(x, blk, cfg), jnp.zeros((), jnp.float32)


def _dense_ffn(x, blk, cfg: TransformerConfig):
    """The dense FFN of kind ``cfg.ffn`` (its width is its matrices')."""
    h = checkpoint_name(
        jnp.einsum("bsd,df->bsf", x, blk["w1"].astype(x.dtype)), "ffn_gate")
    if cfg.ffn == "swiglu":
        h = jax.nn.silu(h) * checkpoint_name(
            jnp.einsum("bsd,df->bsf", x, blk["w3"].astype(x.dtype)),
            "ffn_up")
    elif cfg.ffn == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, blk["w2"].astype(x.dtype))


def _refuse_split_mesh(cfg: TransformerConfig, mesh: Optional[Mesh]):
    """A ``layer_pattern`` stack runs each layer whole on every device:
    its mixers, its share of the experts and their dispatch know no
    ``tp``, ``ep`` or ``sp``. Refused by name, not run wrongly."""
    if mesh is None:
        return
    split = [f"{a}={mesh.shape[a]}" for a in ("tp", "ep", "sp")
             if mesh.shape.get(a, 1) > 1]
    if split:
        raise ValueError(
            f"mpi_tpu: layer_pattern={cfg.layer_pattern!r} on a mesh with "
            f"{', '.join(split)}: the Mamba-2 mixer (M), the short conv "
            f"(C), attention (*), the dense FFN (F), the routed share (E) "
            f"and its dispatch are not split over tp, ep or sp; use dp")


def _pattern_block(x, blk, cfg: TransformerConfig, mesh: Optional[Mesh],
                   kind: str):
    """Block of kind ``kind`` of a ``layer_pattern`` stack: ``x +
    f(norm(x))`` with one sub-layer. The mixers (``M``, ``C``, ``*``)
    stand under the scope ``attn``, the experts (``E``) and the dense FFN
    (``F``) under ``ffn``: the mixer and the feed-forward slot of the
    classic block, so that a trace's layer scopes mean what they meant."""
    with jax.named_scope("ffn" if kind in "EF" else "attn"):
        h = _norm(x, blk["ln1"], cfg)
        if kind == "M":
            from .mamba2 import mamba2_mixer

            y = mamba2_mixer(h, blk, cfg, mesh)
        elif kind == "C":
            from .short_conv import short_conv_mixer

            y = short_conv_mixer(h, blk)
        elif kind == "E":
            from .moe import routed_share_ffn

            y = routed_share_ffn(
                h, blk, cfg.n_experts, cfg.moe_top_k,
                offset=cfg.moe_expert_offset, scale=cfg.moe_routed_scale)
        elif kind == "F":
            y = _dense_ffn(h, blk, cfg)
        else:
            y = _attention(h, blk, cfg, mesh)
        x = x + y.astype(x.dtype)
    return _act_constraint(x, mesh), jnp.zeros((), jnp.float32)


def block_body(x, blk, cfg: TransformerConfig,
               mesh: Optional[Mesh] = None, kind: Optional[str] = None):
    """ONE transformer block (pre-norm attention + FFN residuals) —
    the single definition shared by the sequential stack
    (:func:`forward_with_aux`) and the pipelined stages
    (:mod:`mpi_tpu.models.pipeline_lm`), so the two paths cannot
    drift. Returns ``(x, aux_loss)``. ``kind`` is the block's letter in
    ``cfg.layer_pattern`` (None = the classic block)."""
    if kind is not None:
        return _pattern_block(x, blk, cfg, mesh, kind)
    # The named scopes here and in forward_with_aux / token_xent / the
    # train step are what a profiler trace's device ops are grouped by
    # (docs/OBSERVABILITY.md): metadata only, the program is unchanged.
    # ``x`` is the residual stream (``cfg.stream_dtype``); the norms hand
    # the compute dtype to the matmuls and the sums are the stream's.
    with jax.named_scope("attn"):
        h = _norm(x, blk["ln1"], cfg)
        x = x + _attention(h, blk, cfg, mesh).astype(x.dtype)
    x = _act_constraint(x, mesh)
    with jax.named_scope("ffn"):
        h = _norm(x, blk["ln2"], cfg)
        y, blk_aux = _ffn(h, blk, cfg, mesh)
        x = x + y.astype(x.dtype)
    return _act_constraint(x, mesh), blk_aux


# What a block under ``remat`` holds from its forward for its backward:
# of the values named in ``_attention``, ``_ffn`` and the attention ops'
# forward rules, those that cost far more to redo than to hold. Everything
# else is recomputed. Chosen on a v5e in the benchmark's EvaByte cell (4
# layers of d 4096 / ff 11008, one sequence of 16,384 bytes, bfloat16;
# PERF.md, PR 35): bytes held a token a layer; ms a step and tokens/s, one
# run each, every run correct; GiB the v5e's compiler counts for the step
# / for the benchmark's correctness program with the optimizer state
# beside it, of the chip's 15.75. The rule: the fastest; of two within 1%
# the one that holds less; none that leaves under 0.5 GiB.
#
#   (nothing: the bare checkpoint)    0  1,072.4  15,277  13.741 / 13.633
#   out lse                       8,320  1,031.5  15,883  13.753 / 13.773
#   out lse gate                 30,336    995.2  16,462  14.761 / 14.781  <-
#   out lse gate ks vs           31,360    994.7  16,470  14.894 / 14.819
#   out lse gate q               38,528    985.1  16,630  15.324 / 15.025
#   out lse gate ks vs k v       47,744    984.8  16,635  15.193 / 15.427
#   out lse gate k v             46,720  not run          15.161 / 15.521
#   out lse ks vs q k v          33,920  not run          14.827 / 15.377
#   out lse gate up              52,352  does not fit     15.474 / 15.866
#
# ``out`` + ``lse`` (``attn_out``, ``attn_lse``) take the forward kernels'
# second run a layer away for 12 MiB more at the compiler's peak; the gate
# pre-activation ``x @ w1`` (``ffn_gate``) one of the three recomputed FFN
# matmuls for 344 MiB a layer.
#
# The Mamba-2 scan's forward rule (``ops/ssd.py``, PR 37) has two outputs a
# block could hold: ``y`` and the float32 states entering the chunks, which
# its backward kernel reads. Tried under the names ``ssm_y`` and
# ``ssm_states`` in the benchmark's nemotron cell (four mixers at b 2 x
# 8,192, 64 heads of 64, state 128; PERF.md, PR 37): bytes of the scan held
# a token a mixer; ms a step and tokens/s, one run each; GiB the v5e's
# compiler counts for the step:
#
#   out lse gate                      0    483.7  33,869  10.561          <-
#   out lse gate y states        24,576    488.1  33,567  11.849
#   out lse gate y (or states)   not run: the forward kernel writes both,
#                                so it runs twice a mixer as with neither
#
# Held together they spare the forward kernel's second run (2.6 ms a
# mixer) and the step is slower all the same, for 1.29 GiB more: the rule
# keeps neither, and the scan's rule names nothing until a shape turns that.
#
# An ``M`` and an ``E`` block of a ``layer_pattern`` stack name three values
# more (``models/mamba2.py``, ``models/moe.py``): ``ssm_in``, the
# in-projection's product in the compute dtype; ``ssm_conv``, the conv's
# float32 pre-activation; ``moe_shared_up``, the shared expert's
# up-projection before ``relu2``. The same cell (four mixers, four expert
# layers, shared width 3,712; PERF.md, PR 39): bytes held a token a block
# (``in`` 20,608; ``conv`` 24,576; ``up`` 7,424); ms a step and tokens/s,
# one run each, every run correct; GiB for the step / for the correctness
# program with the 4.97 GiB optimizer state beside it; the largest
# distance of a matrix of block 0's gradient from the float32 reference
# in that run, of the 0.06 the cell allows:
#
#   out lse gate                  0  482.3  33,940  10.561 /  9.013  0.023
#   out lse gate in          20,608  460.9  35,509  11.509 /  9.646  0.024  <-
#   out lse gate in up       28,032  452.5  36,188  11.849 /  9.872  0.049
#   out lse gate in conv     45,184  459.4  35,635  13.009 / 10.395  0.023
#   out lse gate in conv up  52,608  450.9  36,311  13.349 / 10.622  0.045
#
# ``in`` takes the product's second run a mixer away (5.4 ms each).
# ``conv`` buys 0.3% for 1.5 GiB: within 1%, and lost. ``up`` buys 1.9%
# and the speed rule would keep it, but it moves the result: ``jax.
# checkpoint`` rounds a held value to its dtype where it is produced
# (``reduce_precision``, so that forward and backward read one value),
# and without it the chip's compiler carries the product's float32
# accumulator through ``relu2`` and rounds once, after the square. Held,
# the gradient stands twice as far from the reference, at four fifths of
# the cell's limit. So the rule has a line more: none that moves the
# step's values. ``ssm_in`` moves nothing: its consumers are slices, so the
# product was stored in its dtype already.
#
# One constant serves every kind of block, since a name exists only where
# its value is produced: a classic block holds ``out lse gate``, an ``M``
# block ``in``, an ``E`` block nothing, a ``*`` block ``out lse``, an ``F``
# block (the classic block's dense FFN) ``gate``, a ``C`` block nothing
# (its in-projection is named ``shortconv_in``, untried: PR 40 added the
# kind and tuned nothing). Choosing
# the set from the compiler's ``memory_analysis`` waits for two cells that
# want different sets of the same names; at a longer sequence the bytes a
# token above say what a held value costs.
_REMAT_KEEPS = ("attn_out", "attn_lse", "ffn_gate", "ssm_in")


def checkpointed_block(cfg: TransformerConfig, mesh: Optional[Mesh] = None,
                       kind: Optional[str] = None):
    """``block(x, blk) -> (x, aux_loss)``: :func:`block_body` for ``cfg``
    on ``mesh`` (of ``kind``, in a ``layer_pattern`` stack), and the one
    place it is wrapped for ``cfg.remat``. The
    wrapped block's backward recomputes its forward except the named
    values of ``_REMAT_KEEPS``, which it holds. With tracing on
    (docs/OBSERVABILITY.md) each wrapped call adds 1 to ``remat.blocks``
    and the bytes its backward would hold to ``remat.kept_bytes``: at
    trace time, from the shapes."""
    block = functools.partial(block_body, cfg=cfg, mesh=mesh,
                              **({} if kind is None else {"kind": kind}))
    if not cfg.remat:
        return block
    kept = jax.checkpoint(
        block,
        policy=jax.checkpoint_policies.save_only_these_names(*_REMAT_KEEPS))

    def counted(x, blk):
        if trace.enabled():
            trace.count("remat.blocks")
            trace.count("remat.kept_bytes", _kept_bytes(kept, x, blk))
        return kept(x, blk)

    return counted


def _kept_bytes(fn, *args) -> int:
    """Bytes ``fn``'s backward holds from its forward besides ``args``
    themselves, from the shapes: what is held, less one of the same shape
    and dtype for every leaf of ``args``."""
    held = jax.eval_shape(lambda *a: jax.vjp(fn, *a)[1], *args)
    left = collections.Counter(
        (x.shape, x.dtype) for x in jax.tree.leaves(held))
    left.subtract((x.shape, x.dtype) for x in jax.tree.leaves(args))
    return sum(n * math.prod(shape) * dtype.itemsize
               for (shape, dtype), n in left.items() if n > 0)


def token_xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy as ``logsumexp - target_logit`` —
    the fused form that never materialises the (b, s, vocab) float32
    log-softmax. Shared by the sequential and pipelined losses."""
    with jax.named_scope("logits_loss"):
        logits32 = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits32, axis=-1)
        tgt = jnp.take_along_axis(logits32, targets[..., None],
                                  axis=-1)[..., 0]
        return jnp.mean(lse - tgt)


def pred_heads_xent(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Multi-token-prediction loss. ``logits`` ``(b, s, P, vocab)`` are
    those of the inputs ``tokens[:, :s]``; head ``p`` at position ``t``
    predicts ``tokens[:, t + 1 + p]``. Each head's cross-entropy is
    averaged over the positions whose target exists (``t + 1 + p <= s``,
    ``tokens`` being ``s + 1`` long), and the heads weigh equally."""
    with jax.named_scope("logits_loss"):
        _, s, heads, _ = logits.shape
        at = (jnp.arange(s)[:, None] + 1 + jnp.arange(heads)[None, :])
        exists = at <= s
        targets = tokens[:, jnp.minimum(at, s)]               # (b, s, P)
        logits32 = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits32, axis=-1)
        tgt = jnp.take_along_axis(logits32, targets[..., None],
                                  axis=-1)[..., 0]
        nll = jnp.where(exists[None], lse - tgt, 0.0)
        per_head = nll.sum(axis=(0, 1)) / (
            tokens.shape[0] * exists.sum(axis=0))
        return jnp.mean(per_head)


def _embed(params, tokens, cfg: TransformerConfig, mesh: Optional[Mesh]):
    """tokens (batch, seq) -> the residual stream entering block 0."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.stream_dtype)[tokens]
        if _has_pos_table(cfg):
            x = x + params["pos"].astype(cfg.stream_dtype)[
                :tokens.shape[1]][None]
    return _act_constraint(x, mesh)


def forward_with_aux(params: Dict[str, Any], tokens: jax.Array,
                     cfg: TransformerConfig,
                     mesh: Optional[Mesh] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """tokens (batch, seq) int32 → (logits (batch, seq, vocab), aux_loss).
    ``aux_loss`` is the summed MoE load-balance penalty (0 for dense).
    With ``cfg.n_pred_heads`` = P > 1 the logits are (batch, seq, P,
    vocab); those of an untied head are float32."""
    b, s = tokens.shape
    x = _embed(params, tokens, cfg, mesh)
    aux = jnp.zeros((), jnp.float32)

    if cfg.layer_pattern is None:
        block = checkpointed_block(cfg, mesh)
        blocks = [block] * len(params["blocks"])
    else:
        _refuse_split_mesh(cfg, mesh)
        by_kind = {kind: checkpointed_block(cfg, mesh, kind)
                   for kind in sorted(set(cfg.layer_pattern))}
        blocks = [by_kind[kind] for kind in cfg.layer_pattern]
    for block, blk in zip(blocks, params["blocks"]):
        x, blk_aux = block(x, blk)
        aux = aux + blk_aux
    with jax.named_scope("logits_loss"):
        x = _norm(x, params["final_ln"], cfg)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"].astype(x.dtype))
        else:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["head"].astype(x.dtype),
                                preferred_element_type=jnp.float32)
            if cfg.n_pred_heads > 1:
                logits = logits.reshape(b, s, cfg.n_pred_heads, cfg.vocab)
    return logits, aux


def forward(params: Dict[str, Any], tokens: jax.Array,
            cfg: TransformerConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    """tokens (batch, seq) int32 → logits (batch, seq, vocab)."""
    return forward_with_aux(params, tokens, cfg, mesh)[0]


def routed_choices(params: Dict[str, Any], tokens: jax.Array,
                   cfg: TransformerConfig, mesh: Optional[Mesh] = None
                   ) -> List[Tuple[jax.Array, jax.Array]]:
    """What the routed layers of a ``layer_pattern`` stack decide for
    ``tokens`` (batch, seq): for every ``E`` block in order, the router's
    input ``(batch * seq, d_model)`` in the compute dtype and the experts
    it chose ``(batch * seq, moe_top_k)`` among all ``cfg.n_experts`` (by
    the scores plus the selection bias, where the block has one).
    The forward pass of :func:`forward_with_aux`, block by block; for load
    statistics, and for a reference that is to follow the program's
    routing."""
    from .moe import route_top_k

    if cfg.layer_pattern is None:
        raise ValueError("mpi_tpu: routed_choices needs a layer_pattern")
    _refuse_split_mesh(cfg, mesh)
    x = _embed(params, tokens, cfg, mesh)
    choices = []
    for blk, kind in zip(params["blocks"], cfg.layer_pattern):
        if kind == "E":
            h = _norm(x, blk["ln1"], cfg).reshape(-1, cfg.d_model)
            choices.append((h, route_top_k(
                h, blk["router"], cfg.moe_top_k,
                bias=blk.get("router_bias"))[0]))
        x, _ = block_body(x, blk, cfg, mesh, kind)
    return choices


def loss_fn(params, tokens, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None) -> jax.Array:
    """Next-token cross-entropy (mean over all predicted positions), plus
    the MoE load-balance penalty when experts are enabled.

    Written as ``logsumexp - target_logit`` rather than gathering from a
    materialised ``log_softmax``: the full (batch, seq, vocab) float32
    log-prob tensor never exists, saving its HBM round-trips at large
    vocab (the backward of logsumexp produces the softmax directly)."""
    logits, aux = forward_with_aux(params, tokens[:, :-1], cfg, mesh)
    if cfg.n_pred_heads > 1:
        return pred_heads_xent(logits, tokens) + cfg.moe_aux_coef * aux
    return token_xent(logits, tokens[:, 1:]) + cfg.moe_aux_coef * aux


# --------------------------------------------------------------------------
# Training step
# --------------------------------------------------------------------------

def make_optimizer(optimizer: str = "adamw", learning_rate: float = 1e-3,
                   warmup_steps: int = 0, total_steps: Optional[int] = None):
    """An optax optimizer by name with an optional schedule.

    ``optimizer``: ``"adamw"`` (default), ``"adafactor"`` (factored
    second moment — the TPU-classic choice when optimizer state must not
    double the parameter memory), or ``"sgd"`` (momentum 0.9).

    Schedule: with ``total_steps``, linear warmup over ``warmup_steps``
    into cosine decay to 10% of peak at ``total_steps``; with only
    ``warmup_steps``, linear warmup then constant; otherwise constant
    ``learning_rate``."""
    import optax

    if total_steps is not None:
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=learning_rate,
            warmup_steps=max(warmup_steps, 1), decay_steps=total_steps,
            end_value=0.1 * learning_rate)
    elif warmup_steps:
        lr = optax.linear_schedule(0.0, learning_rate, warmup_steps)
    else:
        lr = learning_rate
    if optimizer == "adamw":
        return optax.adamw(lr)
    if optimizer == "adafactor":
        return optax.adafactor(learning_rate=lr)
    if optimizer == "sgd":
        return optax.sgd(lr, momentum=0.9)
    raise ValueError(
        f"mpi_tpu: unknown optimizer {optimizer!r}: expected "
        f"adamw|adafactor|sgd")


def sane_param_specs(cfg: TransformerConfig, params: Any,
                     mesh: Optional[Mesh]):
    """:func:`param_specs` restructured to ``params``'s tree with every
    spec sanitized against ``mesh`` (axes the mesh lacks drop out)."""
    specs = param_specs(cfg)
    return jax.tree.unflatten(
        jax.tree.structure(params),
        [sanitize_spec(s, mesh) for s in jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(s, P))])


def init_sharded_params(key: jax.Array, cfg: TransformerConfig,
                        mesh: Mesh) -> Dict[str, Any]:
    """Fresh parameters committed to their mesh shardings — params
    only, no optimizer state (callers that need just a base model, e.g.
    LoRA fine-tuning, avoid allocating and discarding AdamW moments)."""
    params = init_params(key, cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, sane_param_specs(cfg, params, mesh))


def _keep_untrained(cfg: TransformerConfig, new, old):
    """``new`` parameters with the leaves no step trains as in ``old``:
    the routers' selection bias (its gradient is zero, but AdamW's decay
    would still pull it towards zero)."""
    if cfg.layer_pattern is None or not cfg.moe_router_bias:
        return new
    return dict(new, blocks=[
        dict(n, router_bias=o["router_bias"]) if "router_bias" in o else n
        for n, o in zip(new["blocks"], old["blocks"])])


def make_train_parts(cfg: TransformerConfig, mesh: Optional[Mesh] = None,
                     learning_rate: float = 1e-3, grad_accum: int = 1,
                     optimizer: str = "adamw", warmup_steps: int = 0,
                     total_steps: Optional[int] = None,
                     zero1: bool = False, fsdp: bool = False):
    """Build (init_state, step_body) with ``step_body`` left un-jitted —
    for callers that embed the step in a larger program (the bench
    harness scans it; :func:`make_train_step` jits it as-is). Both
    callers therefore run the *same* optimizer step by construction.

    ``grad_accum=k`` splits the batch into ``k`` microbatches scanned
    inside the step: gradients average across microbatches before ONE
    optimizer update, so a batch k× larger than fits in HBM trains with
    the full-batch math up to float reduction order (with MoE, the
    load-balance aux loss is additionally computed per microbatch and
    averaged). The batch must divide by ``k``.

    ``optimizer``/``warmup_steps``/``total_steps`` select the update
    rule and schedule — see :func:`make_optimizer`.

    ``zero1=True`` (requires a mesh with a ``dp`` axis) shards the
    optimizer state over ``dp`` (:mod:`mpi_tpu.parallel.zero`): GSPMD
    then turns the dp gradient psum into a reduce-scatter, updates
    each device's 1/dp state shard, and all-gathers the fresh params —
    AdamW state memory drops ~dp-fold with the same step math up to
    float reduction order.

    ``fsdp=True`` (ZeRO-3: requires a mesh with a ``dp`` axis) shards
    the PARAMETERS themselves over ``dp`` on top of any tp layout
    (:func:`mpi_tpu.parallel.zero.fsdp_specs`) — parameter AND
    optimizer memory drop ~dp-fold; GSPMD inserts just-in-time weight
    all-gathers per layer (re-run in the backward under ``cfg.remat``)
    and reduce-scatters the gradients straight into the shard. Same
    step math as plain dp up to float reduction order. Subsumes
    ``zero1`` (the optimizer state follows the sharded parameters);
    combining both flags is an error."""
    import optax

    trace.listen_compiles()
    if grad_accum < 1:
        raise ValueError(f"mpi_tpu: grad_accum must be >= 1, got "
                         f"{grad_accum}")
    if zero1 and (mesh is None or "dp" not in mesh.axis_names):
        raise ValueError(
            "mpi_tpu: zero1=True needs a mesh with a 'dp' axis")
    if fsdp and (mesh is None or "dp" not in mesh.axis_names):
        raise ValueError(
            "mpi_tpu: fsdp=True needs a mesh with a 'dp' axis")
    if fsdp and zero1:
        raise ValueError(
            "mpi_tpu: fsdp subsumes zero1 (optimizer state follows the "
            "dp-sharded parameters); pass only fsdp=True")
    if mesh is not None and "tp" in mesh.axis_names:
        tp = mesh.shape["tp"]
        if cfg.n_heads % tp or cfg.kv_heads % tp:
            raise ValueError(
                f"mpi_tpu: tp={tp} must divide n_heads={cfg.n_heads} and "
                f"kv_heads={cfg.kv_heads} (GQA shards kv heads over tp "
                f"too)")
    opt = make_optimizer(optimizer, learning_rate, warmup_steps,
                         total_steps)

    def _sane_param_specs(params):
        return sane_param_specs(cfg, params, mesh)

    def _fsdp_specs(params):
        from ..parallel.zero import fsdp_specs

        return fsdp_specs(params, _sane_param_specs(params), mesh)

    def init_state(key: jax.Array):
        if mesh is not None:
            params = init_sharded_params(key, cfg, mesh)
            if fsdp:
                from ..parallel.zero import (shard_opt_state,
                                             zero1_specs)

                fspecs = _fsdp_specs(params)
                params = jax.tree.map(
                    lambda x, s: jax.device_put(
                        x, NamedSharding(mesh, s)), params, fspecs)
                opt_state = jax.jit(opt.init)(params)
                # State leaves match param shapes, and _leaf_spec is a
                # no-op when dp is already claimed — so this commits
                # the moments to the SAME fully-sharded layouts.
                zspecs = zero1_specs(params, fspecs, opt_state, mesh)
                opt_state = shard_opt_state(opt_state, zspecs, mesh)
                return {"params": params, "opt": opt_state}
            opt_state = jax.jit(opt.init)(params)
            if zero1:
                from ..parallel.zero import shard_opt_state, zero1_specs

                zspecs = zero1_specs(params, _sane_param_specs(params),
                                     opt_state, mesh)
                opt_state = shard_opt_state(opt_state, zspecs, mesh)
        else:
            params = init_params(key, cfg)
            opt_state = opt.init(params)
        return {"params": params, "opt": opt_state}

    def accumulate(params, tokens):
        """(mean loss, mean grads) over grad_accum microbatches."""
        if grad_accum == 1:
            return jax.value_and_grad(loss_fn)(params, tokens, cfg, mesh)
        b = tokens.shape[0]
        if b % grad_accum:
            raise ValueError(
                f"mpi_tpu: batch {b} not divisible by grad_accum="
                f"{grad_accum}")
        micro = tokens.reshape(grad_accum, b // grad_accum,
                               *tokens.shape[1:])

        def body(carry, mtok):
            loss_sum, gsum = carry
            l, g = jax.value_and_grad(loss_fn)(params, mtok, cfg, mesh)
            return (loss_sum + l, jax.tree.map(jnp.add, gsum, g)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, params))
        (loss_sum, gsum), _ = lax.scan(body, zero, micro)
        inv = 1.0 / grad_accum
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, gsum)

    def step(state, tokens):
        if fsdp:
            from ..parallel.zero import (constrain_opt_state,
                                         constrain_params, zero1_specs)

            # Pin weights/grads/state to the fully-sharded layouts at
            # the step boundary so GSPMD keeps the JIT-gather +
            # grad-reduce-scatter plan instead of replicating between
            # steps (specs derive from the state itself, so restored
            # checkpoints behave identically).
            fspecs = _fsdp_specs(state["params"])
            params0 = constrain_params(state["params"], fspecs, mesh)
            loss, grads = accumulate(params0, tokens)
            grads = constrain_params(grads, fspecs, mesh)
            with jax.named_scope("optimizer"):
                updates, new_opt = opt.update(grads, state["opt"], params0)
                new_params = _keep_untrained(
                    cfg, optax.apply_updates(params0, updates), params0)
            new_params = constrain_params(new_params, fspecs, mesh)
            zspecs = zero1_specs(state["params"], fspecs, new_opt, mesh)
            new_opt = constrain_opt_state(new_opt, zspecs, mesh)
            return {"params": new_params, "opt": new_opt}, loss
        loss, grads = accumulate(state["params"], tokens)
        with jax.named_scope("optimizer"):
            updates, new_opt = opt.update(grads, state["opt"],
                                          state["params"])
            new_params = _keep_untrained(
                cfg, optax.apply_updates(state["params"], updates),
                state["params"])
        if zero1:
            from ..parallel.zero import constrain_opt_state, zero1_specs

            # Specs are derived at trace time from the state itself, so
            # the constraint holds even for states that bypassed
            # init_state (checkpoint restores); pinning the updated
            # state to the dp-sharded layouts keeps GSPMD on the
            # reduce-scatter/all-gather plan instead of replicating
            # state between steps.
            zspecs = zero1_specs(state["params"],
                                 _sane_param_specs(state["params"]),
                                 new_opt, mesh)
            new_opt = constrain_opt_state(new_opt, zspecs, mesh)
        return {"params": new_params, "opt": new_opt}, loss

    return init_state, step


def make_train_step(cfg: TransformerConfig, mesh: Optional[Mesh] = None,
                    learning_rate: float = 1e-3, grad_accum: int = 1,
                    optimizer: str = "adamw", warmup_steps: int = 0,
                    total_steps: Optional[int] = None,
                    zero1: bool = False, fsdp: bool = False):
    """Build (init_state, step). ``step(state, tokens) -> (state, loss)``
    is one fully jitted optimizer step; with a mesh, params/opt-state are
    committed to :func:`param_specs` shardings and the batch to
    ``P('dp', 'sp')`` so GSPMD inserts the dp grad-psum and tp
    reductions. See :func:`make_train_parts` for ``grad_accum`` and the
    optimizer/schedule options."""
    init_state, step = make_train_parts(cfg, mesh=mesh,
                                        learning_rate=learning_rate,
                                        grad_accum=grad_accum,
                                        optimizer=optimizer,
                                        warmup_steps=warmup_steps,
                                        total_steps=total_steps,
                                        zero1=zero1, fsdp=fsdp)
    # Donate the incoming state: params + optimizer state alias their
    # output buffers, halving peak HBM for the largest tensors in the
    # step (the standard TPU training setup; callers rebind
    # ``state = step(state, ...)[0]`` so the consumed input is never
    # reused). XLA ignores donation where unsupported (CPU) with a
    # warning, so tests on the virtual mesh are unaffected.
    return init_state, jax.jit(step, donate_argnums=(0,))


def make_mesh_nd(n_devices: int,
                 axes: Tuple[str, ...] = ("dp", "sp", "tp"),
                 devices=None) -> Mesh:
    """Factor ``n_devices`` into a mesh over ``axes``: smallest prime
    factors are peeled off and dealt round-robin starting at the leftmost
    axis, e.g. 8 → (2, 2, 2), 4 → (2, 2, 1), 6 → (2, 3, 1), 12 → (2, 2, 3),
    1 → (1, 1, 1)."""
    if devices is None:
        devices = jax.devices()[:n_devices]
    dims = [1] * len(axes)
    rem = n_devices
    i = 0
    while rem > 1:
        # peel the smallest prime factor
        f = next((p for p in range(2, rem + 1) if rem % p == 0), rem)
        dims[i % len(axes)] *= f
        rem //= f
        i += 1
    return Mesh(np.asarray(devices).reshape(tuple(dims)), axes)
