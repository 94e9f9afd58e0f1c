"""The gated short-conv mixer: one sub-layer of a block stack driven by a
layer pattern (``TransformerConfig.layer_pattern``, kind ``C``).

For the normed stream ``h`` ``(b, s, d)`` and ``k`` = ``TAPS`` = 3
(``conv_L_cache`` of the ``lfm2`` configurations):

    [B | C | u] = h W_in                      d -> 3 d
    v = B * u
    c_t = sum_{j < k} w[j] v_{t-(k-1)+j}      depthwise, causal, zero before
                                              the sequence; no bias
    out = (C * c) W_out                       d -> d

as the ``lfm2`` / ``lfm2_moe`` modelling code computes it
(``Lfm2ShortConv``). The gates and the taps run in float32
(``mamba2._causal_conv``, shared with the Mamba-2 mixer), the two
projections in the compute dtype.

Every leaf is replicated: the mixer runs whole on each device (a mesh with
``tp``, ``ep`` or ``sp`` > 1 is refused where the block stack is built),
on its ``dp`` share of the batch.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..utils import trace
from .mamba2 import _causal_conv

__all__ = ["init_short_conv_params", "short_conv_specs", "short_conv_mixer"]

_F32 = jnp.float32
TAPS = 3


def init_short_conv_params(key: jax.Array, cfg) -> Dict[str, Any]:
    """Every matrix ``N(0, 1 / fan_in)``, the taps ``N(0, 1 / k)``."""
    d, pd, taps = cfg.d_model, cfg.param_dtype, TAPS
    k_in, k_conv, k_out = jax.random.split(key, 3)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(pd)

    return {"in_proj": dense(k_in, (d, 3 * d), d),
            "conv_w": dense(k_conv, (taps, d), taps),
            "out_proj": dense(k_out, (d, d), d)}


def short_conv_specs() -> Dict[str, P]:
    return {name: P() for name in ("in_proj", "conv_w", "out_proj")}


def short_conv_mixer(h: jax.Array, blk: Dict[str, Any]) -> jax.Array:
    """The equations above for ``h`` ``(b, s, d)`` in the compute dtype;
    returns ``(b, s, d)``. Scopes ``shortconv`` > ``shortconv.in_proj`` /
    ``.conv`` (both gates and the taps) / ``.out_proj`` are what a trace
    splits the mixer by; with tracing on each call adds 1 to
    ``shortconv.layers`` (at trace time)."""
    d = h.shape[-1]
    trace.count("shortconv.layers")
    with jax.named_scope("shortconv"):
        with jax.named_scope("shortconv.in_proj"):
            # Named for a later choice of ``_REMAT_KEEPS``
            # (models/transformer.py), which holds nothing of it today.
            bcu = checkpoint_name(
                jnp.einsum("bsd,de->bse", h, blk["in_proj"].astype(h.dtype)),
                "shortconv_in")
        with jax.named_scope("shortconv.conv"):
            gate_in = bcu[..., :d].astype(_F32) * bcu[..., 2 * d:].astype(_F32)
            y = (bcu[..., d:2 * d].astype(_F32)
                 * _causal_conv(gate_in, blk["conv_w"])).astype(h.dtype)
        with jax.named_scope("shortconv.out_proj"):
            return jnp.einsum("bse,ed->bsd", y,
                              blk["out_proj"].astype(h.dtype))
