"""The Mamba-2 mixer: one sub-layer of a block stack driven by a layer
pattern (``TransformerConfig.layer_pattern``, kind ``M``).

For the normed stream ``h`` ``(b, s, d)``, with ``H`` heads of ``P``
(``d_inner = H P``, stated, not ``expand x d``), ``G`` groups and a state of
``N`` (``conv_dim = d_inner + 2 G N``):

    [z | xBC | dt] = h W_in                   d -> d_inner + conv_dim + H
    xBC = silu(conv1d_causal_depthwise(xBC, k) + b)
    [x | B | C] = xBC                         (H, P), (G, N), (G, N)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    y  = scan(x, dt, A, B, C) + D x           mpi_tpu.ops.ssd, chunked:
                                              two Pallas kernels on a TPU
    y  = GroupRMSNorm(y * silu(z)) * w        G groups of d_inner / G, gated
    out = y W_out                             d_inner -> d

as the ``nemotron_h`` / Mamba-2 modelling code computes it ("Transformers
are SSMs", arXiv:2405.21060). ``models/ssm.py``'s diagonal LRU is another
model with its own configuration and train step, and is not this.

Every leaf is replicated: the mixer runs whole on each device (a mesh with
``tp`` or ``sp`` > 1 is refused where the block stack is built), on its
``dp`` share of the batch.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.ssd import ssd_scan_flat
from ..utils import trace

__all__ = ["init_mamba2_params", "mamba2_specs", "mamba2_mixer"]

_F32 = jnp.float32
_NORM_EPS = 1e-5


def _dims(cfg):
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    return d_inner, d_inner + 2 * cfg.ssm_groups * cfg.ssm_state


def init_mamba2_params(key: jax.Array, cfg) -> Dict[str, Any]:
    """``A_log = log U[1, 16]``; ``dt_bias`` the inverse softplus of a
    log-uniform step in ``[0.001, 0.1]`` floored at 1e-4; ``D`` = 1; the
    gated norm's weight 1; the conv's bias 0; every matrix (the conv's
    taps too) ``N(0, 1 / fan_in)``."""
    d, pd, heads = cfg.d_model, cfg.param_dtype, cfg.ssm_heads
    d_inner, conv_dim = _dims(cfg)
    k_in, k_conv, k_a, k_dt, k_out = jax.random.split(key, 5)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(pd)

    step = jnp.maximum(jnp.exp(jax.random.uniform(
        k_dt, (heads,), minval=math.log(1e-3), maxval=math.log(1e-1))), 1e-4)
    return {
        "in_proj": dense(k_in, (d, d_inner + conv_dim + heads), d),
        "conv_w": dense(k_conv, (cfg.ssm_conv, conv_dim), cfg.ssm_conv),
        "conv_b": jnp.zeros((conv_dim,), pd),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "A_log": jnp.log(jax.random.uniform(
            k_a, (heads,), minval=1.0, maxval=16.0)).astype(pd),
        "D": jnp.ones((heads,), pd),
        "ssm_norm": jnp.ones((d_inner,), pd),
        "out_proj": dense(k_out, (d_inner, d), d_inner),
    }


def mamba2_specs() -> Dict[str, P]:
    return {name: P() for name in (
        "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D", "ssm_norm",
        "out_proj")}


def _causal_conv(u, w, bias=None):
    """Depthwise, causal: ``out_t = sum_j w[j] u_{t - (k-1) + j} + bias``
    with ``u`` zero before the sequence, in float32. ``u`` ``(b, s, ch)``,
    ``w`` ``(k, ch)``, ``bias`` ``(ch,)`` or None (none: the short conv of
    ``models/short_conv.py``)."""
    k, s = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(padded[:, j:j + s].astype(_F32) * w[j].astype(_F32)
              for j in range(k))
    return out if bias is None else out + bias.astype(_F32)


def _scan_per_shard(mesh: Optional[Mesh], x, dt, A, B, C, D, chunk, groups):
    """``ssd_scan_flat``, each device on its own rows of the batch. GSPMD
    cannot partition a Mosaic kernel (it would gather the batch and run
    the whole of it on every chip), so on a real mesh the scan runs per
    shard over ``dp`` as the flash kernels do (``_kernel_per_shard``,
    models/transformer.py); one chip and ``mesh=None`` call it directly."""
    def scan(*inputs):
        return ssd_scan_flat(*inputs, chunk, groups)

    if mesh is None or mesh.size == 1:
        return scan(x, dt, A, B, C, D)
    rows, whole = P("dp" if "dp" in mesh.axis_names else None), P()
    return jax.shard_map(
        scan, mesh=mesh, in_specs=(rows, rows, whole, rows, rows, whole),
        out_specs=rows, check_vma=False)(x, dt, A, B, C, D)


def mamba2_mixer(h: jax.Array, blk: Dict[str, Any], cfg,
                 mesh: Optional[Mesh] = None) -> jax.Array:
    """The equations above for ``h`` ``(b, s, d)`` in the compute dtype;
    returns ``(b, s, d)``. On a ``mesh`` of more than one device the scan
    runs per shard of the batch. Scopes ``ssm`` > ``ssm.in_proj`` /
    ``.conv`` / ``.scan`` / ``.norm`` / ``.out_proj`` are what a trace
    splits the mixer by; with tracing on each call adds 1 to
    ``ssm.layers`` (at trace time)."""
    b, s, _ = h.shape
    groups, n = cfg.ssm_groups, cfg.ssm_state
    d_inner, conv_dim = _dims(cfg)
    if s % cfg.ssm_chunk:
        raise ValueError(
            f"mpi_tpu: the Mamba-2 scan runs in chunks of {cfg.ssm_chunk}: "
            f"seq {s} is not a multiple")
    trace.count("ssm.layers")
    with jax.named_scope("ssm"):
        with jax.named_scope("ssm.in_proj"):
            # Named for ``checkpointed_block`` (models/transformer.py), which
            # holds what ``_REMAT_KEEPS`` lists: this product, not the conv's
            # pre-activation below. Outside a checkpoint a name is the
            # identity.
            zxbcdt = checkpoint_name(
                jnp.einsum("bsd,de->bse", h, blk["in_proj"].astype(h.dtype)),
                "ssm_in")
            z = zxbcdt[..., :d_inner]
            xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
            dt = zxbcdt[..., d_inner + conv_dim:]
        with jax.named_scope("ssm.conv"):
            xbc = jax.nn.silu(checkpoint_name(
                _causal_conv(xbc, blk["conv_w"], blk["conv_b"]),
                "ssm_conv")).astype(h.dtype)
        with jax.named_scope("ssm.scan"):
            # Heads and groups stay side by side, as the scan's kernels
            # read them: (b, s, h, p) would be another layout on the chip.
            step = jax.nn.softplus(dt.astype(_F32)
                                   + blk["dt_bias"].astype(_F32))
            y = _scan_per_shard(
                mesh, xbc[..., :d_inner], step,
                -jnp.exp(blk["A_log"].astype(_F32)),
                xbc[..., d_inner:d_inner + groups * n],
                xbc[..., d_inner + groups * n:], blk["D"], cfg.ssm_chunk,
                groups)
        with jax.named_scope("ssm.norm"):
            gated = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
            by_group = gated.reshape(b, s, groups, d_inner // groups)
            by_group = by_group * jax.lax.rsqrt(
                jnp.mean(by_group * by_group, axis=-1, keepdims=True)
                + _NORM_EPS)
            y = (by_group.reshape(b, s, d_inner)
                 * blk["ssm_norm"].astype(_F32)).astype(h.dtype)
        with jax.named_scope("ssm.out_proj"):
            return jnp.einsum("bse,ed->bsd", y,
                              blk["out_proj"].astype(h.dtype))
