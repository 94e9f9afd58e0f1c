"""The Mamba-2 selective scan in its chunked (state-space dual) form.

Per head, with a state ``S`` of ``(p, n)`` (head size x state size), a
step ``dt_t > 0`` and a decay rate ``A < 0``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

``B_t`` and ``C_t`` (``n`` each) are shared by the ``r = h / g`` heads of
a group (head ``j`` reads group ``j // r``). Token by token that is a
recurrence of ``s`` steps over elementwise work; "Transformers are SSMs"
(Dao and Gu, arXiv:2405.21060, section 6) splits the sequence into chunks
of ``l`` positions so that nearly all of it is matmul-shaped:

  * inside a chunk, ``y_t`` gets ``sum_{u<=t} (C_t.B_u) exp(a_t - a_u)
    dt_u x_u`` with ``a`` the running sum of ``dt A`` inside the chunk: a
    masked ``(l, l)`` product a head, like causal attention without a
    softmax;
  * each chunk's own contribution to the state at its end is
    ``sum_u exp(a_end - a_u) dt_u x_u (x) B_u``: one ``(p, l) x (l, n)``
    product a head;
  * the states entering the chunks follow from those by a recurrence over
    the ``s / l`` chunks, carried in float32;
  * ``y_t`` gets ``exp(a_t) S_in C_t`` from the state entering its chunk.

The decays, their running sums and the carried state (and, backward, the
state's gradient) are float32 whatever the inputs' dtype; the matmuls take
their operands in the inputs' dtype (the entering state rounded to it for
the last product, as the model's released kernels do) and accumulate in
float32.

Two implementations of that arithmetic, and :func:`ssd_scan_flat` (which
:func:`ssd_scan` reshapes to) reads which one runs from its input:

  * :func:`ssd_scan_program`, plain ``jnp`` with a ``lax.scan`` over the
    chunk states and ``jax.grad`` as its backward. It writes the
    ``(l, l)`` decays, the masked products and their gradients through
    HBM (some 135 float32 passes a step in the benchmark's nemotron
    cell), and is the reference the tests compare with and the path of
    every platform but the TPU and of every shape the kernels do not
    tile;
  * two Pallas kernels under a ``jax.custom_vjp``, ``ssd_fwd`` and
    ``ssd_bwd``: a grid over (batch, group, chunk), the chunks innermost
    and in order (backward: in reverse). A cell holds one chunk of one
    group in VMEM: ``x`` as a ``(l, r p)`` block of the ``(b, s, h p)``
    array the mixer has, ``B`` and ``C`` as ``(l, n)`` blocks of ``(b, s,
    g n)``, ``dt`` and the running sums as ``(r, l)`` rows, the scores
    once a group, a head at a time the decays and the masked product, and
    the group's float32 state in scratch as ``(n, r p)`` (transposed, so
    that every product with it is a plain one), zeroed at the first
    chunk. HBM sees ``x``, ``B``, ``C`` and ``y`` once forward, those with
    ``y``'s gradient and ``dx``, ``dB``, ``dC`` once backward, and the
    states entering the chunks (``(b, c, g n, r p)`` float32) written
    forward and read backward. The running sums of ``dt A`` (``(b, s,
    h)`` float32, a thirtieth of ``x``) are taken outside, as are the
    sums over positions that ``dA`` and ``dD`` need. The kernels round
    where the program rounds: forward they agree with it bit for bit on a
    v5e.

The kernels serve a lowering for a TPU (``lax.platform_dependent`` round
their ``custom_vjp``: the platform being compiled for, not the process's
default backend) of shapes they tile: ``chunk``, ``n`` and ``r p``
multiples of 128, ``p`` of 64, one dtype for ``x``, ``B`` and ``C``. With
tracing on, each call of :func:`ssd_scan_flat` counts 1 as it is traced, by
what its shapes decide: ``ssd.scans.kernel`` (the kernels wherever a TPU is
compiled for) or ``ssd.scans.program``. Which platform that was is in the
compiled program's text (``%ssd_fwd``, ``%ssd_bwd``) and in a trace's device
ops. A caller that traces once for several layers counts once
(``jax.checkpoint``: the four mixers of the benchmark's nemotron cell read
1 a step program under ``remat``, 4 without).

Nothing is sharded here, and GSPMD cannot partition a Mosaic kernel: on a
mesh the caller runs the scan per shard of the batch (``models/mamba2.py``,
``_scan_per_shard``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import trace

__all__ = ["ssd_scan", "ssd_scan_flat", "ssd_scan_program"]

_F32 = jnp.float32
_LANES = 128


def _check(s, h, g, chunk):
    if s % chunk:
        raise ValueError(
            f"mpi_tpu: ssd_scan needs whole chunks: seq {s} is not a "
            f"multiple of chunk {chunk}")
    if h % g:
        raise ValueError(f"mpi_tpu: ssd_scan: {g} groups do not divide "
                         f"{h} heads")


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, chunk: int) -> jax.Array:
    """``x`` ``(b, s, h, p)``, ``dt`` ``(b, s, h)`` float32 and positive,
    ``A`` ``(h,)`` float32 and negative, ``B``, ``C`` ``(b, s, g, n)`` with
    ``g`` dividing ``h``, ``D`` ``(h,)``; returns ``y`` ``(b, s, h, p)`` in
    ``x``'s dtype. ``s`` must be whole chunks of ``chunk`` positions.
    :func:`ssd_scan_flat` with the heads and the groups taken apart."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    return ssd_scan_flat(
        x.reshape(b, s, h * p), dt, A, B.reshape(b, s, g * n),
        C.reshape(b, s, g * n), D, chunk, g).reshape(x.shape)


def ssd_scan_flat(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                  C: jax.Array, D: jax.Array, chunk: int,
                  groups: int) -> jax.Array:
    """:func:`ssd_scan` on the arrays a mixer has: ``x`` ``(b, s, h p)``
    with a head's ``p`` values side by side, ``B``, ``C`` ``(b, s, g n)``
    likewise for ``groups`` groups; returns ``y`` ``(b, s, h p)``. The
    kernels read and write exactly these, so on their path no array is
    laid out anew (a minor dimension of ``p`` = 64 fills half a register,
    and ``(b, s, h, p)`` <-> ``(b, s, h p)`` is a pass through HBM). The
    kernels where a TPU is compiled for and the shapes tile, else
    :func:`ssd_scan_program` (the module's docstring)."""
    s, h = dt.shape[1:]
    _check(s, h, groups, chunk)
    p, n = x.shape[2] // h, B.shape[2] // groups
    tile = _kernels_tile(p, n, h // groups, chunk, x.dtype, B.dtype, C.dtype)
    if trace.enabled():
        trace.count("ssd.scans.kernel" if tile else "ssd.scans.program")

    def program(x, dt, A, B, C, D):
        b = x.shape[0]
        return ssd_scan_program(
            x.reshape(b, s, h, p), dt, A, B.reshape(b, s, groups, n),
            C.reshape(b, s, groups, n), D, chunk).reshape(x.shape)

    if not tile:
        return program(x, dt, A, B, C, D)
    return lax.platform_dependent(
        x, dt, A, B, C, D, default=program,
        tpu=lambda *inputs: _scan_kernels(*inputs, chunk, groups, False))


def _kernels_tile(p, n, r, chunk, *dtypes) -> bool:
    """Whether the kernels' blocks are whole tiles of the chip's (8, 128)
    registers: ``chunk``, the state size and a group's ``r p`` lanes of
    ``x`` multiples of 128, a head's lanes half a register or whole ones;
    and one dtype for ``x``, ``B`` and ``C``, whose products take both
    operands alike."""
    return (chunk % _LANES == 0 and n % _LANES == 0
            and (r * p) % _LANES == 0 and p % (_LANES // 2) == 0
            and len(set(dtypes)) == 1)


# --------------------------------------------------------------------------
# The program: the reference, and every platform but the TPU
# --------------------------------------------------------------------------

def ssd_scan_program(x: jax.Array, dt: jax.Array, A: jax.Array,
                     B: jax.Array, C: jax.Array, D: jax.Array,
                     chunk: int) -> jax.Array:
    """:func:`ssd_scan` as one differentiable XLA program."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    _check(s, h, g, chunk)
    c, l, r = s // chunk, chunk, h // g
    dt = dt.astype(_F32)
    # a[t]: the running sum of dt A inside the chunk, up to and with t.
    a = jnp.cumsum((dt * A.astype(_F32)).reshape(b, c, l, g, r), axis=2)
    xdt = (x.astype(_F32) * dt[..., None]).reshape(b, c, l, g, r, p)
    Bc, Cc = B.reshape(b, c, l, g, n), C.reshape(b, c, l, g, n)

    # Inside a chunk: (C_t . B_u) exp(a_t - a_u) for u <= t, else 0.
    scores = jnp.einsum("bctgn,bcugn->bcgtu", Cc, Bc,
                        preferred_element_type=_F32)
    a_h = a.transpose(0, 1, 3, 4, 2)                      # (b, c, g, r, l)
    lower = jnp.tril(jnp.ones((l, l), bool))
    decay = jnp.exp(jnp.where(lower, a_h[..., :, None] - a_h[..., None, :],
                              -jnp.inf))                  # (b, c, g, r, t, u)
    y = jnp.einsum("bcgrtu,bcugrp->bctgrp",
                   (scores[:, :, :, None] * decay).astype(x.dtype),
                   xdt.astype(x.dtype), preferred_element_type=_F32)

    # What each chunk adds to the state at its end, and the recurrence.
    to_end = jnp.exp(a[:, :, -1:] - a)                    # (b, c, l, g, r)
    own = jnp.einsum("bcugn,bcugrp->bcgrpn", Bc,
                     (xdt * to_end[..., None]).astype(x.dtype),
                     preferred_element_type=_F32)
    whole = jnp.exp(a[:, :, -1])                          # (b, c, g, r)

    def chunk_step(state, inp):
        own_c, whole_c = inp
        return whole_c[..., None, None] * state + own_c, state  # ENTERING

    _, entering = lax.scan(
        chunk_step, jnp.zeros((b, g, r, p, n), _F32),
        (own.transpose(1, 0, 2, 3, 4, 5), whole.transpose(1, 0, 2, 3)))
    entering = entering.transpose(1, 0, 2, 3, 4, 5)       # (b, c, g, r, p, n)
    y = y + jnp.einsum("bctgn,bcgrpn->bctgrp", Cc, entering.astype(x.dtype),
                       preferred_element_type=_F32) * jnp.exp(a)[..., None]
    y = y.reshape(b, s, h, p) + D.astype(_F32)[:, None] * x.astype(_F32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# The kernels
# --------------------------------------------------------------------------

def _running_sums(dt, A, chunk):
    """``a`` ``(b, s, h)`` float32: the running sum of ``dt A`` inside each
    chunk, up to and with the position."""
    b, s, h = dt.shape
    return jnp.cumsum((dt.astype(_F32) * A.astype(_F32)).reshape(
        b, s // chunk, chunk, h), axis=2).reshape(b, s, h)


def _by_row(v, g):
    """``(b, s, h)`` -> ``(b, g, r, s)``: a group's heads as the rows of a
    ``(r, chunk)`` block, a head's values along the lanes (a block of
    ``(chunk, r)`` would be padded to whole registers of 128 lanes in
    HBM, sixteen times its size at ``r`` 8)."""
    b, s, h = v.shape
    return v.reshape(b, s, g, h // g).transpose(0, 2, 3, 1)


def _from_row(v):
    b, g, r, s = v.shape
    return v.transpose(0, 3, 1, 2).reshape(b, s, g * r)


def _nt(a, b):
    """``a bᵀ``, float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _tn(a, b):
    """``aᵀ b``."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _nn(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=_F32)


# A value a position a head reaches the kernels as a row of ``(r, l)``: along
# the lanes, as HBM holds it densely. The arithmetic wants it down a column
# and repeated along the lanes, once over a head's ``p`` lanes of ``x`` and
# once over the ``l`` columns of its decays. Broadcasting a column along
# the lanes, or summing along them, goes through the chip's cross-lane unit
# a register at a time and took two fifths of the first forward kernel; the
# MXU, which these kernels leave mostly idle, does both exactly as a product
# with a matrix of zeros and ones, once the float32 is split into bfloat16
# pieces that add up to it.

_BF16 = jnp.bfloat16
_PACK = 16      # the rows of a bfloat16 register: pieces are padded to it


def _pieces(v):
    """Three bfloat16 arrays that add up to float32 ``v``, to all 24 bits
    of it."""
    high = v.astype(_BF16)
    rest = v - high.astype(_F32)
    mid = rest.astype(_BF16)
    return [high, mid, (rest - mid.astype(_F32)).astype(_BF16)]


def _head_of(shape, width: int, heads: int, copies: int):
    """``(rows, heads width)`` bool: whether row ``i`` names the head whose
    ``width`` lanes hold lane ``k``, ``i % heads == k // width``, for the
    first ``copies heads`` rows (stacked copies of the heads) and no
    other. No division: a comparison with the head's first and last
    lane."""
    i = lax.broadcasted_iota(jnp.int32, shape, 0)
    k = lax.broadcasted_iota(jnp.int32, shape, 1)
    head = i
    for copy in range(1, copies):
        head = head - heads * (i >= copy * heads)
    return (k >= head * width) & (k < (head + 1) * width) & (
        i < copies * heads)


def _spread(rows, width: int):
    """``(r, l)`` float32 -> ``(l, r width)`` float32 with ``rows[j, t]``
    at ``[t, j width : (j + 1) width]``, exactly: the three pieces stacked
    to ``(3 r, l)`` and contracted with the ``(3 r, r width)`` selection."""
    r, l = rows.shape
    stacked = jnp.concatenate([x.astype(_F32) for x in _pieces(rows)],
                              axis=0)
    pad = -3 * r % _PACK
    if pad:
        stacked = jnp.concatenate([stacked, jnp.zeros((pad, l), _F32)], 0)
    select = _head_of((3 * r + pad, r * width), width, r, 3)
    return _tn(stacked.astype(_BF16), select.astype(_BF16))


def _head_sums(z, r: int):
    """``(l, r p)`` float32 -> ``(r, l)``: each head's sum over its ``p``
    lanes, a position a lane, of all 24 bits of each term."""
    l, lanes = z.shape
    rows = -(-r // _PACK) * _PACK
    select = _head_of((rows, lanes), lanes // r, r, 1).astype(_BF16)
    return sum(_nt(select, piece) for piece in _pieces(z))[:r]


def _lower(l: int, transposed: bool):
    """``(l, l)`` bool: ``u <= t`` at ``[t, u]``, or ``transposed`` at ``[u,
    t]``."""
    rows = lax.broadcasted_iota(jnp.int32, (l, l), 0)
    cols = lax.broadcasted_iota(jnp.int32, (l, l), 1)
    return rows <= cols if transposed else rows >= cols


def _decays(later, earlier, lower):
    """``exp(a_t - a_u)`` where ``lower`` (``u <= t``), else 0: ``(l, l)``
    float32. As ``[t, u]`` ``later`` holds a head's running sums down the
    columns (every lane the same) and ``earlier`` ``(1, l)`` along the
    lanes; as ``[u, t]`` the other way round."""
    return jnp.exp(jnp.where(lower, later - earlier, -jnp.inf))


def _ssd_fwd_kernel(d_ref, x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, st_ref,
                    state_scr, xdt_scr, y_scr, *, r: int, p: int):
    """One chunk of one group. ``state_scr`` ``(n, r p)`` float32 is the
    group's state entering the chunk, transposed (head ``j`` in lanes ``j
    p ..``, so that every product with it is a plain one), written out as
    it stands and left as the state at the chunk's end."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_scr[:] = jnp.zeros_like(state_scr)

    dtype = x_ref.dtype
    Bm, Cm = b_ref[0], c_ref[0]                           # (l, n)
    a_row = a_ref[0, 0]                                   # (r, l)
    l = a_row.shape[1]
    a_cols = _spread(a_row, l)                            # (l, r l)
    a = _spread(a_row, p)                                 # (l, r p)
    a_end = a[l - 1:, :]                                  # (1, r p)
    state = state_scr[:]
    st_ref[0, 0] = state
    x32 = x_ref[0].astype(_F32)
    xdt = x32 * _spread(dt_ref[0, 0], p)
    xdt_scr[:] = xdt.astype(dtype)
    scores, lower = _nt(Cm, Bm), _lower(l, False)         # (t, u), once
    for j in range(r):
        lanes = slice(j * p, (j + 1) * p)
        decay = _decays(a_cols[:, j * l:(j + 1) * l], a_row[j:j + 1, :],
                        lower)
        y_scr[:, lanes] = _nn((scores * decay).astype(dtype),
                              xdt_scr[:, lanes])
    y = y_scr[:] + _nn(Cm, state.astype(dtype)) * jnp.exp(a)
    y_ref[0] = (y + d_ref[:] * x32).astype(dtype)
    state_scr[:] = jnp.exp(a_end) * state + _tn(
        Bm, (xdt * jnp.exp(a_end - a)).astype(dtype))     # (n, r p)


def _specs(chunk, r, p, n, chunk_of):
    """The blocks of grid cell (bi, gi, ci), ``chunk_of(ci)`` being the
    chunk it works on: ``x``-like, ``B``-like, a group's heads by row,
    the states' block, and the group's lanes of ``D``."""
    return dict(
        x=pl.BlockSpec((1, chunk, r * p),
                       lambda bi, gi, ci: (bi, chunk_of(ci), gi)),
        bc=pl.BlockSpec((1, chunk, n),
                        lambda bi, gi, ci: (bi, chunk_of(ci), gi)),
        row=pl.BlockSpec((1, 1, r, chunk),
                         lambda bi, gi, ci: (bi, gi, 0, chunk_of(ci))),
        states=pl.BlockSpec((1, 1, n, r * p),
                            lambda bi, gi, ci: (bi, chunk_of(ci), gi, 0)),
        d=pl.BlockSpec((1, r * p), lambda bi, gi, ci: (0, gi)))


def _ssd_fwd_pallas(x, dt, A, B, C, D, chunk, groups, interpret):
    """``(y, states)`` for the arrays of :func:`ssd_scan_flat`: ``states``
    ``(b, c, g n, r p)`` float32, the state entering each chunk (a group's
    ``(n, r p)`` block: transposed), which the backward reads."""
    b, s, h = dt.shape
    p, n = x.shape[2] // h, B.shape[2] // groups
    c, r = s // chunk, h // groups
    a = _running_sums(dt, A, chunk)
    spec = _specs(chunk, r, p, n, lambda ci: ci)
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, r=r, p=p),
        grid=(b, groups, c),
        in_specs=[spec["d"], spec["x"], spec["bc"], spec["bc"], spec["row"],
                  spec["row"]],
        out_specs=[spec["x"], spec["states"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, c, groups * n, r * p), _F32)],
        scratch_shapes=[pltpu.VMEM((n, r * p), _F32),
                        pltpu.VMEM((chunk, r * p), x.dtype),
                        pltpu.VMEM((chunk, r * p), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_fwd",
    )(jnp.repeat(D.astype(_F32), p)[None], x, B, C,
      _by_row(dt.astype(_F32), groups), _by_row(a, groups))


def _ssd_bwd_kernel(d_ref, x_ref, b_ref, c_ref, g_ref, dt_ref, a_ref, st_ref,
                    dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref,
                    dstate_scr, xdt_scr, dxdt_scr, *, r: int, p: int):
    """One chunk of one group, the chunks in reverse. ``dstate_scr`` ``(n,
    r p)`` float32 is the gradient of the state at the chunk's end, zeroed
    at the last chunk and left as that of the entering state. A head's
    masked product is taken as ``[u, t]`` here, so that no product wants a
    transposed operand of ``(l, l)``. ``ddt`` is ``dt``'s gradient through
    ``dt x`` alone, ``da`` the running sums', ``dd`` ``D``'s a position:
    the caller takes them on."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_scr[:] = jnp.zeros_like(dstate_scr)

    dtype = x_ref.dtype
    Bm, Cm = b_ref[0], c_ref[0]
    a_row = a_ref[0, 0]                                   # (r, l)
    l = a_row.shape[1]
    a_cols = _spread(a_row, l)                            # (l, r l)
    a = _spread(a_row, p)                                 # (l, r p)
    dt = _spread(dt_ref[0, 0], p)
    a_end = a[l - 1:, :]
    to_end, whole = jnp.exp(a_end - a), jnp.exp(a_end)
    state, dstate = st_ref[0, 0], dstate_scr[:]           # (n, r p)
    state16, dstate16 = state.astype(dtype), dstate.astype(dtype)
    x32, g16 = x_ref[0].astype(_F32), g_ref[0]
    g32 = g16.astype(_F32)
    xdt = x32 * dt
    xdt_scr[:] = xdt.astype(dtype)
    scores, lower = _nt(Bm, Cm), _lower(l, True)          # (u, t)
    dscores = jnp.zeros((l, l), _F32)
    da_intra = jnp.zeros((r, l), _F32)
    head = lax.broadcasted_iota(jnp.int32, (r, l), 0)
    for j in range(r):
        lanes = slice(j * p, (j + 1) * p)
        decay = _decays(a_row[j:j + 1, :], a_cols[:, j * l:(j + 1) * l],
                        lower)
        masked = (scores * decay).astype(dtype)
        # y = masked xdt, as [u, t]: both operands' gradients, and through
        # the decays the running sums': what leads to t, summed over u.
        dxdt_scr[:, lanes] = _nn(masked, g_ref[0, :, lanes])
        dmasked = _nt(xdt_scr[:, lanes], g_ref[0, :, lanes])
        dscores = dscores + dmasked * decay
        da_intra = jnp.where(head == j, jnp.sum(
            dmasked * masked.astype(_F32), axis=0, keepdims=True), da_intra)
    # state_end = exp(a_end) state + Bᵀ (xdt exp(a_end - a)).
    dxe = _nn(Bm, dstate16)                               # (l, r p)
    dxdt_intra = dxdt_scr[:]
    dxdt = dxdt_intra + dxe * to_end
    xe = xdt * to_end
    # y += exp(a) (C . state).
    dz = g32 * jnp.exp(a)
    # The running sums' gradient: + where a decay leads to t, - where it
    # leads from u; the chunk's last takes what leads to the end. What
    # leads from u inside the chunk is the column sums of the very matrix
    # whose row sums ``da_intra`` holds, (masked dmasked)[u, t] with both as
    # the MXU saw them, here as xdt dxdt summed over a head: the two must
    # cancel over a chunk, which an operand rounded on one side alone
    # would undo (1e-2 of dt's gradient with bfloat16 inputs).
    through = (dz * _nn(Cm, state16) - xdt_scr[:].astype(_F32) * dxdt_intra
               - dxe * xe)
    at_end = (jnp.sum(dxe * xe, axis=0, keepdims=True)
              + whole * jnp.sum(dstate * state, axis=0, keepdims=True))
    last = lax.broadcasted_iota(jnp.int32, through.shape, 0) == l - 1
    da_ref[0, 0] = da_intra + _head_sums(
        jnp.where(last, through + at_end, through), r)
    ddt_ref[0, 0] = _head_sums(dxdt * x32, r)
    dd_ref[0, 0] = _head_sums(g32 * x32, r)
    dx_ref[0] = (dxdt * dt + d_ref[:] * g32).astype(dtype)
    dscores, dz16 = dscores.astype(dtype), dz.astype(dtype)
    dc_ref[0] = (_tn(dscores, Bm) + _nt(dz16, state16)).astype(dtype)
    db_ref[0] = (_nn(dscores, Cm) + _nt(xe.astype(dtype), dstate16)
                 ).astype(dtype)
    dstate_scr[:] = whole * dstate + _tn(Cm, dz16)


def _ssd_bwd_pallas(x, dt, A, B, C, D, states, g, chunk, groups,
                    interpret):
    """The gradients of ``x``, ``dt``, ``A``, ``B``, ``C``, ``D`` for
    ``y``'s gradient ``g``, from the forward's ``states``."""
    b, s, h = dt.shape
    p, n = x.shape[2] // h, B.shape[2] // groups
    c, r = s // chunk, h // groups
    dt32 = dt.astype(_F32)
    a, a_vjp = jax.vjp(lambda dt, A: _running_sums(dt, A, chunk), dt32,
                       A.astype(_F32))
    spec = _specs(chunk, r, p, n, lambda ci: c - 1 - ci)
    by_row = jax.ShapeDtypeStruct((b, groups, r, s), _F32)
    dx, dB, dC, ddt, da, dd = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, r=r, p=p),
        grid=(b, groups, c),
        in_specs=[spec["d"], spec["x"], spec["bc"], spec["bc"], spec["x"],
                  spec["row"], spec["row"], spec["states"]],
        out_specs=[spec["x"], spec["bc"], spec["bc"], spec["row"],
                   spec["row"], spec["row"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype),
                   by_row, by_row, by_row],
        scratch_shapes=[pltpu.VMEM((n, r * p), _F32),
                        pltpu.VMEM((chunk, r * p), x.dtype),
                        pltpu.VMEM((chunk, r * p), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_bwd",
    )(jnp.repeat(D.astype(_F32), p)[None], x, B, C, g,
      _by_row(dt32, groups), _by_row(a, groups), states)
    ddt_a, dA = a_vjp(_from_row(da))
    return (dx, (_from_row(ddt) + ddt_a).astype(dt.dtype),
            dA.astype(A.dtype), dB, dC,
            jnp.sum(dd, axis=(0, 3)).reshape(-1).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan_kernels(x, dt, A, B, C, D, chunk, groups, interpret):
    """:func:`ssd_scan_flat` as the two kernels (``interpret``: on the
    CPU, for the tests). Where it is not differentiated the forward
    kernel writes the states all the same, and they are dropped."""
    return _ssd_fwd_pallas(x, dt, A, B, C, D, chunk, groups, interpret)[0]


def _scan_kernels_fwd(x, dt, A, B, C, D, chunk, groups, interpret):
    y, states = _ssd_fwd_pallas(x, dt, A, B, C, D, chunk, groups, interpret)
    return y, (x, dt, A, B, C, D, states)


def _scan_kernels_bwd(chunk, groups, interpret, res, g):
    return _ssd_bwd_pallas(*res, g, chunk, groups, interpret)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)
