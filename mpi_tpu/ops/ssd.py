"""The Mamba-2 selective scan in its chunked (state-space dual) form.

Per head, with a state ``S`` of ``(p, n)`` (head size x state size), a
step ``dt_t > 0`` and a decay rate ``A < 0``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

``B_t`` and ``C_t`` (``n`` each) are shared by the ``h / g`` heads of a
group (head ``j`` reads group ``j // (h / g)``). Token by token that is a
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
    the ``s / l`` chunks (``lax.scan``), carried in float32;
  * ``y_t`` gets ``exp(a_t) S_in C_t`` from the state entering its chunk.

The decays, their running sums and the carried state are float32 whatever
the inputs' dtype; the matmuls take their operands in the inputs' dtype
(the entering state rounded to it for the last product, as the model's
released kernels do) and accumulate in float32. The backward pass is
``jax.grad`` of this program: there is no kernel here, and nothing is
sharded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssd_scan"]

_F32 = jnp.float32


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, chunk: int) -> jax.Array:
    """``x`` ``(b, s, h, p)``, ``dt`` ``(b, s, h)`` float32 and positive,
    ``A`` ``(h,)`` float32 and negative, ``B``, ``C`` ``(b, s, g, n)`` with
    ``g`` dividing ``h``, ``D`` ``(h,)``; returns ``y`` ``(b, s, h, p)`` in
    ``x``'s dtype. ``s`` must be whole chunks of ``chunk`` positions."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    if s % chunk:
        raise ValueError(
            f"mpi_tpu: ssd_scan needs whole chunks: seq {s} is not a "
            f"multiple of chunk {chunk}")
    if h % g:
        raise ValueError(f"mpi_tpu: ssd_scan: {g} groups do not divide "
                         f"{h} heads")
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
