"""The chunked (state-space dual) scan of ``mpi_tpu/ops/ssd.py`` against
the token-by-token recurrence it must equal (ISSUE 36): values and the
gradient of every input, at two chunk counts, and the refusal of a length
that is not whole chunks. Small sizes, seeded, CPU, float32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tpu.ops.ssd import ssd_scan

B, S, H, P, G, N = 2, 32, 4, 8, 2, 16
ARGS = ("x", "dt", "A", "B", "C", "D")


def recurrence(x, dt, A, B, C, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
    D x_t``, one token at a time; head ``j`` reads group ``j // (H / G)``."""
    per = x.shape[2] // B.shape[2]
    Bh, Ch = jnp.repeat(B, per, 2), jnp.repeat(C, per, 2)   # (b, s, h, n)

    def step(state, inp):                                   # (b, h, p, n)
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, (jnp.einsum("bhpn,bhn->bhp", state, c_t)
                       + D[None, :, None] * x_t)

    swap = lambda a: jnp.swapaxes(a, 0, 1)                  # noqa: E731
    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], x.dtype)
    _, y = jax.lax.scan(step, zero, (swap(x), swap(dt), swap(Bh), swap(Ch)))
    return swap(y)


@pytest.fixture(scope="module")
def case():
    ks = jax.random.split(jax.random.key(36), 7)
    f32 = jnp.float32
    inputs = dict(
        x=jax.random.normal(ks[0], (B, S, H, P), f32),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), f32) - 1.0),
        A=-jnp.exp(jax.random.uniform(ks[2], (H,), f32, 0.0, 2.0)),
        B=jax.random.normal(ks[3], (B, S, G, N), f32),
        C=jax.random.normal(ks[4], (B, S, G, N), f32),
        D=jax.random.normal(ks[5], (H,), f32))
    weigh = jax.random.normal(ks[6], (B, S, H, P), f32)
    want = jax.value_and_grad(
        lambda kw: jnp.sum(recurrence(**kw) * weigh))(inputs)
    return inputs, weigh, recurrence(**inputs), want[1]


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_scan_values_equal_the_recurrence(case, chunk):
    inputs, _, want, _ = case
    got = ssd_scan(**inputs, chunk=chunk)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _rel(got, want) < 2e-6


@pytest.mark.parametrize("name", ARGS)
@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_scan_gradient_equals_the_recurrences(case, chunk, name):
    inputs, weigh, _, want = case
    got = jax.grad(lambda kw: jnp.sum(
        ssd_scan(**kw, chunk=chunk) * weigh))(inputs)
    assert _rel(got[name], want[name]) < 2e-5


def test_one_chunk_is_the_masked_product_alone(case):
    """A sequence of one chunk has no entering state: the same values."""
    inputs, _, want, _ = case
    assert _rel(ssd_scan(**inputs, chunk=S), want) < 2e-6


def test_ragged_length_is_refused(case):
    inputs, *_ = case
    with pytest.raises(ValueError, match="whole chunks.*multiple of chunk 12"):
        ssd_scan(**inputs, chunk=12)


def test_groups_must_divide_heads(case):
    inputs, *_ = case
    three = dict(inputs, B=inputs["B"][:, :, :1].repeat(3, 2),
                 C=inputs["C"][:, :, :1].repeat(3, 2))
    with pytest.raises(ValueError, match="3 groups do not divide 4 heads"):
        ssd_scan(**three, chunk=8)


def test_the_state_is_carried_in_float32_whatever_the_inputs(case):
    """From chunk to chunk the state is float32 with bfloat16 inputs too:
    the recurrence's carry in the program as traced."""
    inputs, *_ = case
    narrow = {k: v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v
              for k, v in inputs.items()}
    jaxpr = jax.make_jaxpr(lambda kw: ssd_scan(**kw, chunk=8))(narrow)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    carried = scans[0].params["num_carry"]
    consts = scans[0].params["num_consts"]
    carry = scans[0].invars[consts:consts + carried]
    assert [v.aval.dtype for v in carry] == [jnp.float32]
    assert ssd_scan(**narrow, chunk=8).dtype == jnp.bfloat16
