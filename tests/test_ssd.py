"""The chunked (state-space dual) scan of ``mpi_tpu/ops/ssd.py`` against
the token-by-token recurrence it must equal (ISSUE 36): values and the
gradient of every input, at two chunk counts, and the refusal of a length
that is not whole chunks. Small sizes, seeded, CPU, float32.

Since ISSUE 37 the scan is two Pallas kernels where a TPU is compiled for
and the shapes tile: here they run in interpret mode against the same
recurrence and against the program, and the choice of path is held to
what the input says (shapes at trace time, the platform at lowering).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tpu.ops import ssd
from mpi_tpu.ops.ssd import ssd_scan, ssd_scan_flat, ssd_scan_program
from mpi_tpu.utils import trace

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


# --------------------------------------------------------------------------
# The kernels (ISSUE 37), interpreted on the CPU
# --------------------------------------------------------------------------

def _flat(x, B, C):
    """``ssd_scan``'s ``(b, s, h, p)`` and ``(b, s, g, n)`` as
    ``ssd_scan_flat`` and the kernels take them."""
    return (x.reshape(*x.shape[:2], -1), B.reshape(*B.shape[:2], -1),
            C.reshape(*C.shape[:2], -1))


def kernels(x, dt, A, B, C, D, chunk):
    """What ``ssd_scan`` runs where a TPU is compiled for: the two kernels
    under their ``custom_vjp``, here interpreted."""
    xf, Bf, Cf = _flat(x, B, C)
    return ssd._scan_kernels(xf, dt, A, Bf, Cf, D, chunk, B.shape[2],
                             True).reshape(x.shape)


def test_case_has_groups_of_several_heads():
    assert G < H and H // G > 1


@pytest.mark.parametrize("chunk", [8, 16])
def test_kernels_values_equal_the_recurrence_and_the_program(case, chunk):
    inputs, _, want, _ = case
    got = kernels(**inputs, chunk=chunk)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _rel(got, want) < 2e-6
    assert _rel(got, ssd_scan_program(**inputs, chunk=chunk)) < 1e-6


@pytest.fixture(scope="module", params=[8, 16])
def kernel_gradients(request, case):
    inputs, weigh, _, _ = case
    chunk = request.param
    got, program = (jax.grad(lambda kw: jnp.sum(f(**kw, chunk=chunk) * weigh))(
        inputs) for f in (kernels, ssd_scan_program))
    return got, program


@pytest.mark.parametrize("name", ARGS)
def test_kernels_gradient_equals_the_recurrences_and_the_programs(
        case, kernel_gradients, name):
    got, program = kernel_gradients
    assert got[name].shape == case[0][name].shape
    assert got[name].dtype == case[0][name].dtype
    assert _rel(got[name], case[3][name]) < 2e-5
    assert _rel(got[name], program[name]) < 1e-5


TILED = dict(b=1, s=256, h=4, p=64, g=2, n=128, chunk=128)


@pytest.fixture(scope="module")
def tiled():
    """Shapes the kernels tile (two chunks, two groups of two heads)."""
    t = TILED
    ks = jax.random.split(jax.random.key(37), 6)
    f32 = jnp.float32
    return dict(
        x=jax.random.normal(ks[0], (t["b"], t["s"], t["h"], t["p"]), f32),
        dt=jax.nn.softplus(
            jax.random.normal(ks[1], (t["b"], t["s"], t["h"]), f32) - 3.0),
        A=-jnp.exp(jax.random.uniform(ks[2], (t["h"],), f32, 0.0, 2.7)),
        B=jax.random.normal(ks[3], (t["b"], t["s"], t["g"], t["n"]), f32),
        C=jax.random.normal(ks[4], (t["b"], t["s"], t["g"], t["n"]), f32),
        D=jax.random.normal(ks[5], (t["h"],), f32))


def test_kernels_equal_the_program_at_shapes_they_tile(tiled):
    """Whole registers: chunk and state 128, heads of 64 two a group."""
    assert _tiles(tiled, TILED["chunk"])
    f = lambda scan: jax.value_and_grad(lambda kw: jnp.sum(  # noqa: E731
        scan(**kw, chunk=TILED["chunk"]) ** 2))(tiled)
    (got, got_grad), (want, want_grad) = f(kernels), f(ssd_scan_program)
    assert abs(got - want) < 1e-5 * abs(want)
    for name in ARGS:
        # dt and A: sums of terms that all but cancel over a chunk.
        assert _rel(got_grad[name], want_grad[name]) < (
            1e-4 if name in ("dt", "A") else 2e-5), name


def _tiles(inputs, chunk):
    x, B, C = inputs["x"], inputs["B"], inputs["C"]
    return ssd._kernels_tile(x.shape[3], B.shape[3], x.shape[2] // B.shape[2],
                             chunk, x.dtype, B.dtype, C.dtype)


def _scratch_dtypes(jaxpr, name):
    """The dtypes of the scratch buffers of the one ``pallas_call`` named
    ``name`` in ``jaxpr``, in the order the kernel declares them."""
    def eqns(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)
    calls = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
             and e.params["name"] == name]
    assert len(calls) == 1, (name, len(calls))
    kernel = calls[0].params["jaxpr"]
    scratch = calls[0].params["grid_mapping"].num_scratch_operands
    return [v.aval.dtype for v in kernel.invars[-scratch:]]


def test_the_kernels_carry_state_and_its_gradient_in_float32(case):
    """With bfloat16 inputs too: the scratch a grid cell hands the next
    (the first each kernel declares), and the states the forward writes
    out for the backward."""
    inputs, weigh, *_ = case
    narrow = {k: v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v
              for k, v in inputs.items()}
    xf, Bf, Cf = _flat(narrow["x"], narrow["B"], narrow["C"])
    y, states = ssd._ssd_fwd_pallas(xf, narrow["dt"], narrow["A"], Bf, Cf,
                                    narrow["D"], 8, G, True)
    assert y.shape == xf.shape
    assert y.dtype == jnp.bfloat16 and states.dtype == jnp.float32
    assert states.shape == (B, S // 8, G * N, H // G * P)
    jaxpr = jax.make_jaxpr(jax.grad(lambda kw: jnp.sum(
        kernels(**kw, chunk=8) * weigh).astype(jnp.float32)))(narrow)
    assert _scratch_dtypes(jaxpr, "ssd_fwd")[0] == jnp.float32
    assert _scratch_dtypes(jaxpr, "ssd_bwd")[0] == jnp.float32
    grads = jax.grad(lambda kw: jnp.sum(
        kernels(**kw, chunk=8) * weigh).astype(jnp.float32))(narrow)
    assert {k: v.dtype for k, v in grads.items()} == {
        k: v.dtype for k, v in narrow.items()}


@pytest.fixture
def counted():
    was = trace.enabled()
    trace.enable()
    before = dict(trace.counters())

    def since():
        return {k: v - before.get(k, 0) for k, v in trace.counters().items()
                if k.startswith("ssd.") and v != before.get(k, 0)}
    yield since
    if not was:
        trace.disable()


def test_shapes_the_kernels_do_not_tile_take_the_program(case, counted):
    """Decided as ``ssd_scan`` is traced: a state of 16 fills no register,
    so the call IS the program, counted once a call."""
    inputs, *_ = case
    assert not _tiles(inputs, 8)
    got = jax.jit(lambda kw: ssd_scan(**kw, chunk=8))(inputs)
    assert counted() == {"ssd.scans.program": 1}
    assert np.array_equal(got, jax.jit(
        lambda kw: ssd_scan_program(**kw, chunk=8))(inputs))
    jax.make_jaxpr(lambda kw: ssd_scan(**kw, chunk=8) + ssd_scan(
        **kw, chunk=16))(inputs)
    assert counted() == {"ssd.scans.program": 3}


@pytest.mark.parametrize("narrow", [(), ("x", "B", "C"), ("B",), ("C",),
                                    ("x", "C")])
def test_one_dtype_for_x_b_and_c_or_the_program(tiled, narrow):
    """The kernels' products take both operands in one dtype: at shapes
    that tile, ``x``, ``B`` and ``C`` of mixed dtypes take the program."""
    mixed = {k: v.astype(jnp.bfloat16) if k in narrow else v
             for k, v in tiled.items()}
    assert _tiles(mixed, TILED["chunk"]) == (len(narrow) in (0, 3))


def test_a_cpu_lowering_takes_the_program_at_shapes_the_kernels_tile(
        tiled, counted):
    """What the benchmark's host probe does (``kinds/train_step_routed``):
    float32 arrays committed to the CPU under ``jit``. The platform being
    lowered for decides, so no Mosaic kernel reaches the CPU's compiler.
    The counter reads what the shapes decide, once a call as it is traced:
    ``kernel`` means the kernels wherever a TPU is compiled for."""
    cpu = jax.devices("cpu")[0]
    host = jax.device_put(tiled, cpu)
    chunk = TILED["chunk"]
    scan = jax.jit(lambda kw: ssd_scan(**kw, chunk=chunk))
    assert counted() == {}
    got = scan(host)
    assert got.devices() == {cpu}
    assert counted() == {"ssd.scans.kernel": 1}
    assert _rel(got, ssd_scan_program(**tiled, chunk=chunk)) < 1e-6
    text = scan.lower(host).as_text()
    assert "tpu_custom_call" not in text and "ssd_fwd" not in text
    twice = jax.jit(jax.grad(lambda kw: jnp.sum(
        ssd_scan(**kw, chunk=chunk) * ssd_scan(**kw, chunk=chunk))))
    before = counted()["ssd.scans.kernel"]
    grads = twice(host)
    assert counted() == {"ssd.scans.kernel": before + 2}
    want = jax.grad(lambda kw: jnp.sum(
        ssd_scan_program(**kw, chunk=chunk) ** 2))(tiled)
    for name in ARGS:
        assert _rel(grads[name], want[name]) < 1e-5, name


def test_the_flat_scan_is_the_scan_with_heads_side_by_side(case, tiled):
    """``ssd_scan_flat`` is what the mixer calls and ``ssd_scan`` reshapes
    to: the same values at shapes that take the program and at shapes the
    kernels tile, and the same refusals."""
    for inputs, chunk in ((case[0], 8), (tiled, TILED["chunk"])):
        xf, Bf, Cf = _flat(inputs["x"], inputs["B"], inputs["C"])
        got = ssd_scan_flat(xf, inputs["dt"], inputs["A"], Bf, Cf,
                            inputs["D"], chunk, inputs["B"].shape[2])
        assert got.shape == xf.shape
        assert np.array_equal(got.reshape(inputs["x"].shape),
                              ssd_scan(**inputs, chunk=chunk))
    xf, Bf, Cf = _flat(case[0]["x"], case[0]["B"], case[0]["C"])
    rest = case[0]["dt"], case[0]["A"], Bf, Cf, case[0]["D"]
    with pytest.raises(ValueError, match="whole chunks"):
        ssd_scan_flat(xf, *rest, 5, G)
    with pytest.raises(ValueError, match="3 groups do not divide"):
        ssd_scan_flat(xf, *rest, 8, 3)


def test_a_batch_of_scans_is_the_scans_of_a_batch(tiled):
    """``vmap`` over ``ssd_scan`` at shapes the kernels tile: no private
    primitive stands in the way."""
    two = jax.tree.map(lambda v: jnp.stack([v, 0.5 * v]), tiled)
    chunk = TILED["chunk"]
    got = jax.vmap(lambda kw: ssd_scan(**kw, chunk=chunk))(two)
    for i in range(2):
        one = jax.tree.map(lambda v: v[i], two)
        assert _rel(got[i], ssd_scan(**one, chunk=chunk)) < 1e-6
