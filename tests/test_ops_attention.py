"""Attention kernels: flash/blockwise vs the dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_tpu.ops import blockwise_attention, dense_attention, flash_attention


def _qkv(b=2, s=64, h=2, d=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    q = jax.random.normal(ks[0], shape, dtype)
    k = jax.random.normal(ks[1], shape, dtype)
    v = jax.random.normal(ks[2], shape, dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_dense(causal):
    q, k, v = _qkv()
    want = dense_attention(q, k, v, causal=causal)
    got = blockwise_attention(q, k, v, causal=causal, block_k=16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_blockwise_ragged_blocks():
    # seq not divisible by block_k exercises the padding/masking path
    q, k, v = _qkv(s=50)
    want = dense_attention(q, k, v)
    got = blockwise_attention(q, k, v, block_k=16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    want = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, 16, 16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_flash_odd_seq_falls_back_to_full_block():
    # 50 has no power-of-two block divisor except 2 — still correct
    q, k, v = _qkv(s=50)
    want = dense_attention(q, k, v)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_flash_grad_matches_dense():
    q, k, v = _qkv(b=1, s=32, h=2, d=8)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    want = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(
        loss(lambda q, k, v: flash_attention(q, k, v, True, 16, 16)),
        argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_kernel_matches_dense(causal):
    """The Pallas backward (one kernel: dq, dk and dv from each cell's
    probabilities, rebuilt once from the saved log-sum-exp) agrees with
    autodiff through the dense oracle."""
    q, k, v = _qkv(b=2, s=64, h=2, d=16, seed=3)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.vdot(fn(q, k, v), g)

    want = jax.grad(loss(
        lambda q, k, v: dense_attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(
        lambda q, k, v: flash_attention(q, k, v, causal, 16, 16)),
        argnums=(0, 1, 2))(q, k, v)
    for name, gg, w in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(gg, w, rtol=1e-4, atol=1e-5,
                                   err_msg=name)


def test_flash_bwd_uneven_blocks():
    # query/key block sizes that differ and don't divide evenly into
    # power-of-two preferences exercise _pick_block on both grids
    q, k, v = _qkv(b=1, s=48, h=2, d=8, seed=4)

    def f(fn):
        return lambda q, k, v: jnp.sum(jnp.cos(fn(q, k, v)))

    want = jax.grad(f(dense_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(f(lambda q, k, v: flash_attention(q, k, v, True, 16, 8)),
                   argnums=(0, 1, 2))(q, k, v)
    for gg, w in zip(got, want):
        np.testing.assert_allclose(gg, w, rtol=1e-4, atol=1e-5)


def test_flash_bwd_bf16_inputs_accumulate_f32():
    q, k, v = _qkv(b=1, s=64, h=2, d=16, dtype=jnp.bfloat16, seed=5)
    grads = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, True, 16, 16)
                                .astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v)
                                .astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    for gg, w in zip(grads, ref):
        assert gg.dtype == jnp.bfloat16
        np.testing.assert_allclose(gg.astype(np.float32),
                                   w.astype(np.float32), rtol=1e-1,
                                   atol=1e-1)


def test_flash_jit_and_dtypes():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 16, 16))
    got = fn(q, k, v)
    want = dense_attention(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), rtol=5e-2, atol=5e-2)


# --------------------------------------------------------------------------
# Grouped-query (GQA) flash kernels
# --------------------------------------------------------------------------

@pytest.mark.parametrize("heads", [(4, 2), (4, 1), (8, 2)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_gqa_matches_repeat_oracle(heads, causal):
    """Grouped kv heads ride the kernel index maps (nothing materialised
    group x larger); results must equal dense attention over repeated
    kv, forward and gradients — including the grouped dk/dv grid that
    accumulates every group member into one kv-head block."""
    h, hk = heads
    b, s, d = 2, 64, 8
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hk, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hk, d))
    rep = lambda x: jnp.repeat(x, h // hk, axis=2)  # noqa: E731

    out = flash_attention(q, k, v, causal)
    ref = dense_attention(q, rep(k), rep(v), causal)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def loss(q_, k_, v_):
        return jnp.sum(jnp.square(flash_attention(q_, k_, v_, causal)))

    def ref_loss(q_, k_, v_):
        return jnp.sum(jnp.square(
            dense_attention(q_, rep(k_), rep(v_), causal)))

    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    assert got[1].shape == (b, s, hk, d)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_flash_gqa_rejects_indivisible_heads():
    q = jnp.zeros((1, 16, 4, 8))
    kv = jnp.zeros((1, 16, 3, 8))
    with pytest.raises(ValueError, match="kv heads"):
        flash_attention(q, kv, kv)


class TestAutotune:
    @pytest.fixture(autouse=True)
    def _no_ambient_disk_cache(self, monkeypatch):
        # The committed default cache (or an inherited
        # MPI_TPU_TUNE_CACHE) would satisfy sweeps from disk and break
        # the table-shape assertions below; empty = disabled.
        monkeypatch.setenv("MPI_TPU_TUNE_CACHE", "")

    def _shape(self):
        return dict(batch=2, seq=64, heads=2, head_dim=16)

    def test_sweep_picks_and_registers_shape_winner(self):
        from mpi_tpu.ops import flash_block_defaults, tune_flash_blocks
        from mpi_tpu.ops.attention import _tuned_blocks
        from mpi_tpu.ops.autotune import _cache

        _cache.clear()
        _tuned_blocks.clear()
        before = flash_block_defaults()
        try:
            best, table = tune_flash_blocks(
                **self._shape(), candidates=[(32, 32), (64, 64)],
                reps=1, include_bwd=False)
            assert best in [(32, 32), (64, 64)]
            timed = [t for t in table if "ms" in t]
            assert len(timed) == 2
            assert timed[0]["ms"] <= timed[1]["ms"]  # fastest-first
            # The winner registers for the EXACT tuned shape; the
            # process-wide default is untouched (a short-seq winner
            # must not degrade other shapes).
            assert _tuned_blocks[(64, 64)] == best
            assert flash_block_defaults() == before
            # Cache hit: same shape+candidates returns with no table.
            best2, table2 = tune_flash_blocks(
                **self._shape(), candidates=[(32, 32), (64, 64)],
                reps=1, include_bwd=False)
            assert best2 == best and table2 == []
            # Different candidate list = different sweep, not a stale
            # cache hit constrained to the old set.
            best3, table3 = tune_flash_blocks(
                **self._shape(), candidates=[(32, 32)],
                reps=1, include_bwd=False)
            assert best3 == (32, 32) and len(table3) == 1
        finally:
            _cache.clear()
            _tuned_blocks.clear()

    def test_registered_blocks_feed_flash_and_match_dense(self):
        from mpi_tpu.ops.attention import _tuned_blocks, register_tuned_blocks

        rng = np.random.default_rng(3)
        q, k, v = (jnp.asarray(rng.standard_normal((2, 64, 2, 16)),
                               jnp.float32) for _ in range(3))
        try:
            register_tuned_blocks(64, 64, 32, 32)
            got = flash_attention(q, k, v, True)   # blocks default=None
            want = dense_attention(q, k, v, causal=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4)
            # A different shape does NOT hit the (64, 64) entry: it
            # falls back to the global default and still matches dense.
            q2, k2, v2 = (jnp.asarray(
                rng.standard_normal((1, 32, 2, 16)), jnp.float32)
                for _ in range(3))
            np.testing.assert_allclose(
                np.asarray(flash_attention(q2, k2, v2, True)),
                np.asarray(dense_attention(q2, k2, v2, causal=True)),
                rtol=2e-4, atol=2e-4)
        finally:
            _tuned_blocks.clear()

    def test_candidates_collapse_dedupes(self):
        from mpi_tpu.ops import tune_flash_blocks
        from mpi_tpu.ops.attention import _tuned_blocks
        from mpi_tpu.ops.autotune import _cache

        _cache.clear()
        try:
            # seq=32: every preference shrinks to (32, 32) — exactly one
            # config must be timed.
            _, table = tune_flash_blocks(
                batch=1, seq=32, heads=2, head_dim=16,
                candidates=[(128, 128), (256, 512), (512, 512)],
                reps=1, include_bwd=False)
            assert len(table) == 1
        finally:
            _cache.clear()
            _tuned_blocks.clear()

    def test_malformed_env_blocks_warns_not_crashes(self):
        from mpi_tpu.ops import attention as A

        import warnings

        import os as osmod
        osmod.environ["MPI_TPU_FLASH_BLOCKS"] = "256"
        try:
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                got = A._env_flash_blocks()
            assert got == [1024, 1024]
            assert any("malformed" in str(x.message) for x in w)
        finally:
            del osmod.environ["MPI_TPU_FLASH_BLOCKS"]
        assert A._env_flash_blocks() == [1024, 1024]

    def test_disk_cache_roundtrip(self, tmp_path, monkeypatch):
        """MPI_TPU_TUNE_CACHE persists winners across processes: a
        fresh in-process cache hits the disk entry and skips the
        sweep entirely (no table)."""
        from mpi_tpu.ops import tune_flash_blocks
        from mpi_tpu.ops.attention import _tuned_blocks
        from mpi_tpu.ops.autotune import _cache

        path = str(tmp_path / "tune.json")
        monkeypatch.setenv("MPI_TPU_TUNE_CACHE", path)
        _cache.clear()
        try:
            best, table = tune_flash_blocks(
                batch=1, seq=32, heads=2, head_dim=16,
                candidates=[(32, 32)], reps=1, include_bwd=False)
            assert table and best == (32, 32)
            import os as osmod
            assert osmod.path.exists(path)
            # Simulate a new process: wipe the in-memory cache only.
            _cache.clear()
            best2, table2 = tune_flash_blocks(
                batch=1, seq=32, heads=2, head_dim=16,
                candidates=[(32, 32)], reps=1, include_bwd=False)
            assert best2 == best and table2 == []
            # Corrupt file degrades to a re-sweep, never a crash.
            with open(path, "w") as f:
                f.write("not json")
            _cache.clear()
            best3, table3 = tune_flash_blocks(
                batch=1, seq=32, heads=2, head_dim=16,
                candidates=[(32, 32)], reps=1, include_bwd=False)
            assert best3 == best and table3
        finally:
            _cache.clear()
            _tuned_blocks.clear()


def test_tune_deadline_truncates_with_best_so_far(monkeypatch, tmp_path):
    """A sweep deadline keeps the first candidate's result and marks
    the rest untried — tuning can never blow the caller's own budget —
    and a truncated winner must NOT persist to the disk cache (the
    next unhurried run re-tunes the full sweep)."""
    from mpi_tpu.ops import autotune

    cache_file = tmp_path / "tune.json"
    monkeypatch.setenv("MPI_TPU_TUNE_DEADLINE_S", "0.000001")
    monkeypatch.setenv("MPI_TPU_TUNE_CACHE", str(cache_file))
    autotune._cache.clear()
    try:
        best, table = autotune.tune_flash_blocks(
            1, 128, 2, 32, reps=1, set_default=False,
            candidates=[(128, 128), (128, 256), (64, 128)])
        timed = [t for t in table if "ms" in t]
        untried = [t for t in table
                   if "untried" in str(t.get("error", ""))]
        assert len(timed) == 1      # the in-flight candidate finished
        assert untried              # the rest were cut, visibly
        assert best == (timed[0]["block_q"], timed[0]["block_k"])
        assert not cache_file.exists()  # truncated -> not persisted
    finally:
        autotune._cache.clear()


# --------------------------------------------------------------------------
# Dead, crossed and whole grid cells (PR 32)
# --------------------------------------------------------------------------

from mpi_tpu.ops import attention as attn_mod  # noqa: E402
from mpi_tpu.ops import flash_attention_with_lse, flash_chunk_bwd  # noqa: E402
from mpi_tpu.utils import trace  # noqa: E402

# 64 x 64 scores; a prefix of row groups of 16 over column groups of 12,
# which straddle key blocks of 8 and of 16.
_S = _T = 64
_PREFIX = (16, 12)
_MASKS = {"causal": (True, None), "prefix": (False, _PREFIX),
          "both": (True, _PREFIX), "neither": (False, None)}
_BLOCKS = [(8, 16), (16, 16), (16, 8)]   # block_q <, =, > block_k


def _numpy_mask(causal, prefix, s=_S, t=_T):
    row, col = np.arange(s)[:, None], np.arange(t)[None, :]
    mask = np.ones((s, t), bool)
    if causal:
        mask &= row >= col
    if prefix is not None:
        mask &= col // prefix[1] < row // prefix[0]
    return mask


def _cells(mask, bq, bk):
    for qi in range(mask.shape[0] // bq):
        for ki in range(mask.shape[1] // bk):
            yield qi, ki, mask[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]


@pytest.mark.parametrize("blocks", _BLOCKS, ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("kind", list(_MASKS))
def test_cell_kind_matches_numpy_mask(kind, blocks):
    """A cell is dead iff the mask is all false there, whole iff all
    true: with Python ints (here), as with the kernels' traced scalars."""
    causal, prefix = _MASKS[kind]
    bq, bk = blocks
    seen = set()
    for qi, ki, tile in _cells(_numpy_mask(causal, prefix), bq, bk):
        live, whole = attn_mod._cell_kind(qi, ki, bq, bk, causal, prefix)
        assert bool(live) == bool(tile.any()), (qi, ki)
        assert bool(whole) == bool(tile.all()), (qi, ki)
        seen.add("whole" if whole else "crossed" if live else "dead")
    want = {"neither": {"whole"}}.get(kind, {"dead", "crossed", "whole"})
    assert seen == want


def _tiles_census(mask, bq, bk):
    tiles = [t for _, _, t in _cells(mask, bq, bk)]
    return (sum(not t.any() for t in tiles),
            sum(t.any() and not t.all() for t in tiles),
            sum(t.all() for t in tiles))


@pytest.mark.parametrize("blocks", _BLOCKS, ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("kind", list(_MASKS))
def test_census_of_a_head_matches_numpy_mask(kind, blocks):
    """The same function over numpy index arrays, which is how the
    census counts a head's grid."""
    causal, prefix = _MASKS[kind]
    bq, bk = blocks
    assert attn_mod._census(_S // bq, _T // bk, bq, bk, causal, prefix) == \
        _tiles_census(_numpy_mask(causal, prefix), bq, bk)


@pytest.mark.parametrize("blocks", _BLOCKS, ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("kind", list(_MASKS))
def test_dead_cells_name_a_resident_block(kind, blocks):
    """The index maps' clamps: a live cell keeps its own block; a dead
    cell names the nearest live cell of the row (forward, dq) or column
    (dk/dv) it is walked in, so nothing is fetched for it; a row or
    column with no live cell names one block throughout."""
    causal, prefix = _MASKS[kind]
    bq, bk = blocks
    nq, nk = _S // bq, _T // bk
    live = np.zeros((nq, nk), bool)
    for qi, ki, tile in _cells(_numpy_mask(causal, prefix), bq, bk):
        live[qi, ki] = tile.any()
    for qi in range(nq):
        last = max(np.flatnonzero(live[qi]), default=0)
        assert live[qi, :last + 1].all() or not live[qi].any()
        for ki in range(nk):
            got = int(attn_mod._resident_ki(qi, ki, bq, bk, causal, prefix))
            assert got == min(ki, last), (qi, ki, got)
    for ki in range(nk):
        first = min(np.flatnonzero(live[:, ki]), default=nq - 1)
        assert live[first:, ki].all() or not live[:, ki].any()
        for qi in range(nq):
            got = int(attn_mod._resident_qi(qi, ki, nq, bq, bk, causal,
                                            prefix))
            assert got == max(qi, first), (qi, ki, got)


def _cells_counted(counters):
    return tuple(int(counters.get(f"flash.cells.{k}", 0))
                 for k in ("dead", "crossed", "whole"))


@pytest.mark.parametrize("blocks", _BLOCKS, ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("heads", [(2, 2), (6, 2)], ids=["mha", "gqa6-2"])
def test_three_kinds_match_dense_forward_and_grads(heads, blocks, traced):
    """Causal flash at blocks where dead, crossed and whole cells all
    occur: forward, dq, dk and dv against the dense oracle and its
    ``jax.grad`` (kv heads repeated for GQA)."""
    h, hk = heads
    bq, bk = blocks
    b, s, d = 1, _S, 8
    key = jax.random.PRNGKey(32)
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hk, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hk, d))
    g = jax.random.normal(jax.random.fold_in(key, 3), q.shape)
    rep = lambda x: jnp.repeat(x, h // hk, axis=2)  # noqa: E731

    out = flash_attention(q, k, v, True, bq, bk)
    dead, crossed, whole = _cells_counted(traced.counters())
    assert min(dead, crossed, whole) > 0
    assert dead + crossed + whole == b * h * (s // bq) * (s // bk)
    np.testing.assert_allclose(out, dense_attention(q, rep(k), rep(v)),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda q, k, v: jnp.vdot(
        flash_attention(q, k, v, True, bq, bk), g), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.vdot(
        dense_attention(q, rep(k), rep(v)), g), argnums=(0, 1, 2))(q, k, v)
    for name, gg, w in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(gg, w, rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("blocks", [(8, 16), (16, 8)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_prefix_noncausal_matches_eva_jnp_masking(blocks, traced):
    """The prefix mask alone (EVA's summaries' pass), with column groups
    that straddle key blocks: forward and the chunk backward against
    materialised scores under ``_eva_jnp``'s remote mask,
    ``col // k_per < row // q_per``. Rows of the first group see nothing
    and carry no cotangent."""
    bq, bk = blocks
    q_per, k_per = _PREFIX
    b, h, d = 1, 2, 8
    key = jax.random.PRNGKey(29)
    q = jax.random.normal(key, (b, _S, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, _T, h, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, _T, h, d))
    g = jax.random.normal(jax.random.fold_in(key, 3), q.shape)
    g = g.at[:, :q_per].set(0.0)
    remote = jnp.arange(_T)[None, :] // k_per < jnp.arange(_S)[:, None] // q_per

    def dense(q, k, v):
        logits = jnp.einsum("bshk,bthk->bhst", q, k) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where(remote[None, None], logits, attn_mod.NEG_INF), axis=-1)
        return jnp.einsum("bhst,bthk->bshk", probs, v)

    out, lse = flash_attention_with_lse(q, k, v, False, bq, bk,
                                        prefix=_PREFIX)
    dead, crossed, whole = _cells_counted(traced.counters())
    assert min(dead, crossed, whole) > 0
    np.testing.assert_allclose(out[:, q_per:], dense(q, k, v)[:, q_per:],
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(out[:, :q_per]).any()
    got = flash_chunk_bwd(q, k, v, out, lse, g, False, bq, bk,
                          prefix=_PREFIX)
    want = jax.grad(lambda q, k, v: jnp.vdot(dense(q, k, v), g),
                    argnums=(0, 1, 2))(q, k, v)
    for name, gg, w in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(gg, w, rtol=1e-4, atol=1e-5, err_msg=name)


def _cell1_shapes():
    q = jax.ShapeDtypeStruct((2, 4096, 24, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 4096, 2, 128), jnp.bfloat16)
    return q, kv, kv


@pytest.mark.parametrize("blocks,want", [
    ((256, 512), (2688, 768, 2688)), ((None, None), (288, 192, 288))],
    ids=["256x512", "default"])
def test_census_of_cell_one_forward(blocks, want, traced):
    """b 2 x h 24 x s 4096, times 48 heads. At 256 x 512, of a head's 128
    cells 56 are dead, 16 crossed and 56 whole (live iff qi >= 2 ki,
    whole iff qi >= 2 ki + 2); at the default 1024 x 1024, of 16 cells 6,
    4 and 6. Counted when the call is built: nothing runs."""
    jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, True, *blocks),
                   *_cell1_shapes())
    assert _cells_counted(traced.counters()) == want


@pytest.mark.parametrize("kind", list(_MASKS))
def test_census_counts_each_kernel_call(kind, traced):
    """64 x 64 at 16 x 16, one head: the forward's call adds the grid's
    census once, and so does the backward's one fused kernel (causal: 6
    dead, 4 crossed, 6 whole; the prefix with or without it 10, 3, 3)."""
    want = _tiles_census(_numpy_mask(*_MASKS[kind]), 16, 16)
    assert want == {"causal": (6, 4, 6), "neither": (0, 0, 16)}.get(
        kind, (10, 3, 3))
    causal, prefix = _MASKS[kind]
    x = jax.ShapeDtypeStruct((1, _S, 1, 8), jnp.float32)
    jax.eval_shape(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal, 16, 16, prefix=prefix), x, x, x)
    assert _cells_counted(traced.counters()) == want
    lse = jax.ShapeDtypeStruct((1, 1, _S), jnp.float32)
    jax.eval_shape(lambda q, k, v, o, l, g: flash_chunk_bwd(
        q, k, v, o, l, g, causal, 16, 16, prefix=prefix), x, x, x, x, lse, x)
    assert _cells_counted(traced.counters()) == tuple(2 * n for n in want)
    assert traced.counters().get("flash.bwd.fused") == 1


def test_census_stays_silent_with_tracing_off():
    was = trace.enabled()
    trace.disable()
    trace.clear()
    try:
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, True),
                       *_cell1_shapes())
        assert not [n for n in trace.counters() if n.startswith("flash.")]
    finally:
        if was:
            trace.enable()


# --------------------------------------------------------------------------
# One fused backward kernel in place of two
# --------------------------------------------------------------------------

def _bf16_ulps(a, b):
    """How many bfloat16 steps apart each pair of elements lies: the bit
    patterns mapped onto one ordered integer line, so that steps across
    zero count too."""
    def ordered(x):
        bits = np.asarray(jax.lax.bitcast_convert_type(
            jnp.asarray(x, jnp.bfloat16), jnp.int16)).astype(np.int32)
        return np.where(bits < 0, -32768 - bits, bits)
    return np.abs(ordered(a) - ordered(b))


# (causal, prefix, heads, kv heads, head_dim, block_q, block_k) on 64 x 64
# scores: every case has dead, crossed and whole cells but "neither".
_FUSED_CASES = {
    "causal-g1-d64": (True, None, 2, 2, 64, 16, 8),
    "causal-g4-d128": (True, None, 4, 1, 128, 8, 16),
    "causal-g4-d64": (True, None, 8, 2, 64, 16, 16),
    "neither-g4-d128": (False, None, 4, 1, 128, 16, 8),
    "neither-g1-d64": (False, None, 2, 2, 64, 16, 16),
    "prefix-g1-d128": (False, _PREFIX, 2, 2, 128, 16, 8),
    "prefix-g4-d64": (False, _PREFIX, 4, 1, 64, 8, 16),
    "causal-prefix-g4-d128": (True, _PREFIX, 4, 1, 128, 16, 16),
}


@pytest.mark.parametrize("case", list(_FUSED_CASES))
def test_fused_bwd_matches_split_kernels_and_dense(case, monkeypatch,
                                                   traced):
    """The fused backward against the two split kernels, in bfloat16 as
    the train cells run it, and against ``jax.grad`` of materialised
    attention in float32 at the other flash tests' tolerance.

    The sums run in the split kernels' order (dq by key block; dk, dv
    g-major, then by query block), but the fused kernel builds a cell's
    scores keys first, k·qᵀ, and its products from the transposed
    tiles. XLA's CPU dot, which interpret mode runs, may add a product's
    terms in another order for transposed operands, so a bfloat16
    result can round one step apart: held to one bfloat16 ulp. On the
    v5e the three came out bit for bit at every train cell's shape
    (PERF.md, Findings)."""
    causal, prefix, h, hk, d, bq, bk = _FUSED_CASES[case]
    key = jax.random.PRNGKey(46)
    shapes = ((1, _S, h, d), (1, _T, hk, d), (1, _T, hk, d), (1, _S, h, d))
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i), shape)
                  for i, shape in enumerate(shapes))
    g = g.at[:, :_PREFIX[0]].set(0.0) if prefix is not None else g
    mask = jnp.asarray(_numpy_mask(causal, prefix))
    rep = lambda x: jnp.repeat(x, h // hk, axis=2)  # noqa: E731

    def bwd(q, k, v, g):
        out, lse = flash_attention_with_lse(q, k, v, causal, bq, bk,
                                            prefix=prefix)
        return flash_chunk_bwd(q, k, v, out, lse, g, causal, bq, bk,
                               prefix=prefix)

    low = [x.astype(jnp.bfloat16) for x in (q, k, v, g)]
    fused = bwd(*low)
    dead, crossed, whole = _cells_counted(traced.counters())
    if causal or prefix is not None:
        assert min(dead, crossed, whole) > 0
    assert traced.counters().get("flash.bwd.fused") == 1
    with monkeypatch.context() as m:   # as a call over the VMEM budget goes
        m.setattr(attn_mod, "_FUSED_BWD_BUDGET", 0)
        split = bwd(*low)
    assert traced.counters().get("flash.bwd.split") == 1
    for name, a, b in zip(("dq", "dk", "dv"), fused, split):
        assert a.dtype == b.dtype == jnp.bfloat16
        assert _bf16_ulps(a, b).max() <= 1, name

    def dense(q, k, v):
        logits = jnp.einsum("bshk,bthk->bhst", q, rep(k)) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where(mask[None, None], logits, attn_mod.NEG_INF), axis=-1)
        return jnp.einsum("bhst,bthk->bshk", probs, rep(v))

    got = bwd(q, k, v, g)
    want = jax.grad(lambda q, k, v: jnp.vdot(dense(q, k, v), g),
                    argnums=(0, 1, 2))(q, k, v)
    for name, gg, w in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(gg, w, rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("seq, heads, kv_heads, path, kernels", [
    (8192, 32, 2, "fused", ["flash_bwd", "flash_fwd"]),
    (32768, 2, 1, "split", ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"])],
    ids=["nemotron-cell", "over-the-budget"])
def test_backward_path_is_picked_from_the_shapes(seq, heads, kv_heads, path,
                                                 kernels, traced):
    """The nemotron cell's attention (b 2 x s 8,192, 32 query heads on 2
    kv heads of 128) wants 4 MiB of dqᵀ and 4 each of dk and dv, under
    the budget: one ``flash_bwd``. s = t = 32,768 wants 16 + 2 x 16 MiB,
    over it: the two split kernels. Each counts its path once; traced,
    nothing runs."""
    q = jax.ShapeDtypeStruct((2, seq, heads, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, seq, kv_heads, 128), jnp.bfloat16)
    fits = attn_mod._fused_bwd_bytes(seq, seq, 128) <= \
        attn_mod._FUSED_BWD_BUDGET
    assert fits == (path == "fused")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, True)
                                .astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, kv, kv)
    assert sorted(eqn.params["name"] for eqn in jaxpr.jaxpr.eqns
                  if eqn.primitive.name == "pallas_call") == kernels
    assert {k: v for k, v in traced.counters().items()
            if k.startswith("flash.bwd.")} == {f"flash.bwd.{path}": 1}
