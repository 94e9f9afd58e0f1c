"""Vision Transformer (models/vit.py): the encoder family over the
shared blocks — non-causal kernels, classifier training, dp/tp
sharding parity."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mpi_tpu.models import (ViTConfig, forward_vit, init_vit_params,
                            make_vit_train_step)
from mpi_tpu.models.transformer import make_mesh_nd

CFG = ViTConfig(image_size=16, patch_size=4, channels=3, n_classes=7,
                d_model=32, n_heads=4, n_layers=2, d_ff=64)


def _images(b=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((b, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 7, b).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def test_forward_shapes_and_patchify_order():
    params = init_vit_params(jax.random.PRNGKey(0), CFG)
    imgs, _ = _images(3)
    logits = forward_vit(params, imgs, CFG)
    assert logits.shape == (3, 7) and logits.dtype == jnp.float32
    # wrong image shape is a loud error
    with pytest.raises(ValueError, match="expected 16x16x3"):
        forward_vit(params, jnp.zeros((2, 8, 8, 3)), CFG)


def test_flash_noncausal_matches_dense():
    """The encoder runs the flash kernel with causal=False — logits
    must match the dense-attention oracle."""
    import dataclasses

    params = init_vit_params(jax.random.PRNGKey(1), CFG)
    imgs, _ = _images(2, seed=3)
    dense = forward_vit(params, imgs, CFG)
    flash = forward_vit(params, imgs,
                        dataclasses.replace(CFG, attention_impl="flash"))
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_bidirectional_attention_is_position_symmetric():
    """causal=False means information flows both ways: permuting the
    PATCH positions of the input must change logits only through the
    position table — with a zeroed position table, logits are
    invariant to patch permutation (impossible under a causal mask)."""
    params = init_vit_params(jax.random.PRNGKey(2), CFG)
    params = dict(params, pos=jnp.zeros_like(params["pos"]))
    rng = np.random.default_rng(5)
    imgs = rng.standard_normal((1, 16, 16, 3)).astype(np.float32)
    # swap the top and bottom halves of the image (patch rows permute)
    swapped = np.concatenate([imgs[:, 8:], imgs[:, :8]], axis=1)
    a = forward_vit(params, jnp.asarray(imgs), CFG)
    b = forward_vit(params, jnp.asarray(swapped), CFG)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)


def test_training_reduces_loss():
    init_state, step = make_vit_train_step(CFG, learning_rate=1e-2)
    state = init_state(jax.random.PRNGKey(0))
    batch = _images(8)
    losses = []
    for _ in range(5):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert losses[0] == pytest.approx(np.log(7), rel=0.3)  # ~uniform


def test_sharded_training_matches_single_device():
    mesh = make_mesh_nd(8)  # dp x sp x tp — vit uses dp + tp
    init_s, step_s = make_vit_train_step(CFG, mesh=mesh,
                                         learning_rate=1e-2)
    init_1, step_1 = make_vit_train_step(CFG, learning_rate=1e-2)
    ss, s1 = init_s(jax.random.PRNGKey(0)), init_1(jax.random.PRNGKey(0))
    batch = _images(8)
    for _ in range(3):
        ss, ls = step_s(ss, batch)
        s1, l1 = step_1(s1, batch)
        assert float(ls) == pytest.approx(float(l1), rel=2e-4)
    # tp sharding reached the shared blocks (w1 is (d, f), tp on f)
    w1 = ss["params"]["blocks"][0]["w1"]
    assert len({s.index for s in w1.addressable_shards}) == 2


def test_zigzag_rejected_for_encoder():
    """Only the zigzag layouts are causal-only; the ring layer raises
    with its own message when an encoder asks for them."""
    import dataclasses

    cfg = dataclasses.replace(CFG, attention_impl="zigzag_flash")
    params = init_vit_params(jax.random.PRNGKey(0), CFG)
    mesh = make_mesh_nd(8)
    with pytest.raises(ValueError, match="zigzag"):
        forward_vit(params, _images(2)[0], cfg, mesh)


def test_remat_with_mesh_matches_no_remat():
    """remat + mesh (the combination the module doc advertises):
    jax.checkpoint wraps the cfg/mesh-bound block, so the Mesh never
    becomes a dynamic argument — and the math is unchanged."""
    import dataclasses

    mesh = make_mesh_nd(8)
    params = init_vit_params(jax.random.PRNGKey(4), CFG)
    imgs, labels = _images(4, seed=9)
    plain = forward_vit(params, imgs, CFG, mesh)
    remat = forward_vit(params, imgs,
                        dataclasses.replace(CFG, remat=True), mesh)
    np.testing.assert_allclose(np.asarray(remat), np.asarray(plain),
                               rtol=1e-5, atol=1e-6)
    # and it trains (the backward recompute path compiles)
    init_s, step = make_vit_train_step(
        dataclasses.replace(CFG, remat=True), mesh=mesh,
        learning_rate=1e-2)
    state = init_s(jax.random.PRNGKey(0))
    state, l1 = step(state, (imgs, labels))
    _, l2 = step(state, (imgs, labels))
    assert float(l2) < float(l1)


def test_remat_goes_through_the_one_helper(traced):
    """``forward_vit`` wraps its blocks where the decoder does
    (``transformer.checkpointed_block``): the helper's counter sees every
    block of an encoder under remat and none of one without."""
    import dataclasses

    params = init_vit_params(jax.random.PRNGKey(4), CFG)
    imgs, _ = _images(2)
    forward_vit(params, imgs, CFG)
    assert "remat.blocks" not in traced.counters()
    forward_vit(params, imgs, dataclasses.replace(CFG, remat=True))
    assert traced.counters()["remat.blocks"] == CFG.n_layers


def test_encoder_sequence_parallel_ulysses_and_ring():
    """causal=False flows through to the contiguous ring and ulysses
    sequence-parallel impls (only zigzag is causal-only): encoder
    logits match the dense oracle on an sp mesh."""
    import dataclasses

    from mpi_tpu.models import TransformerConfig, forward, init_params

    mesh = make_mesh_nd(8)  # dp x sp x tp
    base = TransformerConfig(vocab=32, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_seq=16,
                             causal=False)
    params = init_params(jax.random.PRNGKey(0), base)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 32, (4, 16)),
                       jnp.int32)
    want = forward(params, toks, base)
    for impl in ("ulysses", "ring"):
        got = forward(params, toks,
                      dataclasses.replace(base, attention_impl=impl),
                      mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    # zigzag stays causal-only, raising at the ring layer
    with pytest.raises(ValueError, match="zigzag"):
        forward(params, toks,
                dataclasses.replace(base, attention_impl="zigzag"), mesh)
