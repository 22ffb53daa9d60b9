"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


PAGED_CASES = [
    # (B, H, Hkv, D, page, NB, dtype)
    (4, 8, 4, 64, 16, 8, jnp.float32),
    (2, 8, 8, 128, 16, 4, jnp.float32),     # MHA
    (3, 16, 2, 64, 32, 4, jnp.float32),     # high group ratio
    (1, 4, 1, 256, 16, 8, jnp.float32),     # MQA, big head
    (4, 8, 4, 64, 16, 8, jnp.bfloat16),     # serving dtype
]


@pytest.mark.parametrize("b,h,hkv,d,page,nb,dtype", PAGED_CASES)
def test_paged_attention_matches_ref(b, h, hkv, d, page, nb, dtype):
    p = b * nb + 3
    q = _rand((b, h, d), dtype)
    kp = _rand((hkv, p, page, d), dtype)
    vp = _rand((hkv, p, page, d), dtype)
    bt = jnp.asarray(RNG.permutation(p)[:b * nb].reshape(b, nb), jnp.int32)
    lengths = jnp.asarray(RNG.integers(1, nb * page + 1, b), jnp.int32)
    out = paged_attention(q, kp, vp, bt, lengths)
    refv = kref.paged_attention_ref(q, kp, vp, bt, lengths)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(refv, np.float32),
                               atol=tol, rtol=tol)


def test_paged_attention_length_one():
    """Degenerate cache of a single token."""
    q = _rand((2, 4, 64), jnp.float32)
    kp = _rand((2, 8, 16, 64), jnp.float32)
    vp = _rand((2, 8, 16, 64), jnp.float32)
    bt = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    lengths = jnp.ones(2, jnp.int32)
    out = paged_attention(q, kp, vp, bt, lengths)
    refv = kref.paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(out, refv, atol=2e-5, rtol=2e-5)


FLASH_CASES = [
    # (B, Sq, Sk, H, Hkv, D, window, q_offset, dtype)
    (2, 128, 128, 8, 4, 64, 0, 0, jnp.float32),
    (2, 128, 256, 4, 2, 64, 0, 128, jnp.float32),   # chunked prefill
    (1, 256, 256, 4, 1, 128, 64, 0, jnp.float32),   # sliding window MQA
    (2, 64, 64, 4, 4, 32, 0, 0, jnp.float32),
    (1, 192, 320, 6, 3, 64, 100, 128, jnp.float32),  # window + offset
    (2, 128, 128, 8, 4, 64, 0, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("b,sq,sk,h,hkv,d,win,qoff,dtype", FLASH_CASES)
def test_flash_prefill_matches_ref(b, sq, sk, h, hkv, d, win, qoff, dtype):
    q = _rand((b, sq, h, d), dtype)
    k = _rand((b, sk, hkv, d), dtype)
    v = _rand((b, sk, hkv, d), dtype)
    lengths = jnp.asarray(RNG.integers(sk // 2, sk + 1, b), jnp.int32)
    # the kernel takes head-major K/V, (B, Hkv, Sk, D)
    out = flash_prefill(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                        lengths, window=win, q_offset=qoff,
                        block_q=64, block_k=64)
    refv = kref.flash_prefill_ref(q, k, v, lengths, window=win,
                                  q_offset=qoff)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(refv, np.float32),
                               atol=tol, rtol=tol)


def test_ops_wrapper_pads_ragged_seqs():
    """ops.flash_attention must handle non-block-multiple lengths."""
    q = _rand((2, 100, 4, 64), jnp.float32)
    k = _rand((2, 173, 2, 64), jnp.float32)
    v = _rand((2, 173, 2, 64), jnp.float32)
    lengths = jnp.asarray([173, 90], jnp.int32)
    out = ops.flash_attention(q, k, v, lengths, block_q=64, block_k=64)
    refv = kref.flash_prefill_ref(q, k, v, lengths)
    np.testing.assert_allclose(out, refv, atol=3e-5, rtol=3e-5)


def test_blockwise_model_attention_matches_materialized():
    """The pure-JAX flash used by train/prefill (repro.models.layers)."""
    from repro.models import layers
    q = _rand((2, 200, 8, 64), jnp.float32)
    k = _rand((2, 200, 4, 64), jnp.float32)
    v = _rand((2, 200, 4, 64), jnp.float32)
    for win in (0, 64):
        small = layers.attn_causal(q, k, v, window=win)
        blocked = layers._blockwise(q, k, v, scale=None, q_offset=0,
                                    window=win, softcap=0.0,
                                    norm="softmax", block_q=64, block_k=64)
        np.testing.assert_allclose(small, blocked, atol=2e-5, rtol=2e-5)


def test_blockwise_gradients_finite():
    from repro.models import layers

    def loss(q, k, v):
        return layers._blockwise(q, k, v, scale=None, q_offset=0, window=0,
                                 softcap=0.0, norm="softmax",
                                 block_q=64, block_k=64).sum()

    q = _rand((1, 128, 4, 32), jnp.float32)
    k = _rand((1, 128, 2, 32), jnp.float32)
    v = _rand((1, 128, 2, 32), jnp.float32)
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
