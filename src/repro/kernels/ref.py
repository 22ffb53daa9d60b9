"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth for tests/test_kernels.py shape/dtype sweeps
(assert_allclose vs the interpret-mode kernels) and the ``impl="ref"``
path of every step function.  Page pools are head-major,
``(Hkv, P, page, D)``, as the kernels read them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def gather_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(Hkv, P, page, D) pool + (B, NB) tables -> contiguous
    (B, NB*page, Hkv, D) per-sequence K or V."""
    hkv, _, page, d = pages.shape
    b, nb = block_tables.shape
    x = pages[:, block_tables]                           # (Hkv, B, NB, page, D)
    return x.transpose(1, 2, 3, 0, 4).reshape(b, nb * page, hkv, d)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array
                        ) -> jax.Array:
    """Decode attention over a paged KV cache.

    q:            (B, H, D)         one query token per sequence
    k_pages:      (Hkv, P, page, D) global page pool, head-major
    v_pages:      (Hkv, P, page, D)
    block_tables: (B, NB) int32     page ids per sequence (padded arbitrary)
    lengths:      (B,) int32        tokens in cache (incl. current token)
    returns:      (B, H, D)
    """
    b, h, d = q.shape
    hkv, _, page, _ = k_pages.shape
    nb = block_tables.shape[1]
    g = h // hkv
    k = gather_pages(k_pages, block_tables)              # (B, NB*page, Hkv, D)
    v = gather_pages(v_pages, block_tables)
    qf = (q.astype(jnp.float32) * d ** -0.5).reshape(b, hkv, g, d)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qf, k.astype(jnp.float32))
    valid = jnp.arange(nb * page)[None] < lengths[:, None]        # (B, K)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_prefill_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      block_tables: jax.Array, ctx_lens: jax.Array,
                      chunk_lens: jax.Array) -> jax.Array:
    """Chunked-prefill attention over a paged KV cache (dense oracle).

    q:            (B, S, H, D)      one prefill chunk per request; row j
                                    sits at absolute pos ctx_lens[b] + j
    k_pages:      (Hkv, P, page, D) global page pool (chunk K/V already
    v_pages:      (Hkv, P, page, D)  scattered in), head-major
    block_tables: (B, NB) int32     pages covering [0, ctx+chunk)
    ctx_lens:     (B,) int32        tokens in pages before the chunk
    chunk_lens:   (B,) int32        valid chunk tokens (rows beyond are
                                    padding; their output rows are 0)
    returns:      (B, S, H, D)
    """
    b, s, h, d = q.shape
    hkv, _, page, _ = k_pages.shape
    nb = block_tables.shape[1]
    g = h // hkv
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    qpos = ctx_lens[:, None] + jnp.arange(s)[None]             # (B, S)
    kpos = jnp.arange(nb * page)
    total = (ctx_lens + chunk_lens)[:, None, None]
    mask = (kpos[None, None, :] <= qpos[:, :, None]) \
        & (kpos[None, None, :] < total) \
        & (jnp.arange(s)[None, :, None] < chunk_lens[:, None, None])
    qf = (q.astype(jnp.float32) * d ** -0.5).reshape(b, s, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    any_valid = mask.any(-1)[:, None, None, :, None]
    probs = jnp.where(any_valid, probs, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


def flash_prefill_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      lengths: jax.Array, *, window: int = 0,
                      q_offset: int = 0) -> jax.Array:
    """Causal (optionally sliding-window) prefill attention.

    q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D); lengths: (B,) valid k tokens.
    ``q_offset`` places the query chunk at absolute positions
    [q_offset, q_offset+Sq) — used by chunked prefill.
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    mask = kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    mask = mask[None] & (kpos[None, None, :] < lengths[:, None, None])
    qf = (q.astype(jnp.float32) * d ** -0.5).reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # rows with no valid key (qpos >= length under a window) define to 0
    any_valid = mask.any(-1)[:, None, None, :, None]
    probs = jnp.where(any_valid, probs, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)
