"""Pallas TPU paged-attention decode kernel.

The serving hot-spot: one query token per sequence attends over a paged
KV cache addressed through per-sequence block tables (vLLM-style).  TPU
adaptation (vs. the CUDA original): block tables ride in as *scalar
prefetch* so each grid step's BlockSpec index_map can stage exactly one
KV page HBM->VMEM ahead of compute; the flash accumulator lives in VMEM
scratch and persists across the (sequential, innermost) page dimension
of the grid.  The pool is head-major, ``(Hkv, P, page, D)``, so each
staged KV block is a ``(page, D)`` tile: Mosaic needs a block's last two
dims divisible by (8, 128) or equal to the array's, which a page of a
multiple of 8 tokens at head_dim 128 meets.  The MXU sees
(G, page) x (page, D) matmuls.

Grid: (batch, kv_heads, num_blocks); one program handles the G = H/Hkv
query-head group for one page of one sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(
    # scalar prefetch
    block_tables_ref,   # (B, NB) int32
    lengths_ref,        # (B,) int32
    # inputs (blocked)
    q_ref,              # (1, 1, G, D)
    k_ref,              # (1, 1, page, D)
    v_ref,              # (1, 1, page, D)
    # output
    o_ref,              # (1, 1, G, D)
    # scratch
    acc_ref,            # (G, D) f32
    m_ref,              # (G, 1) f32
    l_ref,              # (G, 1) f32
    *, page_size: int,
):
    b = pl.program_id(0)
    i = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lengths_ref[b]
    page_start = i * page_size

    @pl.when(page_start < length)
    def _compute():
        g, d = q_ref.shape[2], q_ref.shape[3]
        q = q_ref[0, 0].astype(jnp.float32) * (d ** -0.5)      # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                    # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)                    # (page, D)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (G, page)
        pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        logits = jnp.where(pos < length, logits, NEG_INF)
        # --- online softmax update
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)        # (G, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)                            # (G, page)
        l_ref[...] = l_prev * alpha + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array,
                    *, page_size: int = 0,
                    interpret: bool = True) -> jax.Array:
    """q: (B, H, D); k_pages/v_pages: (Hkv, P, page, D) head-major;
    block_tables: (B, NB) int32; lengths: (B,) int32 -> (B, H, D)."""
    b, h, d = q.shape
    hkv, _, page, _ = k_pages.shape
    if page_size == 0:
        page_size = page
    assert page == page_size
    nb = block_tables.shape[1]
    g = h // hkv
    q4 = q.reshape(b, hkv, g, d)

    def kv_map(b_, h_, i_, bt, ln):
        # pages past the sequence length are masked out of compute; clamp
        # their index to the last live page so the dead grid steps re-stage
        # an already-resident page instead of DMA'ing padding entries.
        last = jnp.maximum((ln[b_] + page_size - 1) // page_size - 1, 0)
        return h_, bt[b_, jnp.minimum(i_, last)], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, i_, bt, ln:
                         (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d), kv_map),
            pl.BlockSpec((1, 1, page_size, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h_, i_, bt, ln:
                               (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q4, k_pages, v_pages)
    return out.reshape(b, h, d)
