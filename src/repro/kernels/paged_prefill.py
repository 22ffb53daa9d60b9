"""Pallas TPU paged flash-prefill kernel (chunked prefill over paged KV).

The second serving hot-spot, closing the gap the decode kernel left
open: a prefill chunk whose K/V (and all earlier context) already live
in the global page pool attends *directly over the pages* — no
per-layer ``k_pages[block_table]`` materialization and no dense
(S, NB*page) score matrix.  Block tables ride in as scalar prefetch so
each grid step's BlockSpec index_map stages KV pages HBM->VMEM; the
chunk's dynamic context offset (``ctx_lens``) is a traced scalar, not a
static kernel param, so one compiled kernel serves every chunk position
of every request.

Each grid step stages a PAIR of pages (two scalar-prefetched K and V
BlockSpecs) so the MXU sees a (G*bq, 2*page) score tile per step — one
page per step would halve the tile and double the sequential grid
length.  Masking: query row j of request b sits at absolute position
``ctx_lens[b] + j`` and may see keys at positions <= that (causal over
the whole paged history, chunk included).  Rows past ``chunk_lens[b]``
are padding and fully masked (their output rows are zero).  Pages past
the live context clamp their index_map to the last live page so dead
grid steps re-stage an already-resident page instead of burning
HBM->VMEM bandwidth on padding block-table entries, and skip compute
via ``pl.when``.

Grid: (batch, kv_heads, q_blocks, page_pairs), page dim innermost
(sequential) so the VMEM flash accumulator carries across pages.  GQA
is folded into the q-block rows — the G query heads of a KV-head group
share each staged page pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

PAIR = 2                      # pages staged per sequential grid step


def _kernel(
    # scalar prefetch
    block_tables_ref,   # (B, NB) int32
    ctx_lens_ref,       # (B,) int32 — tokens already in pages before chunk
    chunk_lens_ref,     # (B,) int32 — valid tokens in the chunk
    # inputs (blocked)
    q_ref,              # (1, 1, G, bq, D)
    k0_ref, k1_ref,     # (1, 1, page, D) — the staged page pair
    v0_ref, v1_ref,     # (1, 1, page, D)
    # output
    o_ref,              # (1, 1, G, bq, D)
    # scratch
    acc_ref,            # (G*bq, D) f32
    m_ref,              # (G*bq, 1) f32
    l_ref,              # (G*bq, 1) f32
    *, block_q: int, page_size: int,
):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    g, d = q_ref.shape[2], q_ref.shape[4]
    rows = g * block_q
    span = PAIR * page_size

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = ctx_lens_ref[b]
    total = ctx + chunk_lens_ref[b]
    q_lo = ctx + qi * block_q               # first absolute q position
    q_hi = q_lo + block_q - 1               # last absolute q position
    k_lo = ki * span
    # tile dead if: every key pos is beyond every causal q pos, the pair
    # is past the live context, or the whole q block is chunk padding.
    alive = (k_lo <= q_hi) & (k_lo < total) & \
        (qi * block_q < chunk_lens_ref[b])

    @pl.when(alive)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d) * (d ** -0.5)
        k = jnp.concatenate([k0_ref[0, 0], k1_ref[0, 0]]).astype(
            jnp.float32)                                       # (span, D)
        v = jnp.concatenate([v0_ref[0, 0], v1_ref[0, 0]]).astype(
            jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (rows, span)
        rowid = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        j = rowid % block_q                  # row = g*bq + j
        qpos = q_lo + j
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        mask = (kpos <= qpos) & (kpos < total) & \
            (qi * block_q + j < chunk_lens_ref[b])
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # rows fully masked in this tile have m_new == NEG_INF; exp(0)=1
        # would pollute the accumulator — zero them via the mask.
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        l_ref[...] = l_prev * alpha + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = ((acc_ref[...] / l)
                       .reshape(g, block_q, d).astype(o_ref.dtype))


def _live_page(bt, ctx, chunk, b, i, nb, page_size):
    """Clamp page index ``i`` to the request's last live (or last real)
    page so masked-out grid steps never DMA padding block-table entries."""
    total = ctx[b] + chunk[b]
    last = jnp.maximum((total + page_size - 1) // page_size - 1, 0)
    return bt[b, jnp.minimum(jnp.minimum(i, last), nb - 1)]


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def paged_prefill(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                  block_tables: jax.Array, ctx_lens: jax.Array,
                  chunk_lens: jax.Array, *, block_q: int = 128,
                  interpret: bool = True) -> jax.Array:
    """q: (B, S, H, D) chunk queries; k_pages/v_pages: (Hkv, P, page, D)
    head-major;
    block_tables: (B, NB) int32; ctx_lens/chunk_lens: (B,) int32.
    Pages must already contain the chunk's own K/V.  Returns
    (B, S, H, D) with rows >= chunk_lens[b] zeroed."""
    b, s, h, d = q.shape
    hkv, _, page, _ = k_pages.shape
    nb = block_tables.shape[1]
    g = h // hkv
    block_q = min(block_q, s)
    assert s % block_q == 0, \
        f"chunk len {s} must tile by block_q {block_q}"
    nq = s // block_q
    npair = -(-nb // PAIR)
    # layout: (B, Hkv, G, S, D) so one block carries the whole head group
    q5 = q.reshape(b, s, hkv, g, d).transpose(0, 2, 3, 1, 4)

    def kv_map(which):
        def index_map(b_, h_, qi, ki, bt, cx, cl):
            return (h_, _live_page(bt, cx, cl, b_, PAIR * ki + which, nb,
                                   page), 0, 0)
        return index_map

    kv_specs = [pl.BlockSpec((1, 1, page, d), kv_map(w))
                for w in range(PAIR)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, nq, npair),
        in_specs=[
            pl.BlockSpec((1, 1, g, block_q, d),
                         lambda b_, h_, qi, ki, bt, cx, cl:
                         (b_, h_, 0, qi, 0)),
            *kv_specs, *kv_specs,
        ],
        out_specs=pl.BlockSpec((1, 1, g, block_q, d),
                               lambda b_, h_, qi, ki, bt, cx, cl:
                               (b_, h_, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((g * block_q, d), jnp.float32),
            pltpu.VMEM((g * block_q, 1), jnp.float32),
            pltpu.VMEM((g * block_q, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, page_size=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, s, d), q.dtype),
        interpret=interpret,
    )(block_tables, ctx_lens, chunk_lens, q5, k_pages, k_pages,
      v_pages, v_pages)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d)
