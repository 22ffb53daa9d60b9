"""Pallas TPU flash-attention prefill kernel (causal, sliding-window,
chunked-prefill aware).

The prefill hot-spot.  Tiled (q_block x kv_block) online-softmax flash
attention with GQA folded into the q-block rows (the G query heads of a
KV-head group share the staged K/V tile — one HBM->VMEM copy serves G
heads).  ``q_offset`` places the query chunk at absolute positions for
chunked prefill (queries [q_offset, q_offset+Sq) attend over K/V
[0, Sk)).  Off-diagonal tiles that the causal/window mask fully excludes
are skipped before any compute.

K/V arrive head-major, ``(B, Hkv, Sk, D)``, so a staged K/V block is a
``(block_k, D)`` tile that meets Mosaic's (8, 128) block rule.

Grid: (batch, kv_heads, q_blocks, kv_blocks), kv innermost (sequential)
so the VMEM accumulator carries across KV tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref,
            *, block_q: int, block_k: int, window: int, q_offset: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    g, d = q_ref.shape[2], q_ref.shape[4]
    rows = g * block_q

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = qi * block_q + q_offset              # first absolute q position
    q_hi = q_lo + block_q - 1                   # last absolute q position
    k_lo = ki * block_k
    # causal: tile dead if all kpos > all qpos; window: dead if all kpos
    # <= all qpos - window.
    alive = (k_lo <= q_hi) & (lengths_ref[b] > k_lo)
    if window:
        alive &= (k_lo + block_k - 1) > (q_lo - window)

    @pl.when(alive)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, d) * (d ** -0.5)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (rows, bk)
        rowid = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        qpos = q_lo + rowid % block_q            # row = g*bq + j
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = (kpos <= qpos) & (kpos < lengths_ref[b])
        if window:
            mask &= kpos > (qpos - window)
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


@functools.partial(jax.jit, static_argnames=(
    "window", "q_offset", "block_q", "block_k", "interpret"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                  lengths: jax.Array, *, window: int = 0, q_offset: int = 0,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool = True) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Hkv, Sk, D) head-major; lengths: (B,)
    valid K tokens.  Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, \
        f"seq lens ({sq},{sk}) must tile by ({block_q},{block_k})"
    nq, nk = sq // block_q, sk // block_k
    # layout: (B, Hkv, G, Sq, D) so one block carries the whole head group
    q5 = q.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, block_q, d),
                         lambda b_, h_, qi, ki, ln: (b_, h_, 0, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, ln: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, ln: (b_, h_, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, block_q, d),
                               lambda b_, h_, qi, ki, ln: (b_, h_, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((g * block_q, d), jnp.float32),
            pltpu.VMEM((g * block_q, 1), jnp.float32),
            pltpu.VMEM((g * block_q, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          window=window, q_offset=q_offset),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, sq, d), q.dtype),
        interpret=interpret,
    )(lengths, q5, k, v)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
