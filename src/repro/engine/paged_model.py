"""Jitted paged-KV forward passes for serving (uniform-attention archs).

This is the engine's "vLLM model runner" role.  Three entry points, all
``lax.scan``s over the stacked layer parameters of a single-run config
(DENSE or MOE pattern), reusing the substrate's MoE/MLP/norm code:

- ``prefill_step``: one (possibly chunked) prefill for one request.
  The chunk's K/V are scattered into the global page pool and the chunk
  attends *directly over the pages* via the paged flash-prefill Pallas
  kernel (``kernels/paged_prefill.py``) — no per-layer
  ``k_pages[block_table]`` materialization, no dense (S, NB*page) mask.
- ``decode_batch``: one token per sequence through the Pallas
  paged-attention decode kernel.
- ``mixed_step``: the fused continuous-batching step.  B decode tokens
  and K prefill chunks are flattened into ONE (1, B + K*S, d) token
  batch: embedding, norms, QKV/out projections, LoRA and the MLP/MoE
  all run over the unified token dim (so the MXU sees one big matmul
  per op instead of two small ones), and only attention forks — decode
  rows through the decode kernel, chunk rows through the paged-prefill
  kernel.  This is the vLLM-style mixed batch the engine's token-budget
  scheduler drives.

High-density LoRA (paper §3.2.1) is applied in-batch: every request
carries an adapter id into a gathered (adapter, d, r) x (adapter, r, out)
pair on the q/v projections — adapter 0 is the zero (base-model) adapter,
so mixed batches of base + N adapters run in one step.  ``mixed_step``
gathers one adapter pair per decode row and per chunk, so decode and
prefill rows of different adapters coexist in the same fused pass.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import layers, moe
from repro.models import model as M
from repro.models.config import DENSE, MOE, ModelConfig
from repro.models.params import Spec, abstract_params, init_params


def pageable(cfg: ModelConfig) -> bool:
    """True when the paged path supports this config (uniform attn run)."""
    return (len(cfg.layer_runs) == 1
            and cfg.layer_runs[0][0] in (DENSE, MOE)
            and cfg.num_codebooks == 0)


class PagePool(NamedTuple):
    """Head-major page pool: one KV head of one page is a contiguous
    (page, D) tile, the block the Pallas kernels stage per grid step."""
    k: jax.Array            # (L, Hkv, P, page, D)
    v: jax.Array


def init_pool(cfg: ModelConfig, num_pages: int, page_size: int,
              dtype=jnp.float32) -> PagePool:
    shape = (cfg.n_layers, cfg.n_kv_heads, num_pages, page_size,
             cfg.head_dim)
    return PagePool(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _write_kv(pages: jax.Array, pidx: jax.Array, slot: jax.Array,
              x: jax.Array) -> jax.Array:
    """Scatter token rows x (T, Hkv, D) into one layer's (Hkv, P, page,
    D) pages at (pidx, slot) per token; pidx == P (out of range) drops
    the row."""
    return pages.at[:, pidx, slot].set(x.transpose(1, 0, 2), mode="drop")


# ---------------------------------------------------------------- LoRA
def lora_specs(cfg: ModelConfig, n_adapters: int, rank: int) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "A_q": Spec((n_adapters, d, rank), (None, None, None), "zeros"),
        "B_q": Spec((n_adapters, rank, h * hd), (None, None, None), "zeros"),
        "A_v": Spec((n_adapters, d, rank), (None, None, None), "zeros"),
        "B_v": Spec((n_adapters, rank, hkv * hd), (None, None, None),
                    "zeros"),
    }


def init_lora(cfg: ModelConfig, n_adapters: int, rank: int,
              dtype=jnp.float32):
    return init_params(lora_specs(cfg, n_adapters, rank), jax.random.PRNGKey(7),
                       dtype)


def make_adapter(cfg: ModelConfig, rank: int, key: jax.Array,
                 dtype=jnp.float32):
    """A single random (non-zero) adapter's weights."""
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    a_scale = 1.0 / (d ** 0.5)
    b_scale = 0.5 / (rank ** 0.5)       # strong enough to alter outputs
    return {
        "A_q": jax.random.normal(k1, (d, rank), dtype) * a_scale,
        "B_q": jax.random.normal(k2, (rank, h * hd), dtype) * b_scale,
        "A_v": jax.random.normal(k3, (d, rank), dtype) * a_scale,
        "B_v": jax.random.normal(k4, (rank, hkv * hd), dtype) * b_scale,
    }


def _lora_delta(lora, which, x, adapter_ids):
    """x: (B, S, d); adapter_ids: (B,) -> (B, S, out)."""
    a = lora[f"A_{which}"][adapter_ids]          # (B, d, r)
    b_ = lora[f"B_{which}"][adapter_ids]         # (B, r, out)
    return jnp.einsum("bsr,bro->bso", jnp.einsum("bsd,bdr->bsr", x, a), b_)


def _qkv_lora(p_attn, cfg, x, positions, lora, adapter_ids):
    q, k, v = layers.attn_qkv(p_attn, cfg, x, positions)
    if lora is not None:
        b, s = x.shape[:2]
        dq = _lora_delta(lora, "q", x, adapter_ids).reshape(
            b, s, cfg.n_heads, cfg.head_dim)
        dv = _lora_delta(lora, "v", x, adapter_ids).reshape(
            b, s, cfg.n_kv_heads, cfg.head_dim)
        # note: LoRA delta applied post-rope on q is an approximation we
        # avoid — recompute rope on the delta instead (rope is linear).
        sin, cos = layers.rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
        q = q + layers.apply_rope(dq, sin, cos)
        v = v + dv
    return q, k, v


def _qkv_lora_groups(p_attn, cfg, x, positions, lora, groups):
    """Like ``_qkv_lora_mixed`` for a flattened token batch made of
    chunk-shaped *groups*: ``groups`` is a static list of
    ``(adapter_ids, n_chunks, chunk_width)`` covering the token dim in
    order.  The adapter pair is gathered once per chunk, not per token
    (all rows of a chunk share one request's adapter)."""
    q, k, v = layers.attn_qkv(p_attn, cfg, x, positions)
    if lora is not None:
        d_model = x.shape[-1]

        def delta(which, heads):
            parts, off = [], 0
            for aids, n, s in groups:
                seg = x[0, off:off + n * s].reshape(n, s, d_model)
                d_seg = _lora_delta(lora, which, seg, aids)
                parts.append(d_seg.reshape(n * s, heads, cfg.head_dim))
                off += n * s
            return jnp.concatenate(parts)[None]
        dq = delta("q", cfg.n_heads)
        dv = delta("v", cfg.n_kv_heads)
        sin, cos = layers.rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
        q = q + layers.apply_rope(dq, sin, cos)
        v = v + dv
    return q, k, v


def _qkv_lora_mixed(p_attn, cfg, x, positions, lora, dec_adapter_ids,
                    pre_adapter_ids, n_dec, n_pre, s):
    """Like ``_qkv_lora`` for the flattened (1, B + K*S, d) mixed batch.

    The adapter pair is gathered once per *request* — (B, d, r) for the
    decode rows and (K, d, r) for the chunks — not per token: all S rows
    of a chunk share one adapter, so a per-token gather would stream S
    duplicate copies of the same weights per projection per layer."""
    q, k, v = layers.attn_qkv(p_attn, cfg, x, positions)
    if lora is not None:
        d_model = x.shape[-1]

        def delta(which, heads):
            d_dec = _lora_delta(lora, which, x[0, :n_dec, None],
                                dec_adapter_ids)               # (B, 1, out)
            d_pre = _lora_delta(lora, which,
                                x[0, n_dec:].reshape(n_pre, s, d_model),
                                pre_adapter_ids)               # (K, S, out)
            return jnp.concatenate(
                [d_dec.reshape(n_dec, heads, cfg.head_dim),
                 d_pre.reshape(n_pre * s, heads, cfg.head_dim)])[None]
        dq = delta("q", cfg.n_heads)
        dv = delta("v", cfg.n_kv_heads)
        sin, cos = layers.rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
        q = q + layers.apply_rope(dq, sin, cos)
        v = v + dv
    return q, k, v


# ---------------------------------------------------------------- prefill
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "impl"),
    donate_argnums=(1,))
def prefill_step(params, pool: PagePool, tokens: jax.Array,
                 block_table: jax.Array, ctx_len: jax.Array,
                 chunk_len: jax.Array, lora=None,
                 adapter_ids: Optional[jax.Array] = None, *,
                 cfg: ModelConfig, page_size: int, impl: str = "pallas"
                 ) -> Tuple[jax.Array, PagePool]:
    """One (possibly chunked) prefill for ONE request.

    tokens:      (1, s) current chunk (padded; ``chunk_len`` valid)
    block_table: (1, NB) pages covering [0, ctx+s)
    ctx_len:     scalar — tokens already in the pages (prefix cache +
                 earlier chunks)
    Returns (last-token logits (1, V), updated pool).
    """
    s = tokens.shape[1]
    positions = ctx_len + jnp.arange(s)[None]                  # (1, s)
    x = M.embed(params, cfg, tokens)
    ltype = cfg.layer_runs[0][0]
    ctx1 = jnp.reshape(ctx_len, (1,)).astype(jnp.int32)
    chunk1 = jnp.reshape(chunk_len, (1,)).astype(jnp.int32)

    def body(x, xs):
        p_l, kp_l, vp_l = xs
        h = layers.rms_norm(x, p_l["ln1"], cfg.norm_eps)
        q, k, v = _qkv_lora(p_l["attn"], cfg, h, positions, lora,
                            adapter_ids)
        # scatter the chunk's K/V into this layer's pages
        tok_pos = (ctx_len + jnp.arange(s))                    # (s,)
        in_range = jnp.arange(s) < chunk_len
        pidx = jnp.where(in_range, block_table[0, tok_pos // page_size],
                         kp_l.shape[1])                        # OOB -> drop
        slot = tok_pos % page_size
        kp_l = _write_kv(kp_l, pidx, slot, k[0])
        vp_l = _write_kv(vp_l, pidx, slot, v[0])
        # chunk attends directly over the pages (ctx + chunk), no gather
        o = kops.paged_prefill(q, kp_l, vp_l, block_table, ctx1, chunk1,
                               impl=impl)
        a = layers.attn_out(p_l["attn"], o)
        x = x + a
        h2 = layers.rms_norm(x, p_l["ln2"], cfg.norm_eps)
        if ltype == MOE:
            f, _aux = moe.moe_ffn(p_l["moe"], cfg.moe, h2, cfg.act)
        else:
            f = layers.mlp(p_l["mlp"], h2, cfg.act)
        return x + f, (kp_l, vp_l)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["run_0"], pool.k,
                                               pool.v))
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    # last valid token's logits
    last = jnp.take(x, jnp.maximum(chunk_len - 1, 0), axis=1)[:, None]
    logits = M.unembed(params, cfg, last)[:, 0]
    return logits, PagePool(k_new, v_new)


# ---------------------------------------------------------------- decode
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "impl"),
    donate_argnums=(1,))
def decode_batch(params, pool: PagePool, tokens: jax.Array,
                 positions: jax.Array, block_tables: jax.Array,
                 active: jax.Array, lora=None,
                 adapter_ids: Optional[jax.Array] = None, *,
                 cfg: ModelConfig, page_size: int, impl: str = "pallas"
                 ) -> Tuple[jax.Array, PagePool]:
    """One decode step for a batch.

    tokens: (B,) int32; positions: (B,) next position (== current length);
    block_tables: (B, NB); active: (B,) bool (padding slots excluded).
    Returns (logits (B, V), updated pool).
    """
    b = tokens.shape[0]
    positions_ = positions[:, None]                            # (B, 1)
    x = M.embed(params, cfg, tokens[:, None])
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    bidx = jnp.arange(b)

    def body(x, xs):
        p_l, kp_l, vp_l = xs
        h = layers.rms_norm(x, p_l["ln1"], cfg.norm_eps)
        q, k, v = _qkv_lora(p_l["attn"], cfg, h, positions_, lora,
                            adapter_ids)
        pidx = jnp.where(active,
                         block_tables[bidx, positions // page_size],
                         kp_l.shape[1])                        # OOB -> drop
        slot = positions % page_size
        kp_l = _write_kv(kp_l, pidx, slot, k[:, 0])
        vp_l = _write_kv(vp_l, pidx, slot, v[:, 0])
        o = kops.paged_attention(q[:, 0], kp_l, vp_l, block_tables,
                                 lengths, impl=impl)
        a = layers.attn_out(p_l["attn"], o[:, None])
        x = x + a
        h2 = layers.rms_norm(x, p_l["ln2"], cfg.norm_eps)
        if cfg.layer_runs[0][0] == MOE:
            f, _aux = moe.moe_ffn(p_l["moe"], cfg.moe, h2, cfg.act)
        else:
            f = layers.mlp(p_l["mlp"], h2, cfg.act)
        return x + f, (kp_l, vp_l)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["run_0"], pool.k,
                                               pool.v))
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = M.unembed(params, cfg, x)[:, 0]
    return logits, PagePool(k_new, v_new)


# ---------------------------------------------------------------- mixed step
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "impl"),
    donate_argnums=(1,))
def mixed_step(params, pool: PagePool,
               dec_tokens: jax.Array, dec_positions: jax.Array,
               dec_block_tables: jax.Array, dec_active: jax.Array,
               pre_tokens: jax.Array, pre_block_tables: jax.Array,
               pre_ctx: jax.Array, pre_chunk: jax.Array,
               lora=None, dec_adapter_ids: Optional[jax.Array] = None,
               pre_adapter_ids: Optional[jax.Array] = None, *,
               cfg: ModelConfig, page_size: int, impl: str = "pallas"
               ) -> Tuple[jax.Array, jax.Array, PagePool]:
    """One fused continuous-batching step: B decode tokens + K prefill
    chunks in a single forward pass over one flattened token batch.

    dec_tokens:       (B,) int32; dec_positions: (B,) next position
    dec_block_tables: (B, NBd); dec_active: (B,) bool
    pre_tokens:       (K, S) chunk tokens (padded; ``pre_chunk`` valid)
    pre_block_tables: (K, NBp); pre_ctx/pre_chunk: (K,) int32
                      (pre_chunk == 0 marks an idle prefill slot)
    Returns (decode logits (B, V), prefill last-token logits (K, V),
    updated pool).  The token budget of the pass is B + K*S.
    """
    b = dec_tokens.shape[0]
    kk, s = pre_tokens.shape
    h_, hkv = cfg.n_heads, cfg.n_kv_heads
    hd = cfg.head_dim
    ltype = cfg.layer_runs[0][0]

    pre_positions = pre_ctx[:, None] + jnp.arange(s)[None]     # (K, S)
    tokens_flat = jnp.concatenate([dec_tokens, pre_tokens.reshape(-1)])
    positions_flat = jnp.concatenate(
        [dec_positions, pre_positions.reshape(-1)])            # (T,)
    x = M.embed(params, cfg, tokens_flat[None])                # (1, T, d)
    dec_lengths = jnp.where(dec_active, dec_positions + 1, 0).astype(
        jnp.int32)
    bidx = jnp.arange(b)
    kidx = jnp.arange(kk)
    in_range = jnp.arange(s)[None] < pre_chunk[:, None]        # (K, S)

    def body(x, xs):
        p_l, kp_l, vp_l = xs
        oob = kp_l.shape[1]
        h = layers.rms_norm(x, p_l["ln1"], cfg.norm_eps)
        q, k, v = _qkv_lora_mixed(p_l["attn"], cfg, h,
                                  positions_flat[None], lora,
                                  dec_adapter_ids, pre_adapter_ids,
                                  b, kk, s)
        # scatter all new K/V (decode tokens + prefill chunks) at once
        pidx_d = jnp.where(dec_active,
                           dec_block_tables[bidx,
                                            dec_positions // page_size],
                           oob)
        pidx_p = jnp.where(
            in_range,
            pre_block_tables[kidx[:, None], pre_positions // page_size],
            oob)
        pidx = jnp.concatenate([pidx_d, pidx_p.reshape(-1)])
        slot = jnp.concatenate([dec_positions % page_size,
                                (pre_positions % page_size).reshape(-1)])
        kp_l = _write_kv(kp_l, pidx, slot, k[0])
        vp_l = _write_kv(vp_l, pidx, slot, v[0])
        # attention forks: decode rows vs chunk rows, both over pages
        o_dec = kops.paged_attention(q[0, :b], kp_l, vp_l,
                                     dec_block_tables, dec_lengths,
                                     impl=impl)                # (B, H, D)
        o_pre = kops.paged_prefill(q[0, b:].reshape(kk, s, h_, hd),
                                   kp_l, vp_l, pre_block_tables,
                                   pre_ctx, pre_chunk, impl=impl)
        o = jnp.concatenate([o_dec, o_pre.reshape(kk * s, h_, hd)])[None]
        a = layers.attn_out(p_l["attn"], o)
        x = x + a
        h2 = layers.rms_norm(x, p_l["ln2"], cfg.norm_eps)
        if ltype == MOE:
            f, _aux = moe.moe_ffn(p_l["moe"], cfg.moe, h2, cfg.act)
        else:
            f = layers.mlp(p_l["mlp"], h2, cfg.act)
        return x + f, (kp_l, vp_l)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["run_0"], pool.k,
                                               pool.v))
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    # only unembed the rows that produce tokens: every decode row plus
    # each chunk's last valid row
    sel = jnp.concatenate(
        [bidx, b + kidx * s + jnp.maximum(pre_chunk - 1, 0)])
    logits = M.unembed(params, cfg, x[0, sel][None])[0]        # (B+K, V)
    return logits[:b], logits[b:], PagePool(k_new, v_new)


# ------------------------------------------------- speculative verification
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "impl"),
    donate_argnums=(1,))
def spec_decode_step(params, pool: PagePool, spec_tokens: jax.Array,
                     spec_ctx: jax.Array, spec_len: jax.Array,
                     spec_block_tables: jax.Array, lora=None,
                     adapter_ids: Optional[jax.Array] = None, *,
                     cfg: ModelConfig, page_size: int, impl: str = "pallas"
                     ) -> Tuple[jax.Array, PagePool]:
    """One speculative decode step: every decode row is a short
    multi-query chunk ``[last_token, draft_1..draft_d]`` verified in a
    single pass.

    spec_tokens: (B, SD) int32 — row i feeds its last sampled token at
                 position ``spec_ctx[i]`` followed by the drafter's
                 proposals (padded; ``spec_len`` valid, 0 = idle slot)
    spec_ctx:    (B,) tokens already in the pages (== the last sampled
                 token's position)
    Returns logits for EVERY chunk row, (B, SD, V): row j is the
    model's distribution after consuming drafts[:j], which is exactly
    what acceptance needs — unlike ``mixed_step``, which only unembeds
    each chunk's last row.  KV for all fed tokens (drafts included) is
    scattered into the pages; rejected drafts leave stale slots past
    the accepted length that attention masks out (lengths-bounded) and
    the next step's real tokens overwrite in place — rollback costs
    nothing.
    """
    b, sd = spec_tokens.shape
    positions = spec_ctx[:, None] + jnp.arange(sd)[None]       # (B, SD)
    positions_flat = positions.reshape(-1)
    x = M.embed(params, cfg, spec_tokens.reshape(-1)[None])    # (1, B*SD, d)
    bidx = jnp.arange(b)
    in_range = jnp.arange(sd)[None] < spec_len[:, None]        # (B, SD)
    ltype = cfg.layer_runs[0][0]

    def body(x, xs):
        p_l, kp_l, vp_l = xs
        oob = kp_l.shape[1]
        h = layers.rms_norm(x, p_l["ln1"], cfg.norm_eps)
        q, k, v = _qkv_lora_groups(p_l["attn"], cfg, h,
                                   positions_flat[None], lora,
                                   [(adapter_ids, b, sd)])
        pidx = jnp.where(in_range,
                         spec_block_tables[bidx[:, None],
                                           positions // page_size],
                         oob)                                  # OOB -> drop
        slot = positions % page_size
        kp_l = _write_kv(kp_l, pidx.reshape(-1), slot.reshape(-1), k[0])
        vp_l = _write_kv(vp_l, pidx.reshape(-1), slot.reshape(-1), v[0])
        o = kops.paged_verify(
            q[0].reshape(b, sd, cfg.n_heads, cfg.head_dim), kp_l, vp_l,
            spec_block_tables, spec_ctx, spec_len, impl=impl)
        a = layers.attn_out(p_l["attn"],
                            o.reshape(b * sd, cfg.n_heads,
                                      cfg.head_dim)[None])
        x = x + a
        h2 = layers.rms_norm(x, p_l["ln2"], cfg.norm_eps)
        if ltype == MOE:
            f, _aux = moe.moe_ffn(p_l["moe"], cfg.moe, h2, cfg.act)
        else:
            f = layers.mlp(p_l["mlp"], h2, cfg.act)
        return x + f, (kp_l, vp_l)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["run_0"], pool.k,
                                               pool.v))
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = M.unembed(params, cfg, x)[0]                      # (B*SD, V)
    return logits.reshape(b, sd, -1), PagePool(k_new, v_new)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "page_size", "impl"),
    donate_argnums=(1,))
def spec_mixed_step(params, pool: PagePool, spec_tokens: jax.Array,
                    spec_ctx: jax.Array, spec_len: jax.Array,
                    spec_block_tables: jax.Array,
                    pre_tokens: jax.Array, pre_block_tables: jax.Array,
                    pre_ctx: jax.Array, pre_chunk: jax.Array,
                    lora=None,
                    spec_adapter_ids: Optional[jax.Array] = None,
                    pre_adapter_ids: Optional[jax.Array] = None, *,
                    cfg: ModelConfig, page_size: int, impl: str = "pallas"
                    ) -> Tuple[jax.Array, jax.Array, PagePool]:
    """``spec_decode_step`` fused with prefill chunks: B speculative
    decode chunks + K prefill chunks flattened into ONE pass (the
    spec-enabled sibling of ``mixed_step``).  Both groups ride the
    paged-prefill kernel — speculative lanes are just short chunks at a
    dynamic context offset — and only the unembed differs: ALL spec
    rows produce logits (verification needs every draft position), one
    last-row logit per prefill chunk.  Returns
    (spec logits (B, SD, V), prefill last-token logits (K, V), pool).
    """
    b, sd = spec_tokens.shape
    kk, s = pre_tokens.shape
    h_, hd = cfg.n_heads, cfg.head_dim
    ltype = cfg.layer_runs[0][0]

    spec_positions = spec_ctx[:, None] + jnp.arange(sd)[None]  # (B, SD)
    pre_positions = pre_ctx[:, None] + jnp.arange(s)[None]     # (K, S)
    tokens_flat = jnp.concatenate([spec_tokens.reshape(-1),
                                   pre_tokens.reshape(-1)])
    positions_flat = jnp.concatenate([spec_positions.reshape(-1),
                                      pre_positions.reshape(-1)])
    x = M.embed(params, cfg, tokens_flat[None])                # (1, T, d)
    bidx = jnp.arange(b)
    kidx = jnp.arange(kk)
    in_spec = jnp.arange(sd)[None] < spec_len[:, None]         # (B, SD)
    in_pre = jnp.arange(s)[None] < pre_chunk[:, None]          # (K, S)

    def body(x, xs):
        p_l, kp_l, vp_l = xs
        oob = kp_l.shape[1]
        h = layers.rms_norm(x, p_l["ln1"], cfg.norm_eps)
        q, k, v = _qkv_lora_groups(p_l["attn"], cfg, h,
                                   positions_flat[None], lora,
                                   [(spec_adapter_ids, b, sd),
                                    (pre_adapter_ids, kk, s)])
        pidx_s = jnp.where(in_spec,
                           spec_block_tables[bidx[:, None],
                                             spec_positions // page_size],
                           oob)
        pidx_p = jnp.where(
            in_pre,
            pre_block_tables[kidx[:, None], pre_positions // page_size],
            oob)
        pidx = jnp.concatenate([pidx_s.reshape(-1), pidx_p.reshape(-1)])
        slot = jnp.concatenate(
            [(spec_positions % page_size).reshape(-1),
             (pre_positions % page_size).reshape(-1)])
        kp_l = _write_kv(kp_l, pidx, slot, k[0])
        vp_l = _write_kv(vp_l, pidx, slot, v[0])
        o_spec = kops.paged_verify(
            q[0, :b * sd].reshape(b, sd, h_, hd), kp_l, vp_l,
            spec_block_tables, spec_ctx, spec_len, impl=impl)
        o_pre = kops.paged_prefill(
            q[0, b * sd:].reshape(kk, s, h_, hd), kp_l, vp_l,
            pre_block_tables, pre_ctx, pre_chunk, impl=impl)
        o = jnp.concatenate([o_spec.reshape(b * sd, h_, hd),
                             o_pre.reshape(kk * s, h_, hd)])[None]
        a = layers.attn_out(p_l["attn"], o)
        x = x + a
        h2 = layers.rms_norm(x, p_l["ln2"], cfg.norm_eps)
        if ltype == MOE:
            f, _aux = moe.moe_ffn(p_l["moe"], cfg.moe, h2, cfg.act)
        else:
            f = layers.mlp(p_l["mlp"], h2, cfg.act)
        return x + f, (kp_l, vp_l)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["run_0"], pool.k,
                                               pool.v))
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    # every spec row + each prefill chunk's last valid row
    sel = jnp.concatenate(
        [jnp.arange(b * sd),
         b * sd + kidx * s + jnp.maximum(pre_chunk - 1, 0)])
    logits = M.unembed(params, cfg, x[0, sel][None])[0]        # (B*SD+K, V)
    return (logits[:b * sd].reshape(b, sd, -1), logits[b * sd:],
            PagePool(k_new, v_new))
