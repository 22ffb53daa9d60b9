"""Public jit'd wrappers for the Pallas kernels.

Handles shape padding (seq lens to block multiples, batch page tables),
platform dispatch (compiled on a TPU, interpret mode on the CPU so tests
execute the real kernel bodies, an error on any other backend), and an
``impl`` switch so every call site can be A/B'd against the pure-jnp
oracle (impl="ref").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_prefill import flash_prefill as _flash
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.paged_prefill import paged_prefill as _paged_pre


def _interpret() -> bool:
    """Pallas mode for the current backend: compiled Mosaic kernels on a
    TPU, the interpreter on the CPU.  Any other backend is refused rather
    than silently interpreted."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"backend {backend!r} is neither (use impl='ref')")


def paged_attention(q, k_pages, v_pages, block_tables, lengths,
                    *, impl: str = "pallas"):
    """Decode attention over paged KV.  See kernels/ref.py for shapes."""
    if impl == "ref":
        return ref.paged_attention_ref(q, k_pages, v_pages, block_tables,
                                       lengths)
    return _paged(q, k_pages, v_pages, block_tables, lengths,
                  interpret=_interpret())


def paged_prefill(q, k_pages, v_pages, block_tables, ctx_lens, chunk_lens,
                  *, block_q: int = 128, impl: str = "pallas"):
    """Chunked-prefill attention directly over paged KV (chunk K/V must
    already be scattered into the pages).  Pads the chunk dim to a
    block_q multiple; see kernels/ref.py for shapes."""
    if impl == "ref":
        return ref.paged_prefill_ref(q, k_pages, v_pages, block_tables,
                                     ctx_lens, chunk_lens)
    b, s, h, d = q.shape
    bq = min(block_q, _round_up(s, 8))
    s_p = _round_up(s, bq)
    if s_p != s:
        q = jnp.pad(q, ((0, 0), (0, s_p - s), (0, 0), (0, 0)))
    out = _paged_pre(q, k_pages, v_pages, block_tables, ctx_lens,
                     chunk_lens, block_q=bq, interpret=_interpret())
    return out[:, :s]


def paged_verify(q, k_pages, v_pages, block_tables, ctx_lens, draft_lens,
                 *, impl: str = "pallas"):
    """Speculative-verification attention: each decode row is a short
    multi-query chunk ``[last_token, draft_1..draft_d]`` at a dynamic
    context offset — exactly the paged-prefill shape, so the lanes ride
    :func:`paged_prefill` with ``block_q`` sized for the small draft
    window (one q-block instead of a 128-wide tile mostly full of
    padding).  ``draft_lens`` counts valid rows per lane (1 + accepted
    drafts to verify; 0 marks an idle decode slot)."""
    sd = q.shape[1]
    return paged_prefill(q, k_pages, v_pages, block_tables, ctx_lens,
                         draft_lens, block_q=min(_round_up(sd, 8), 32),
                         impl=impl)


def flash_attention(q, k, v, lengths, *, window: int = 0, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    impl: str = "pallas"):
    """Causal/windowed prefill attention with automatic seq padding.
    q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D) — transposed here to the
    kernel's head-major (B, Hkv, Sk, D)."""
    if impl == "ref":
        return ref.flash_prefill_ref(q, k, v, lengths, window=window,
                                     q_offset=q_offset)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, _round_up(sq, 8))
    bk = min(block_k, _round_up(sk, 8))
    sq_p = _round_up(sq, bq)
    sk_p = _round_up(sk, bk)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    out = _flash(q, k, v, lengths, window=window, q_offset=q_offset,
                 block_q=bq, block_k=bk, interpret=_interpret())
    return out[:, :sq]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
