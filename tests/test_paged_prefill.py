"""Paged flash-prefill kernel parity + fused mixed-batch engine tests.

Kernel: interpret-mode Pallas vs the pure-jnp oracle across ragged
ctx/chunk lengths, GQA group ratios and page sizes.  Engine: the fused
token-budget scheduler must reproduce the legacy two-phase scheduler's
greedy outputs exactly, hold multiple requests in PREFILLING while
decoding, and respect the per-step token budget.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.engine import (EngineConfig, InferenceEngine, Request,
                          RequestState, SamplingParams)
from repro.kernels import ops
from repro.kernels import ref as kref

RNG = np.random.default_rng(7)


def _rand(shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


# ------------------------------------------------------------------ kernel
PAGED_PREFILL_CASES = [
    # (B, H, Hkv, D, page) — GQA ratios 1/2/4 x page sizes 8/16/64
    (2, 4, 4, 32, 8),       # MHA, small pages
    (1, 8, 4, 64, 16),      # GQA 2
    (2, 8, 2, 32, 16),      # GQA 4
    (1, 4, 4, 64, 64),      # MHA, big pages
    (2, 8, 2, 64, 64),      # GQA 4, big pages
    (1, 8, 4, 32, 8),       # GQA 2, small pages
]


@pytest.mark.parametrize("b,h,hkv,d,page", PAGED_PREFILL_CASES)
def test_paged_prefill_matches_ref(b, h, hkv, d, page):
    s = 24                              # ragged: not a block_q multiple
    cap = 192                           # tokens of paged capacity per seq
    nb = cap // page
    p = b * nb + 3
    kp = _rand((hkv, p, page, d))
    vp = _rand((hkv, p, page, d))
    bt = jnp.asarray(RNG.permutation(p)[:b * nb].reshape(b, nb), jnp.int32)
    ctx = jnp.asarray(RNG.integers(0, cap - s + 1, b), jnp.int32)
    chunk = jnp.asarray(RNG.integers(1, s + 1, b), jnp.int32)
    q = _rand((b, s, h, d))
    out = ops.paged_prefill(q, kp, vp, bt, ctx, chunk)
    refv = kref.paged_prefill_ref(q, kp, vp, bt, ctx, chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refv),
                               atol=3e-5, rtol=3e-5)


def test_paged_prefill_zero_ctx_equals_flash():
    """A first chunk (ctx=0) must agree with contiguous flash prefill."""
    b, s, h, hkv, d, page = 1, 32, 4, 2, 32, 8
    nb = s // page
    kp = _rand((hkv, nb + 1, page, d))
    vp = _rand((hkv, nb + 1, page, d))
    bt = jnp.arange(nb, dtype=jnp.int32)[None]
    q = _rand((b, s, h, d))
    ctx = jnp.zeros(b, jnp.int32)
    chunk = jnp.full(b, s, jnp.int32)
    out = ops.paged_prefill(q, kp, vp, bt, ctx, chunk)
    k = kref.gather_pages(kp, bt)
    v = kref.gather_pages(vp, bt)
    refv = kref.flash_prefill_ref(q, k, v, jnp.full(b, s, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(refv),
                               atol=3e-5, rtol=3e-5)


def test_paged_prefill_padding_rows_are_zero():
    b, s, h, hkv, d, page = 2, 16, 4, 2, 32, 8
    nb = 4
    kp = _rand((hkv, b * nb + 1, page, d))
    vp = _rand((hkv, b * nb + 1, page, d))
    bt = jnp.asarray(np.arange(b * nb).reshape(b, nb), jnp.int32)
    chunk = jnp.asarray([5, 12], jnp.int32)
    ctx = jnp.asarray([8, 0], jnp.int32)
    out = np.asarray(ops.paged_prefill(_rand((b, s, h, d)), kp, vp, bt,
                                       ctx, chunk))
    for i, c in enumerate([5, 12]):
        assert np.all(out[i, c:] == 0.0)
        assert np.any(out[i, :c] != 0.0)


# ------------------------------------------------------------------ engine
def _engine(seed=0, **kw):
    cfg = get_reduced_config("qwen3-0.6b")
    defaults = dict(page_size=8, num_pages=64, max_batch=4,
                    max_pages_per_seq=16, chunk_size=16)
    defaults.update(kw)
    return cfg, InferenceEngine(cfg, EngineConfig(**defaults), seed=seed)


def test_mixed_batch_matches_two_phase_greedy():
    """The fused mixed-batch scheduler must emit exactly the tokens the
    legacy one-prefill-at-a-time scheduler emits under greedy sampling."""
    cfg, _ = _engine()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (22, 35, 9, 28)]

    def run(mixed: bool):
        _, eng = _engine(mixed_batching=mixed, max_prefills=2)
        reqs = [Request(prompt_tokens=list(p),
                        sampling=SamplingParams(max_new_tokens=5))
                for p in prompts]
        for r in reqs:
            eng.submit(r)
        eng.run_until_idle()
        return [r.output_tokens for r in reqs]

    assert run(True) == run(False)


def test_concurrent_prefills_while_decoding():
    cfg, eng = _engine(max_prefills=2)
    rng = np.random.default_rng(12)
    warm = Request(prompt_tokens=rng.integers(0, cfg.vocab_size, 10).tolist(),
                   sampling=SamplingParams(max_new_tokens=30))
    eng.submit(warm)
    while warm.state != RequestState.RUNNING:
        eng.step()
    for _ in range(2):          # two long, distinct-prefix prompts
        eng.submit(Request(
            prompt_tokens=rng.integers(0, cfg.vocab_size, 40).tolist(),
            sampling=SamplingParams(max_new_tokens=3)))
    eng.step()
    assert len(eng.prefills) == 2
    assert all(r.state == RequestState.PREFILLING for r in eng.prefills)
    assert warm.state == RequestState.RUNNING
    decoded_before = len(warm.output_tokens)
    eng.step()                  # decode continues alongside both prefills
    assert len(warm.output_tokens) > decoded_before
    eng.run_until_idle()
    assert eng.metrics().finished_requests == 3


def test_token_budget_caps_prefill_progress():
    cfg, eng = _engine(max_prefills=2, max_batch=2, chunk_size=16,
                       token_budget=12)
    rng = np.random.default_rng(13)
    reqs = [Request(prompt_tokens=rng.integers(0, cfg.vocab_size,
                                               40).tolist(),
                    sampling=SamplingParams(max_new_tokens=2))
            for _ in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(30):
        before = [r.prefill_done_tokens for r in reqs]
        n_dec = len(eng.running[:eng.ecfg.max_batch])
        eng.step()
        progressed = sum(r.prefill_done_tokens - b
                         for r, b in zip(reqs, before))
        assert n_dec + progressed <= eng.ecfg.step_token_budget
        if not eng.has_work:
            break
    assert all(r.state == RequestState.FINISHED for r in reqs)


def test_single_prefill_config_reproduces_legacy():
    """max_prefills=1 + mixed batching off == the old engine behavior."""
    cfg, eng = _engine(mixed_batching=False, max_prefills=1)
    rng = np.random.default_rng(14)
    r1 = Request(prompt_tokens=rng.integers(0, cfg.vocab_size, 20).tolist(),
                 sampling=SamplingParams(max_new_tokens=4))
    r2 = Request(prompt_tokens=rng.integers(0, cfg.vocab_size, 20).tolist(),
                 sampling=SamplingParams(max_new_tokens=4))
    eng.submit(r1)
    eng.submit(r2)
    eng.step()
    # legacy scheduler: never more than ONE request in PREFILLING
    assert len(eng.prefills) <= 1
    eng.run_until_idle()
    assert r1.state == r2.state == RequestState.FINISHED


def test_prefix_sharing_deferred_until_pages_register():
    """Cache-aware admission: a request sharing its leading block with an
    in-flight prefill waits, then reuses the registered prefix pages."""
    cfg, eng = _engine(max_prefills=2)
    rng = np.random.default_rng(15)
    shared = rng.integers(0, cfg.vocab_size, 16).tolist()
    reqs = [Request(prompt_tokens=shared + [1000 + i],
                    sampling=SamplingParams(max_new_tokens=2))
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert len(eng.prefills) == 1       # second deferred, not co-admitted
    eng.run_until_idle()
    assert eng.metrics().prefix_hit_tokens >= 16


def test_deferred_head_does_not_block_distinct_prefix():
    """A deferred prefix-sharer must not head-of-line-block a waiter
    with a distinct prefix from taking the free prefill slot."""
    cfg, eng = _engine(max_prefills=2, chunk_size=8)
    rng = np.random.default_rng(16)
    shared = rng.integers(0, cfg.vocab_size, 16).tolist()
    first = Request(prompt_tokens=shared + [7],
                    sampling=SamplingParams(max_new_tokens=2))
    sharer = Request(prompt_tokens=shared + [8],
                     sampling=SamplingParams(max_new_tokens=2))
    distinct = Request(
        prompt_tokens=rng.integers(0, cfg.vocab_size, 20).tolist(),
        sampling=SamplingParams(max_new_tokens=2))
    for r in (first, sharer, distinct):
        eng.submit(r)
    eng.step()
    assert first.state == RequestState.PREFILLING
    assert sharer.state == RequestState.QUEUED      # deferred
    assert distinct.state == RequestState.PREFILLING  # skipped past sharer
    eng.run_until_idle()
    assert eng.metrics().finished_requests == 3
    assert eng.metrics().prefix_hit_tokens >= 16    # sharer reused prefix
