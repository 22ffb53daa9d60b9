#!/usr/bin/env python3
"""Smoke run of the serving path on TPU: gateway -> Scheduler ->
ModelRunner -> mixed_step -> Pallas kernels, at the published width of
Qwen3-0.6B in bf16 with random weights.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four one-chip replicas vs one

One chip: about 8 requests (513-1024-token prompts, half of them sharing
a 256-token prefix, 32 new tokens each) go through the prefix-cache-aware
``Gateway`` to one engine built by ``repro.launch.serve.build_engines``.
The run fails unless every request finishes, the prefix cache hit, no
KV page leaked, and one mixed step's logits from the Pallas kernels agree
with the pure-jnp reference (``impl="ref"``) on the same inputs.

``--chips 4``: only the replica path.  The same requests are served once
by a single replica and once by four replicas with identical weights,
each engine's params and pool on its own chip, behind the same gateway
policy.  Every chip must serve, every engine's arrays must sit on its
chip, and greedy outputs must equal the single replica's, request by
request.  In both runs each engine serves one request at a time: the
gateway routes a wave of as many requests as there are replicas, and the
wave drains before the next.  A token's bf16 logits depend on which
compiled step computed it (``decode_batch`` alone vs ``mixed_step``
beside another request's prefill), and random weights leave near-ties
that such rounding flips, so only equal schedules give equal outputs.

The script refuses to run unless JAX's first device is a TPU.  The
numbers it prints come from one smoke run and are not benchmark results.
Its last stdout line is ``{"ok": true, "device": {...}}``; any failed
check or exception exits non-zero before that line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

MODEL = "qwen3-0.6b"
REQUESTS = 8
MAX_NEW = 32            # new tokens per request
PREFIX_TOKENS = 256     # shared by the even-numbered prompts
KV_GB = 2.0             # KV page pool per engine: 1,169 pages of 16 tokens
# bf16 parity bound: max |pallas - ref| over a step's logits, relative to
# max |ref|.  Both paths read the same bf16 weights and pages and differ
# only in how attention rounds (Mosaic kernel vs XLA einsums).  The bound
# sits between the sound reading and that of a decode kernel which leaves
# out each sequence's last live page (tools/smoke_experiments.py
# parity-fault; readings in PERF.md).
PARITY_RTOL = 1.2e-2


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def require_tpu(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's devices are "
                 f"{devices[0].platform!r}; nothing was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devices)}")
    return devices


class CompileCounter:
    """Counts backend compiles and their seconds through JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def make_prompts(vocab: int, n: int, seed: int):
    """n prompts of 513-1024 tokens; the even ones share a prefix.  The
    prefix covers under half of any prompt, so the prefix-cache-aware
    gateway routes by load and spreads a wave over idle replicas."""
    import numpy as np
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, PREFIX_TOKENS).tolist()
    prompts = []
    for i in range(n):
        length = int(rng.integers(2 * PREFIX_TOKENS + 1, 1025))
        if i % 2 == 0:
            tail = rng.integers(0, vocab, length - PREFIX_TOKENS).tolist()
            prompts.append(prefix + tail)
        else:
            prompts.append(rng.integers(0, vocab, length).tolist())
    return prompts


def serve(cfg, params, n_replicas: int, ecfg_kw: dict, prompts, seed: int,
          wave: int):
    """Serve ``prompts`` greedily through the gateway on ``n_replicas``
    engines, routing ``wave`` requests at a time and draining each wave
    before the next; returns (engines, [(engine id, request)])."""
    from repro.core.gateway import Gateway
    from repro.engine import Request, SamplingParams
    from repro.launch.serve import build_engines

    t0 = time.monotonic()
    clock = lambda: time.monotonic() - t0      # noqa: E731
    gw = Gateway(policy="prefix-cache-aware", clock=clock)
    engines, manager, _ = build_engines(
        cfg, ["mixed"] * n_replicas, clock, ecfg_kw, gateway=gw,
        params=params, seed=seed)

    def pump():
        for eng in engines.values():
            if eng.has_work:
                eng.step()
        manager.poll(clock())

    routed = []
    for start in range(0, len(prompts), wave):
        for prompt in prompts[start:start + wave]:
            req = Request(prompt_tokens=list(prompt),
                          sampling=SamplingParams(max_new_tokens=MAX_NEW,
                                                  temperature=0.0),
                          arrival_time=clock())
            eid = gw.route(req.prompt_tokens, est_output_tokens=MAX_NEW)
            if eid is None:
                fail(f"gateway refused request {req.request_id}")
            engines[eid].submit(req)
            routed.append((eid, req))
            pump()
        while any(e.has_work for e in engines.values()):
            pump()
    return engines, routed


def check_served(engines, routed) -> None:
    from repro.engine import RequestState
    for eid, r in routed:
        if r.state != RequestState.FINISHED \
                or len(r.output_tokens) != MAX_NEW:
            fail(f"request {r.request_id} on {eid}: state={r.state.value}, "
                 f"{len(r.output_tokens)}/{MAX_NEW} tokens")
    for eid, eng in engines.items():
        if eng.alloc.num_free != eng.alloc.num_pages:
            fail(f"{eid} leaked KV pages: {eng.alloc.num_free} of "
                 f"{eng.alloc.num_pages} free after drain")


def parity(cfg, params, ecfg_kw: dict, seed: int) -> float:
    """Worst relative logits error of one mixed step, Pallas vs
    reference, over two steps that each start from identical inputs:
    step 1 prefills two chunks into empty pages, step 2 decodes one
    sequence while the other continues its prefill over the pages
    step 1 wrote."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine import paged_model as PM

    page, b = ecfg_kw["page_size"], ecfg_kw["max_batch"]
    s, kk, nb = ecfg_kw["chunk_size"], 2, 32
    num_pages = 2 * nb
    rng = np.random.default_rng(seed + 1)
    lora = PM.init_lora(cfg, 8, 8, jnp.bfloat16)
    # sequence A takes pages [0, nb), sequence B pages [nb, 2*nb); the
    # pad entries name the scratch page past the pool's last real page
    bt_a = np.arange(nb, dtype=np.int32)
    bt_b = np.arange(nb, 2 * nb, dtype=np.int32)
    toks_a = rng.integers(0, cfg.vocab_size, 2 * s).astype(np.int32)
    toks_b = rng.integers(0, cfg.vocab_size, s).astype(np.int32)
    len_b = 3 * s // 4 - 12                     # B's prompt: 180 tokens
    pad_bt = np.full((b, nb), num_pages, np.int32)

    def step(pool, impl, dec, pre):
        dtok, dpos, dbt, dact = dec
        ptok, pbt, pctx, pchunk = pre
        return PM.mixed_step(
            params, pool, jnp.asarray(dtok), jnp.asarray(dpos),
            jnp.asarray(dbt), jnp.asarray(dact), jnp.asarray(ptok),
            jnp.asarray(pbt), jnp.asarray(pctx), jnp.asarray(pchunk), lora,
            jnp.zeros(b, jnp.int32), jnp.zeros(kk, jnp.int32), cfg=cfg,
            page_size=page, impl=impl)

    no_dec = (np.zeros(b, np.int32), np.zeros(b, np.int32), pad_bt,
              np.zeros(b, bool))
    pre1 = (np.stack([toks_a[:s], np.where(np.arange(s) < len_b, toks_b, 0)]),
            np.stack([bt_a, bt_b]), np.zeros(kk, np.int32),
            np.array([s, len_b], np.int32))
    dec2_bt = pad_bt.copy()
    dec2_bt[0] = bt_b
    dec2 = (np.array([7] + [0] * (b - 1), np.int32),
            np.array([len_b] + [0] * (b - 1), np.int32), dec2_bt,
            np.arange(b) == 0)
    pre2 = (np.stack([toks_a[s:], np.zeros(s, np.int32)]),
            np.stack([bt_a, np.full(nb, num_pages, np.int32)]),
            np.array([s, 0], np.int32),
            np.array([3 * s // 4 + 8, 0], np.int32))

    def fresh():
        return PM.init_pool(cfg, num_pages + 1, page, jnp.bfloat16)

    worst = 0.0
    pool = fresh()
    pool_ref = jax.tree.map(jnp.copy, pool)
    _, pre_p, pool = step(pool, "pallas", no_dec, pre1)
    _, pre_r, _ = step(pool_ref, "ref", no_dec, pre1)
    pairs = [(pre_p, pre_r)]
    pool_ref = jax.tree.map(jnp.copy, pool)
    dec_p, pre_p, _ = step(pool, "pallas", dec2, pre2)
    dec_r, pre_r, _ = step(pool_ref, "ref", dec2, pre2)
    # step 2's live rows: decode row 0 (B) and prefill slot 0 (A); the
    # other rows are idle slots whose logits no request reads
    pairs += [(dec_p[:1], dec_r[:1]), (pre_p[:1], pre_r[:1])]
    for got, want in pairs:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if not (np.isfinite(got).all() and np.isfinite(want).all()):
            fail("non-finite logits in the parity step")
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        worst = max(worst, err)
    return worst


def arrays_on(engine, dev) -> bool:
    import jax
    r = engine.runner
    leaves = jax.tree.leaves((r.params, r.pool, r.lora))
    return all(leaf.devices() == {dev} for leaf in leaves)


def one_chip(args, devices, cfg, kw, params, prompts) -> None:
    t0 = time.perf_counter()
    engines, routed = serve(cfg, params, 1, kw, prompts, args.seed,
                            wave=len(prompts))
    wall = time.perf_counter() - t0
    for eid, r in routed:
        print(f"smoke: request {r.request_id} -> {eid}: prompt={r.prompt_len}"
              f" cached={r.cached_prefix_tokens} "
              f"tokens={len(r.output_tokens)} ttft_s={r.ttft:.3f}")
    check_served(engines, routed)
    eng = engines["engine-0"]
    hits = eng.metrics().prefix_hit_tokens
    print(f"smoke: served {len(routed)} requests in {wall:.1f}s wall "
          f"(compiles included), prefix_hit_tokens={hits}, "
          f"free pages {eng.alloc.num_free}/{eng.alloc.num_pages}")
    if hits <= 0:
        fail("no prefix-cache hit although half the prompts share a "
             f"{PREFIX_TOKENS}-token prefix")
    if not arrays_on(eng, devices[0]):
        fail("engine arrays are not on device 0")
    err = parity(cfg, eng.params, kw, args.seed)
    print(f"smoke: mixed-step logits pallas vs ref: max rel err {err:.3e} "
          f"(bound {PARITY_RTOL:.1e})")
    if not err <= PARITY_RTOL:
        fail(f"pallas/ref logits differ by {err:.3e} > {PARITY_RTOL:.1e}")


def four_chips(args, devices, cfg, kw, params, prompts) -> None:
    n = args.chips
    t0 = time.perf_counter()
    engines, routed = serve(cfg, params, 1, kw, prompts, args.seed,
                            wave=1)
    check_served(engines, routed)
    single = [list(r.output_tokens) for _, r in routed]
    print(f"smoke: single replica served {len(routed)} requests in "
          f"{time.perf_counter() - t0:.1f}s wall")
    del engines, routed
    gc.collect()
    t0 = time.perf_counter()
    engines, routed = serve(cfg, params, n, kw, prompts, args.seed,
                            wave=n)
    print(f"smoke: {n} replicas served {len(routed)} requests in "
          f"{time.perf_counter() - t0:.1f}s wall")
    check_served(engines, routed)
    for i, (eid, eng) in enumerate(sorted(engines.items())):
        served = eng.metrics().finished_requests
        on_dev = arrays_on(eng, devices[i])
        print(f"smoke: {eid} on {devices[i]}: served={served} "
              f"arrays_on_device={on_dev}")
        if served < 1:
            fail(f"{eid} on device {i} served no request")
        if not on_dev:
            fail(f"{eid}'s params/pool/LoRA are not all on device {i}")
    for (eid, r), want in zip(routed, single):
        if list(r.output_tokens) != want:
            fail(f"request {r.request_id} on {eid}: replica output differs "
                 "from the single-replica run")
    print(f"smoke: greedy outputs of all {len(routed)} requests equal the "
          "single-replica run")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-replica comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import engine_kw
    from repro.models import model as M

    cache = enable_compile_cache()
    compiles = CompileCounter()
    dev = devices[0]
    print(f"smoke: device {dev.device_kind} ({dev.platform}) x{len(devices)}"
          f", compile cache {cache}")
    cfg = get_config(MODEL)
    kw = engine_kw(cfg, KV_GB)
    print(f"smoke: model {cfg.name} {cfg.param_count() / 1e9:.3f}B params, "
          f"layers={cfg.n_layers} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"head_dim={cfg.head_dim} dtype={kw['dtype']} "
          f"pages={kw['num_pages']}x{kw['page_size']} per engine")
    params = M.init(cfg, jax.random.PRNGKey(args.seed), jnp.bfloat16)
    prompts = make_prompts(cfg.vocab_size, REQUESTS, args.seed)
    phase = one_chip if args.chips == 1 else four_chips
    phase(args, devices, cfg, kw, params, prompts)
    print(f"smoke: {compiles.n} backend compiles, "
          f"{compiles.seconds:.1f}s compiling")
    stats = dev.memory_stats() or {}
    print(f"smoke: device 0 peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))


if __name__ == "__main__":
    main()
