#!/usr/bin/env python3
"""Experiments behind three of ``chip_smoke.py``'s choices, run on one TPU
chip at the smoke's model, weights and requests (Qwen3-0.6B, bf16,
random weights from seed 0, 8 prompts, 32 greedy tokens each).

    python tools/smoke_experiments.py schedule       # why --chips 4 serves in waves
    python tools/smoke_experiments.py bt-width       # block tables: multiples of 4 vs powers of two
    python tools/smoke_experiments.py parity-fault   # what the parity bound separates

``schedule``: one replica serves the requests one at a time, then all at
once, and the script counts the requests whose greedy outputs differ.

``bt-width``: one replica serves all requests at once, cold and then
warm, once with ``ModelRunner._bt_width`` as it is (block-table widths
rounded up to multiples of 4) and once with powers of two (at least 4).
It prints the backend compiles, their seconds, the serve's wall time,
the distinct block-table widths and the share of kernel page-grid steps
that padding adds.

``parity-fault``: the smoke's pallas-vs-ref parity reading, then the same
reading with a decode kernel that leaves out each sequence's last live
page.

The persistent compile cache is off, so compile counts start cold.  The
numbers are one run each, on the host clock, and are not benchmark
results.
"""
from __future__ import annotations

import argparse
import gc
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

import chip_smoke as C  # noqa: E402


def _pow2(self, pages_needed: int) -> int:
    cap = max(4, 1 << (max(pages_needed, 1) - 1).bit_length())
    return min(cap, self.ecfg.max_pages_per_seq)


def _serve(cfg, params, kw, prompts, wave: int):
    """One replica serves ``prompts`` through the smoke's gateway; returns
    (greedy outputs, wall seconds) and frees the engine's pool."""
    t0 = time.perf_counter()
    engines, routed = C.serve(cfg, params, 1, kw, prompts, 0, wave=wave)
    wall = time.perf_counter() - t0
    C.check_served(engines, routed)
    outs = [list(r.output_tokens) for _, r in routed]
    del engines, routed
    gc.collect()
    return outs, wall


def schedule(cfg, params, kw, prompts) -> None:
    runs = {}
    for name, wave in (("one at a time", 1), ("all at once", len(prompts))):
        runs[name], wall = _serve(cfg, params, kw, prompts, wave)
        print(f"schedule: {name}: served in {wall:.1f}s wall")
    diffs = []
    for i, (a, b) in enumerate(zip(*runs.values())):
        first = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                     None)
        if first is not None:
            diffs.append((i, first))
    print(f"schedule: {len(diffs)} of {len(prompts)} requests differ "
          f"(request, first differing token): {diffs}")


def bt_width(cfg, params, kw, prompts) -> None:
    import jax
    from repro.engine.runner import ModelRunner

    mult4 = ModelRunner._bt_width
    rules = {"multiples of 4": mult4, "powers of two": _pow2}
    counter = C.CompileCounter()
    try:
        for name, rule in rules.items():
            seen, grid = set(), [0, 0]

            def recorded(self, pages_needed, rule=rule):
                width = rule(self, pages_needed)
                seen.add(width)
                grid[0] += max(pages_needed, 1)
                grid[1] += width
                return width

            ModelRunner._bt_width = recorded
            jax.clear_caches()
            for state in ("cold", "warm"):
                n0, s0 = counter.n, counter.seconds
                _, wall = _serve(cfg, params, kw, prompts, len(prompts))
                print(f"bt-width: {name}, {state}: {counter.n - n0} backend "
                      f"compiles, {counter.seconds - s0:.1f}s compiling, "
                      f"{wall:.1f}s wall")
            print(f"bt-width: {name}: widths {sorted(seen)}; summed over "
                  f"steps, {grid[1]} block-table columns for {grid[0]} "
                  f"pages needed (+{grid[1] / grid[0] - 1:.1%} page-grid "
                  "steps)")
    finally:
        ModelRunner._bt_width = mult4


def parity_fault(cfg, params, kw, prompts) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    sound = C.parity(cfg, params, kw, 0)
    kernel = ops._paged

    def last_page_dropped(q, k, v, bt, lengths, **kw_):
        page = k.shape[2]
        cut = jnp.maximum((lengths - 1) // page * page, 0)
        return kernel(q, k, v, bt, jnp.minimum(lengths, cut), **kw_)

    ops._paged = last_page_dropped
    jax.clear_caches()
    try:
        fault = C.parity(cfg, params, kw, 0)
    finally:
        ops._paged = kernel
    print(f"parity-fault: max rel err sound {sound:.3e}, decode kernel "
          f"without the last live page {fault:.3e} "
          f"(chip_smoke bound {C.PARITY_RTOL:.1e})")


EXPERIMENTS = {"schedule": schedule, "bt-width": bt_width,
               "parity-fault": parity_fault}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("experiments", nargs="+", choices=sorted(EXPERIMENTS))
    args = ap.parse_args()

    devices = C.require_tpu(1)
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.serve import engine_kw
    from repro.models import model as M

    jax.config.update("jax_enable_compilation_cache", False)
    print(f"experiments: device {devices[0].device_kind}, one run each, "
          "not benchmark numbers")
    cfg = get_config(C.MODEL)
    kw = engine_kw(cfg, C.KV_GB)
    params = M.init(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    prompts = C.make_prompts(cfg.vocab_size, C.REQUESTS, 0)
    for name in args.experiments:
        EXPERIMENTS[name](cfg, params, kw, prompts)


if __name__ == "__main__":
    main()
