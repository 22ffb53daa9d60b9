"""Benchmark driver: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick]``

Order mirrors the paper: Table 1 (distributed KV cache), routing
(§3.2.2), autoscaling (§3.2.4), heterogeneous serving (§3.2.7/Fig 7-8),
cold start (§3.2.3), LoRA density (§3.2.1), kernel microbench, and the
roofline table from the dry-run artifacts.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (bench_autoscaling, bench_chaos, bench_coldstart,
                        bench_hetero, bench_kernels, bench_kv_tiers,
                        bench_kvcache, bench_lora, bench_pd_disagg,
                        bench_pd_pools, bench_routing, bench_sessions,
                        bench_slo, bench_speculative, roofline)
from repro.core.gateway.gateway import Gateway
from repro.core.sim.events import EventLoop
from repro.engine.scheduler import Scheduler
from repro.launch.compile_cache import enable_compile_cache

SUITES = [
    ("table1_distributed_kvcache", bench_kvcache.main),
    ("routing_strategies", bench_routing.main),
    ("llm_autoscaling", bench_autoscaling.main),
    ("heterogeneous_slo_serving", bench_hetero.main),
    ("coldstart_streaming_loader", bench_coldstart.main),
    ("high_density_lora", bench_lora.main),
    ("pd_disaggregation_via_pool", bench_pd_disagg.main),
    ("pd_role_pools_rebalancing", bench_pd_pools.main),
    ("kv_tiers_swap_and_streaming", bench_kv_tiers.main),
    ("million_session_serving", bench_sessions.main),
    ("slo_aware_scheduling", bench_slo.main),
    ("chaos_and_crash_recovery", bench_chaos.main),
    ("pallas_kernels", bench_kernels.main),
    ("speculative_decoding", bench_speculative.main),
    ("roofline_from_dryrun", lambda quick=False: roofline.main("", quick)),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI mode)")
    ap.add_argument("--only", default="",
                    help="substring filter on suite name")
    args = ap.parse_args()
    enable_compile_cache()
    failures = []
    for name, fn in SUITES:
        if args.only and args.only not in name:
            continue
        print(f"\n===== {name} " + "=" * max(8, 60 - len(name)))
        t0 = time.time()
        shed0 = Gateway.total_shed
        ev0 = EventLoop.total_events
        lr0, lh0 = Gateway.total_lora_routed, Gateway.total_lora_hits
        lm0 = Scheduler.total_lora_miss
        try:
            fn(quick=args.quick)
            # loud load shedding: a suite whose gateway rate limiter
            # silently dropped requests must say so next to its results
            # (it served LESS than the offered load it reports against)
            shed = Gateway.total_shed - shed0
            note = f" [gateway shed {shed} request(s)!]" if shed else ""
            # multi-LoRA accounting: affinity hit rate of this suite's
            # LoRA-tagged routes + scheduler-level adapter misses (a
            # request that reached an engine without its adapter)
            lr = Gateway.total_lora_routed - lr0
            lm = Scheduler.total_lora_miss - lm0
            if lr > 0:
                lh = Gateway.total_lora_hits - lh0
                note += f" [lora affinity {lh}/{lr}, miss {lm}]"
            # event-core throughput: fired sim events per wall-second
            # of the whole suite (0 events for real-engine-only suites)
            ev = EventLoop.total_events - ev0
            wall = max(time.time() - t0, 1e-9)
            if ev > 0:
                note += f" [{ev} sim events, {ev / wall:,.0f}/wall-s]"
            print(f"----- {name} done in {wall:.1f}s{note}")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"\nFAILED suites: {failures}")
        sys.exit(1)
    print("\nall benchmark suites completed")


if __name__ == "__main__":
    main()
