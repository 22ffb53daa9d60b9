"""High-density LoRA management + serving (paper §3.2.1, Figure 2).

Three sections:

1. **Planner** — long-tail adapter fleet: N adapters with zipf demand.
   Compare (a) dedicated-pod-per-adapter (the rigid baseline the paper
   calls out) vs (b) AIBrix high-density placement (many adapters per
   pod, replicas by heat) — pods needed, cost, coverage.
2. **End-to-end serving** — the same zipf trace driven through the full
   ``ServingCluster`` stack (gateway -> LoRA-aware routing -> adapter
   tiering on the engines, demand-driven replanning) under
   ``lora-affinity`` vs an adapter-blind baseline at EQUAL engine
   count: affinity hit rate, cold-load stalls, $/attained-SLO.
3. **Real engine** — a small real-JAX fleet behind the same gateway +
   controller: affinity hit rate and cold loads on actual devices.
"""
from __future__ import annotations

import numpy as np

from repro.core.lora.manager import AdapterSpec, LoRAController
from repro.core.optimizer.profiles import DEVICES

SLO_TTFT_S = 0.5          # attained = TTFT within this bound


# ------------------------------------------------------------ 1. planner
def planner_section(quick: bool = False):
    n_adapters = 12 if quick else 32
    pods = 4 if quick else 8
    slots_per_pod = 8
    rng = np.random.default_rng(0)
    heat = 1.0 / (np.arange(n_adapters) + 1.0)       # zipf demand
    heat = heat / heat.sum() * 20.0                  # total 20 rps

    ctrl = LoRAController(min_replicas=1, max_replicas=3)
    ctrl.register(AdapterSpec("base-sft", "llama-7b", rank=16))
    for i in range(n_adapters):
        ctrl.register(AdapterSpec(
            f"adapter-{i}", "llama-7b", rank=8,
            parent="base-sft" if i % 4 == 0 else None,
            requests_per_s=float(heat[i])))
    for p in range(pods):
        ctrl.add_pod(f"pod-{p}", capacity=slots_per_pod)
    actions = ctrl.sync({})
    plan = {p: sorted(s.loaded) for p, s in ctrl.pods.items()}

    placed = sum(len(v) for v in plan.values())
    covered = len({a for v in plan.values() for a in v})
    # dedicated baseline: one pod per adapter (+1 for base)
    dedicated_pods = n_adapters + 1
    density_pods = pods
    cost = DEVICES["a10"].cost_per_hour
    print("scheme,pods,adapters_covered,cost_per_hour")
    print(f"dedicated-pod-per-adapter,{dedicated_pods},{n_adapters + 1}"
          f",{dedicated_pods*cost:.2f}")
    print(f"aibrix-high-density,{density_pods},{covered}"
          f",{density_pods*cost:.2f}")
    hot_replicas = len(ctrl.endpoints("adapter-0"))
    cold_replicas = len(ctrl.endpoints(f"adapter-{n_adapters-1}"))
    print(f"derived,cost_reduction_pct="
          f"{100*(1-density_pods/dedicated_pods):.1f}"
          f",hot_adapter_replicas={hot_replicas}"
          f",cold_adapter_replicas={cold_replicas}"
          f",loads={ctrl.stats['loads']}")
    assert covered == n_adapters + 1, "density placement must cover all"
    return plan


# ------------------------------------------------- 2. end-to-end serving
def _run_serving(policy: str, n_adapters: int, engines: int,
                 rate_rps: float, duration_s: float,
                 max_adapters: int = 9, seed: int = 1) -> dict:
    from repro.configs import get_config
    from repro.core.gateway.gateway import RateLimit
    from repro.core.sim import (ClusterConfig, ServingCluster,
                                SimEngineConfig)
    from repro.core.sim.workloads import lora_zipf

    cfg = get_config("deepseek-coder-7b")
    # fresh workload per run: the sim mutates Request state in place
    wl = lora_zipf(n_adapters=n_adapters, rate_rps=rate_rps,
                   duration_s=duration_s, seed=seed)
    ccfg = ClusterConfig(
        routing_policy=policy, device_type="a10", num_engines=engines,
        lora_adapters=n_adapters,
        rate_limit=RateLimit(rpm=10**9, tpm=10**12),
        engine=SimEngineConfig(device_type="a10", max_batch=16,
                               chunk_size=512,
                               max_adapters=max_adapters))
    s = ServingCluster(cfg, ccfg).run(wl)
    done = [tr.request for tr in wl if tr.request.finish_time > 0]
    attained = sum(1 for r in done if r.ttft <= SLO_TTFT_S)
    span_h = s["completion_time_s"] / 3600.0
    dollars = engines * DEVICES["a10"].cost_per_hour * span_h
    s["slo_attained"] = attained
    s["cost_per_1k_slo"] = 1000.0 * dollars / max(attained, 1)
    return s


def serving_section(quick: bool = False):
    n_adapters = 120 if quick else 1000
    engines = 4 if quick else 8
    rate = 12.0 if quick else 40.0
    duration = 30.0 if quick else 60.0
    cols = ("lora_affinity_hit_rate", "lora_cold_loads",
            "lora_cold_load_s", "lora_miss", "lora_shed",
            "ttft_avg_ms", "latency_avg_s", "slo_attained",
            "cost_per_1k_slo")
    print(f"\nserving: {n_adapters} adapters zipf, {engines} engines, "
          f"{rate:.0f} rps x {duration:.0f}s")
    print("policy," + ",".join(cols))
    rows = {}
    for policy in ("least-request", "lora-affinity"):
        s = _run_serving(policy, n_adapters, engines, rate, duration)
        rows[policy] = s
        print(policy + "," + ",".join(
            f"{s.get(c, 0):.3f}" if isinstance(s.get(c, 0), float)
            else str(s.get(c, 0)) for c in cols))
    aff, blind = rows["lora-affinity"], rows["least-request"]
    print(f"derived,affinity_hit_gain="
          f"{aff['lora_affinity_hit_rate'] - blind['lora_affinity_hit_rate']:.3f}"
          f",cold_load_reduction_pct="
          f"{100*(1 - aff['lora_cold_loads']/max(blind['lora_cold_loads'],1)):.1f}"
          f",cost_per_1k_slo_delta="
          f"{aff['cost_per_1k_slo'] - blind['cost_per_1k_slo']:+.4f}")
    assert aff["lora_affinity_hit_rate"] >= \
        blind["lora_affinity_hit_rate"], \
        "lora-affinity must beat adapter-blind routing on hit rate"
    assert aff["lora_cold_load_s"] <= blind["lora_cold_load_s"], \
        "lora-affinity must not stall more on cold loads"
    return rows


# ---------------------------------------------------- 3. real-JAX fleet
def real_engine_section(quick: bool = False):
    from repro.configs import get_reduced_config
    from repro.core.gateway.gateway import Gateway
    from repro.engine.engine import EngineConfig, InferenceEngine
    from repro.engine.request import Request, SamplingParams

    cfg = get_reduced_config("qwen3-0.6b")
    ecfg = EngineConfig(page_size=8, num_pages=64, max_batch=4,
                        max_pages_per_seq=16, chunk_size=16,
                        max_adapters=5)
    fleet = {f"engine-{i}": InferenceEngine(cfg, ecfg, seed=i)
             for i in range(2)}
    ctrl = LoRAController(min_replicas=1, max_replicas=2)
    n_adapters = 4 if quick else 6
    for i in range(n_adapters):
        ctrl.register(AdapterSpec(f"lora-{i}", cfg.name,
                                  requests_per_s=1.0 / (i + 1)))
    for eid in fleet:
        ctrl.add_pod(eid, capacity=ecfg.max_adapters - 1)
    gw = Gateway(policy="lora-affinity")
    for eid, eng in fleet.items():
        gw.register_engine(eid, eng)
    gw.attach_lora_controller(ctrl)
    ctrl.sync(fleet)

    rng = np.random.default_rng(0)
    heat = 1.0 / (np.arange(1, n_adapters + 1) ** 1.1)
    heat /= heat.sum()
    n_req = 8 if quick else 16
    reqs = []
    for _ in range(n_req):
        a = int(rng.choice(n_adapters, p=heat))
        r = Request(prompt_tokens=rng.integers(
                        0, cfg.vocab_size, 12).tolist(),
                    sampling=SamplingParams(max_new_tokens=4),
                    lora_adapter=f"lora-{a}")
        eid = gw.route(r.prompt_tokens, lora_adapter=r.lora_adapter)
        fleet[eid].submit(r)
        reqs.append(r)
    for eng in fleet.values():
        eng.run_until_idle()
    cold = sum(e.runner.adapter_loads for e in fleet.values())
    stall = sum(e.runner.adapter_load_s for e in fleet.values())
    wait = sum(e.metrics().device_wait_s for e in fleet.values())
    finished = sum(1 for r in reqs if r.output_tokens)
    print(f"\nreal-jax,engines=2,adapters={n_adapters},requests={n_req}"
          f",finished={finished}"
          f",affinity_hit_rate={gw.stats.lora_affinity_hit_rate:.3f}"
          f",cold_loads={cold},cold_load_s={stall:.3f}"
          f",device_wait_s={wait:.3f}")
    assert finished == n_req, "every routed request must finish"
    # the controller pre-placed the fleet, so routed requests land on a
    # resident pod far more often than the 1/2 an adapter-blind split
    # would give — and cold loads stay bounded by placement, not traffic
    assert gw.stats.lora_affinity_hit_rate >= 0.5
    assert cold <= n_adapters + ctrl.stats["loads"]
    return gw.stats


def main(quick: bool = False):
    plan = planner_section(quick)
    serving_section(quick)
    real_engine_section(quick)
    return plan


if __name__ == "__main__":
    import sys
    main(quick="--quick" in sys.argv)
