"""Control-plane components: LoRA controller, GPU optimizer,
orchestration (incl. rolling upgrade), diagnostics, runtime sidecar."""
import pytest

from repro.configs import get_config
from repro.core.diagnostics.tools import (DiagnosticMonitor, FailureInjector,
                                          FaultKind, Telemetry)
from repro.core.lora.manager import AdapterSpec, LoRAController
from repro.core.optimizer import (GPUOptimizer, ProfileTable, WorkloadBucket,
                                  homogeneous_cost)
from repro.core.optimizer.gpu_optimizer import DemandBucket, LoadMonitor
from repro.core.orchestration.cluster import (ClusterManager, EngineGroup,
                                              GroupSpec, PodState)
from repro.core.runtime.sidecar import (AIRuntime, ColdStartManager,
                                        ModelArtifact, load_time_s)
from repro.core.sim.events import EventLoop


# ----------------------------------------------------------------- LoRA
def test_lora_registry_lineage():
    c = LoRAController()
    c.register(AdapterSpec("base", "llama"))
    c.register(AdapterSpec("child", "llama", parent="base"))
    assert c.lineage("child") == ["child", "base"]
    with pytest.raises(ValueError):
        c.deregister("base")              # has dependents
    with pytest.raises(KeyError):
        c.register(AdapterSpec("orphan", "llama", parent="missing"))


def test_lora_density_placement_covers_all_and_replicates_hot():
    c = LoRAController(min_replicas=1, max_replicas=3)
    for i in range(10):
        c.register(AdapterSpec(f"a{i}", "m", requests_per_s=10.0 / (i + 1)))
    for p in range(4):
        c.add_pod(f"pod-{p}", capacity=6)
    c.sync({})
    covered = {a for pod in c.pods.values() for a in pod.loaded}
    assert covered == {f"a{i}" for i in range(10)}
    assert len(c.endpoints("a0")) >= len(c.endpoints("a9"))
    for pod in c.pods.values():
        assert len(pod.loaded) <= 6


# ------------------------------------------------------------ optimizer
def test_gpu_optimizer_beats_or_matches_homogeneous():
    cfg = get_config("deepseek-coder-7b")
    table = ProfileTable(cfg, slo_ttft_s=5.0, slo_itl_s=0.25)
    demand = [DemandBucket(WorkloadBucket(150, 50), 20.0),
              DemandBucket(WorkloadBucket(2000, 300), 3.0)]
    alloc = GPUOptimizer(table, ("a10", "l20", "v100")).optimize(demand)
    assert alloc.feasible and sum(alloc.counts.values()) > 0
    _, cost_hom = homogeneous_cost(table, demand, "l20")
    assert alloc.cost_per_hour <= cost_hom * 1.001


def test_gpu_optimizer_respects_availability():
    cfg = get_config("deepseek-coder-7b")
    table = ProfileTable(cfg)
    demand = [DemandBucket(WorkloadBucket(150, 50), 50.0)]
    alloc = GPUOptimizer(table, ("a10",),
                         availability={"a10": 2}).optimize(demand)
    assert alloc.counts["a10"] <= 2


def test_load_monitor_buckets_gateway_logs():
    logs = [(float(i), 100, 50, "u", "e") for i in range(10)] + \
           [(float(i), 3000, 200, "u", "e") for i in range(5)]
    demand = LoadMonitor().demand(logs, window_s=100.0, now=10.0)
    assert len(demand) == 2
    assert sum(d.rps for d in demand) == pytest.approx(15 / 100.0)


# -------------------------------------------------------- orchestration
def _cluster(loop):
    cold = ColdStartManager()
    cold.register_artifact(ModelArtifact(
        "m7b", 14e9, tier_by_node={"node-0": "dram"}))
    cm = ClusterManager(cold, clock=loop.clock)
    for i in range(6):
        cm.add_node(f"node-{i}", "a10", 8)
    return cm


def test_pod_lifecycle_and_cold_start_aware_placement():
    loop = EventLoop()
    cm = _cluster(loop)
    pod = cm.create_pod("m7b", "a10")
    assert pod.node == "node-0"           # dram-cached artifact node
    assert pod.state == PodState.PULLING
    loop.after(pod.ready_at + 1.0, lambda: None)
    loop.run()
    ready = cm.tick()
    assert [p.pod_id for p in ready] == [pod.pod_id]
    assert pod.state == PodState.READY
    cm.delete_pod(pod.pod_id)
    assert cm.nodes["node-0"].used_devices == 0


def test_reconcile_scales_up_and_down():
    loop = EventLoop()
    cm = _cluster(loop)
    cm.reconcile("m7b", "a10", desired=3)
    assert len(cm.pods) == 3
    cm.reconcile("m7b", "a10", desired=1)
    alive = [p for p in cm.pods.values()
             if p.state not in (PodState.TERMINATING,)]
    assert len(alive) == 1


def test_rolling_upgrade_keeps_availability():
    loop = EventLoop()
    cm = _cluster(loop)
    grp = EngineGroup(GroupSpec("ds", "m7b", "a10", group_size=2,
                                replicas=2), cm, max_unavailable=1)
    grp.scale_to(2)

    def tick_until(pred):
        for _ in range(500):
            if pred():
                return
            loop.clock.now += 5.0
            cm.tick()
        raise AssertionError("tick_until never satisfied")

    tick_until(lambda: len(grp.ready_replicas()) == 2)
    log = grp.rolling_upgrade("v2", tick_until)
    assert all("upgraded" in line for line in log)
    versions = {cm.pods[p].version for pods in grp.replica_pods.values()
                for p in pods}
    assert versions == {"v2"}


# ---------------------------------------------------------- diagnostics
def test_injector_and_monitor_detect_each_fault():
    """Hard faults act on one sample; soft faults must persist for
    confirm_n consecutive scrapes and then quarantine (hysteresis)."""
    inj = FailureInjector()
    mon = DiagnosticMonitor(confirm_n=3)
    cases = [
        (FaultKind.DEVICE_LOST, "restart", 1),    # hard: immediate
        (FaultKind.ECC_ERROR, "cordon", 1),       # hard: immediate
        (FaultKind.THERMAL_THROTTLE, "quarantine", 3),  # soft: confirmed
    ]
    for i, (kind, action, samples) in enumerate(cases):
        pid = f"p{i}"
        inj.active.clear()
        inj.inject(pid, kind, now=0.0, severity=1.0)
        diags = []
        for t in range(1, samples + 1):
            s = inj.perturb(Telemetry(pod_id=pid, t=float(t),
                                      tokens_per_sec=100.0))
            diags += mon.observe(s)
        assert any(d.fault == kind and d.action == action
                   for d in diags), (kind, diags)


def test_silent_degradation_needs_history():
    inj = FailureInjector()
    mon = DiagnosticMonitor()
    for t in range(10):                  # healthy baseline
        mon.observe(Telemetry("p1", float(t), tokens_per_sec=100.0))
    inj.inject("p1", FaultKind.SILENT_DEGRADATION, 10.0, severity=0.9)
    found = []
    for t in range(10, 25):
        s = inj.perturb(Telemetry("p1", float(t), tokens_per_sec=100.0))
        found += mon.observe(s)
    assert any(d.fault == FaultKind.SILENT_DEGRADATION for d in found)


# -------------------------------------------------------------- runtime
def test_streaming_loader_beats_sequential():
    for tier in ("remote", "local", "dram"):
        assert load_time_s(14e9, tier, True) < load_time_s(14e9, tier, False)


def test_sidecar_exports_step_counters():
    """The engine's step count and per-phase host seconds reach the
    sidecar's scrape with the tracer off."""
    from repro.configs import get_reduced_config
    from repro.engine import (EngineConfig, InferenceEngine, Request,
                              SamplingParams)
    from repro.engine.tracing import PHASES
    eng = InferenceEngine(get_reduced_config("qwen3-0.6b"), EngineConfig(
        page_size=8, num_pages=32, max_batch=2, max_pages_per_seq=8,
        chunk_size=16))
    eng.submit(Request(prompt_tokens=list(range(1, 20)),
                       sampling=SamplingParams(max_new_tokens=3)))
    eng.run_until_idle()
    s = AIRuntime(eng).scrape()
    assert s["steps"] == eng.tracer.steps > 0
    for p in PHASES:
        assert s[f"step_{p}_s"] == eng.tracer.phase_s[p] >= 0.0
    assert s["step_launch_s"] > 0.0 and s["step_readback_s"] > 0.0


def test_cold_start_manager_prefers_fastest_tier():
    m = ColdStartManager()
    m.register_artifact(ModelArtifact(
        "x", 14e9, tier_by_node={"a": "local", "b": "dram"}))
    assert m.best_node("x", ["a", "b", "c"]) == "b"
    assert m.cold_start_s("x", "b") < m.cold_start_s("x", "c")
