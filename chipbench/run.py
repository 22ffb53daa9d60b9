#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` -> ``workloads``) names a configuration
file under ``chipbench/configs``, a traffic mix under
``chipbench/traffic`` and its correctness limit under
``chipbench/limits``.  The run refuses to start unless JAX's first
device is a TPU and there are as many as the cell asks for; it never
falls back to the CPU.

Set-up (``setup_s``, from process start to the window's opening): make
the weights from the seed on the device, build the engine behind the
gateway, load every step program the traffic can meet, replay the
cell's warm-up traffic.  Then ``--seconds`` of open-loop traffic, the
drain, and the check of the served tokens against the plain reference
(``chipbench/correctness.py``) once the program's state is freed.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read by ``chipbench/metrics/<metric>.py`` from the
harness's own spans and counters over the window, from the program's
(its step records, and ``engine.metrics()`` as the window opened and
closed), and from a profiler trace of the first seconds of the drain
(3 s, or until a traced step has carried a prefill chunk, at most 10
s), under the same load: stopping the profiler stalls the host for
seconds, which would distort the window's requests.  Only ``--trace
1`` switches the engine's tracer on.  The trace's kernels are the
``KERNELS`` of the configuration's architecture module
(``chipbench/arch``).  The last stdout line is one JSON object; the
numbers compared for ``correct`` come last in it, under ``checks``, and
again as the last lines on stderr.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_S = 3.0               # length of the traced slice
# ... or longer, until a traced step has carried a prefill chunk: a fast
# engine can finish the window's prefills before the slice opens (the
# long-docs timeline has no arrival from 49.7 to 55.0 s of any seed)
TRACE_CAP_S = 10.0


def fail(msg: str, code: int = 3):
    print(f"chipbench: {msg}", file=sys.stderr)
    sys.exit(code)


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(name: str):
    """(workload entry, configuration, traffic mix, limits) of a cell."""
    from chipbench import correctness, traffic
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"unknown workload {name!r}; known: {sorted(cells)}", 2)
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(ROOT / conf["file"]) as f:
        cdoc = json.load(f)
    return (cell, cdoc, traffic.load_mix(cell["traffic"]),
            correctness.load_limits(name))


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU, but JAX's first device is "
             f"{devices[0].platform!r}; nothing was run")
    if len(devices) < n:
        fail(f"the cell needs {n} TPU chips, JAX sees {len(devices)}")
    return devices


def use_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed place in the checkout,
    small programs included, so only a checkout's first run compiles.
    The directory reaches the program's own cache set-up through
    ``JAX_COMPILATION_CACHE_DIR``, which is set before JAX is imported:
    a directory the machine names elsewhere would be shared with other
    checkouts."""
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def span_wrappers(bench):
    """Host spans around the calls into each layer, for labelling the
    device's idle gaps in the trace."""
    import jax
    TA = jax.profiler.TraceAnnotation
    r, sched = bench.engine.runner, bench.engine.sched

    def wrap(obj, attr, name):
        inner = getattr(obj, attr, None)
        if inner is None:
            return

        def call(*a, **kw):
            with TA(name):
                return inner(*a, **kw)
        setattr(obj, attr, call)

    wrap(sched, "schedule", "schedule")
    for attr in ("_prefill_inputs", "_decode_inputs"):
        wrap(r, attr, "input_prep")
    for attr in ("run_mixed", "run_decode", "run_prefill"):
        wrap(r, attr, "dispatch")
    wrap(r, "sample", "sample")
    wrap(r, "readback", "readback")
    return TA


def listed(metrics: list, cell: str) -> list:
    """The metrics of ``BENCHMARK.json`` that a cell reports."""
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def run_cell(name: str, cell: dict, cdoc: dict, mix: dict, limits: dict,
             seed: int, seconds: float, trace: bool, device, t0: float,
             faults=(), controls=()) -> dict:
    """Everything after the look for a chip: set-up, window, drain,
    metrics and the check; returns the result object."""
    from chipbench import arch, correctness, traffic, window
    from chipbench import trace as T
    from chipbench.bench import Bench

    bench = Bench(cdoc, seed, device)
    for fault in faults:
        fault(bench)
    bench.warm_programs(mix["context_cap"])
    arrivals = traffic.timeline(mix, seed, seconds, cdoc["vocab_size"])
    spans = span_wrappers(bench) if trace else None
    if trace:
        bench.engine.tracer.enable()
    res = bench.serve(arrivals, mix["warmup_s"], seconds, mix["drain_s"],
                      (seconds, seconds + TRACE_S, seconds + TRACE_CAP_S)
                      if trace else None,
                      spans)
    setup_s = res.window_open - t0
    memory = dict(device.memory_stats() or {})
    open_e = res.to_engine(res.window_open)
    summary = window.summarize(res.records, open_e, seconds, res.drained_at,
                               mix["limits"]["gap_ms"] / 1e3)
    late = sorted(r.late_s for r in res.records if r.in_window)
    reduced = None
    if res.trace is not None:
        events = T.load(res.trace["dir"])
        reduced = T.reduce(events, (res.trace["t_stop"]
                                    - res.trace["t_start"]) * 1e9,
                           arch.load(cdoc).KERNELS)
    ctx = SimpleNamespace(
        cdoc=cdoc, mix=mix, records=res.records, serve=res,
        open_e=open_e, close_e=open_e + seconds, stop_e=res.drained_at,
        memory=memory, trace=reduced, peak=peaks(device) if trace else None,
        step_records=list(bench.engine.tracer.records),
        counters_open=res.counters_open, counters_close=res.counters_close,
        window_steps=[s for s in res.steps
                      if res.window_open <= s.t < res.window_close],
        traced_steps=[s for s in res.steps if res.trace is not None
                      and res.trace["t_start"] <= s.t < res.trace["t_stop"]])
    bench_doc = load_benchmark()
    metrics = {}
    if trace:
        for m in listed(bench_doc["per_layer"], name):
            value = importlib.import_module(
                f"chipbench.metrics.{m['name']}").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    records = res.records
    bench.free()
    del ctx, res
    samples = correctness.pick(records, seed)
    readings = correctness.gap_readings(
        cdoc, bench.weights, samples, mix["context_cap"],
        mix["output"]["max"], controls)
    correct, checks = correctness.decide(
        readings["served_gap_max"], readings["served_tokens"], limits)
    if not trace:
        e2e = dict(summary, setup_s=setup_s)
        for m in listed(bench_doc["end_to_end"], name):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": int(cell["chips"]),
           "memory_peak_bytes": memory.get("peak_bytes_in_use")}
    out = {"correct": correct, "attempted": summary["attempted"],
           "failed": summary["failed"], "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"],
                            "host_gaps": reduced["program"]["host_gaps"]}
    out["_diag"] = {
        "summary": summary, "setup_s": setup_s, "readings": readings,
        "late_p50_ms": 1e3 * late[len(late) // 2] if late else None,
        "late_max_ms": 1e3 * late[-1] if late else None,
        "compiles_total": len(bench.compiles.ends),
        "compile_s_total": bench.compiles.seconds,
        "trace": None if reduced is None else {
            k: reduced[k] for k in ("kernel_s", "kernel_calls",
                                    "step_programs", "step_busy_s",
                                    "step_busy_by",
                                    "op_events", "devices")},
        "program_trace": None if reduced is None else {
            k: reduced["program"][k] for k in ("modules", "step_spans",
                                               "host_idle_s", "mismatched")}}
    out["checks"] = checks
    return out


def peaks(device) -> dict:
    with open(ROOT / "chipbench" / "peaks.json") as f:
        table = json.load(f)
    if device.device_kind not in table:
        fail(f"no peaks for device kind {device.device_kind!r} in "
             "chipbench/peaks.json")
    return table[device.device_kind]


def emit(out: dict) -> None:
    diag = out.pop("_diag", None)
    if diag is not None:
        print("chipbench: " + json.dumps(diag, default=str), file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr)
    print(json.dumps(out))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    use_compile_cache()
    cell, cdoc, mix, limits = load_cell(args.workload)
    devices = require_chips(int(cell["chips"]))
    out = run_cell(args.workload, cell, cdoc, mix, limits, args.seed,
                   args.seconds, bool(args.trace), devices[0], T0)
    emit(out)


if __name__ == "__main__":
    main()
