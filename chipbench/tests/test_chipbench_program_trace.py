"""The program's spans in a trace (chipbench/program_trace.py) and the
readers of what the engine's tracer adds: hand-made modules, ops and
program spans against numbers worked by hand, a trace the CPU profiler
records from a tiny engine, and nothing read where the program has no
tracer."""
import importlib
import json
import tempfile
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import program_trace as P
from chipbench import trace as T
from chipbench.arch.qwen_dense import KERNELS
from repro.engine.tracing import StepRecord

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6


def read(name, ctx):
    return importlib.import_module(f"chipbench.metrics.{name}").read(ctx)


def dev(start, end, name, line=T.OPS_LINE):
    return {"plane": "/device:TPU:0", "line": line, "name": name,
            "start": start * MS, "dur": (end - start) * MS, "meta": ""}


def mod(start, end, name):
    return dev(start, end, name, T.MODULES_LINE)


def span(start, end, name, **stats):
    return {"name": name, "start": start * MS, "dur": (end - start) * MS,
            "stats": stats}


# three steps: a decode step, a fused step with a prefill chunk, and a
# decode step; the sampler's module after the first
DEVICE = [
    mod(0, 10, "jit_decode_batch(11)"), dev(0, 4, "copy.1"),
    dev(5, 10, "%paged_attention.3 = bf16[8]"),
    mod(10.5, 11, "jit_sample(12)"), dev(10.5, 11, "sort.2"),
    mod(14, 24, "jit_mixed_step(13)"), dev(14, 18, "fusion.4"),
    dev(19, 24, "%paged_prefill.5 = bf16[8]"),
    mod(26, 30, "jit_decode_batch(11)"), dev(26, 30, "copy.6"),
]
PROGRAM = sorted([
    span(-2, 12, "engine.step", step=0),
    span(-1, -0.5, "runner.launch", step=0, program="decode_batch",
         dec_width=4, pre_width=0, chunks=0),
    span(1, 11.2, "runner.readback"),
    span(11.2, 12, "engine.post"),
    span(12.5, 25, "engine.step", step=1),
    span(12.6, 13, "engine.schedule"),
    span(13, 13.5, "runner.prep"),
    span(13.5, 13.8, "runner.launch", step=1, program="mixed_step",
         dec_width=4, pre_width=4, chunks=1),
    span(25.2, 31, "engine.step", step=2),
    span(25.5, 25.8, "runner.launch", step=2, program="decode_batch",
         dec_width=4, pre_width=0, chunks=0),
], key=lambda s: s["start"])
WINDOW_MS = 32.0
STEPS = [[0, "decode_batch", pytest.approx(10e-3)],
         [1, "mixed_step", pytest.approx(10e-3)],
         [2, "decode_batch", pytest.approx(4e-3)]]


def test_between_module_gaps_and_join():
    out = P.reduce(DEVICE, PROGRAM)
    # gaps between modules, inside an open engine.step, each labelled
    # by the innermost span at its middle: 10-10.5 (readback), 11-12
    # (post), 12.5-14 (prep), 24-25 (the step alone), 25.2-26 (launch);
    # 12-12.5 and 25-25.2 fall between steps, the ops' own gaps inside
    # a module (4-5, 18-19 ms) are no host idle
    assert out["host_idle_s"] == pytest.approx(4.8e-3)
    assert out["host_gaps"] == [
        ["runner.prep x1", pytest.approx(1.5e-3)],
        ["engine.post x1", pytest.approx(1e-3)],
        ["engine.step x1", pytest.approx(1e-3)],
        ["runner.launch x1", pytest.approx(0.8e-3)],
        ["runner.readback x1", pytest.approx(0.5e-3)]]
    # the step programs join the launches in order; the sampler's
    # module is no step program
    assert out["steps"] == STEPS
    assert out["step_spans"] == 3 and out["modules"] == 4
    assert out["mismatched"] == 0


def test_join_is_by_launch_order():
    """A device clock 3 ms early puts modules before their launches on
    the trace's time line; the order still joins each to its step."""
    early = [dict(e, start=e["start"] - 3 * MS) for e in DEVICE]
    assert P.reduce(early, PROGRAM)["steps"] == STEPS


def test_disagreeing_programs_join_nothing():
    """Module i joins launch i; a pair whose programs differ is counted
    and left out, and a launch whose module fell after the trace joins
    nothing."""
    device = [mod(0, 5, "jit_mixed_step(3)"),
              mod(6, 9, "jit_prefill_step(1)"),
              mod(10, 12, "jit_spec_mixed_step(2)")]
    program = [span(-1, -0.8, "runner.launch", step=7,
                    program="mixed_step"),
               span(5.8, 5.9, "runner.launch", step=8,
                    program="mixed_step"),
               span(9.5, 9.6, "runner.launch", step=9,
                    program="spec_mixed_step"),
               span(12.5, 12.6, "runner.launch", step=10,
                    program="decode_batch")]   # ran after the trace
    out = P.reduce(device, program)
    assert out["steps"] == [[7, "mixed_step", pytest.approx(5e-3)],
                            [9, "spec_mixed_step", pytest.approx(2e-3)]]
    assert out["mismatched"] == 1
    assert out["step_spans"] == 0 and out["host_idle_s"] == 0.0


def _ctx(**kw):
    base = dict(trace=None, step_records=[], records=[], stop_e=10.0)
    base.update(kw)
    return NS(**base)


def test_readers():
    window = {"device": DEVICE, "host": []}
    trace = T.reduce(window, WINDOW_MS * MS, KERNELS)
    trace["program"] = P.reduce(DEVICE, PROGRAM)
    recs = [StepRecord(0, "decode_batch"),
            StepRecord(1, "mixed_step", chunks=[(0, 256)]),
            StepRecord(2, "decode_batch")]
    c = _ctx(trace=trace, step_records=recs)
    host = read("step_host_idle_share", c)
    assert host == pytest.approx(100 * 4.8 / WINDOW_MS)
    # busy 22.5 of 32 ms: the between-program idle is part of it
    assert read("device_idle_share", c) == pytest.approx(
        100 * 9.5 / WINDOW_MS)
    assert host <= read("device_idle_share", c)
    # the decode-only steps 0 and 2: 10 and 4 ms
    assert read("decode_step_device_ms", c) == pytest.approx(7.0)


def _rec(in_window, admit=None, first=0.0, req=True):
    q = NS(admit_time=admit, first_token_time=first) if req else None
    return NS(in_window=in_window, req=q)


def test_prefill_span():
    records = [_rec(True, 1.0, 1.3), _rec(True, 2.0, 4.5),
               _rec(True, 3.0),                 # no first token: to stop
               _rec(True),                      # never admitted
               _rec(False, 0.5, 9.5),           # not due in the window
               _rec(True, req=False)]           # refused
    c = _ctx(records=records)
    assert read("prefill_span_p90_ms", c) == pytest.approx(7000.0)
    records[2].req.first_token_time = 3.2
    assert read("prefill_span_p90_ms", c) == pytest.approx(2500.0)


def test_nothing_to_read_without_the_tracer():
    """A program without the tracer (no admit_time, no program spans,
    no records): each reader returns nothing, never 0."""
    old = NS(in_window=True, req=NS(first_token_time=2.0))
    trace = T.reduce({"device": DEVICE, "host": []}, WINDOW_MS * MS,
                     KERNELS)
    for t in (None, trace, dict(trace, program=P.reduce(DEVICE, []))):
        c = _ctx(trace=t, records=[old])
        for name in ("step_host_idle_share", "decode_step_device_ms",
                     "prefill_span_p90_ms"):
            assert read(name, c) is None, (name, t is None)


def test_recorded_trace_reads_as_before():
    """The recorded slice (harness spans only, from a program without
    the tracer) reduces to what it always did, and holds no program
    span."""
    ev = json.load(open(DATA / "trace-short-chat.json"))
    d = ev["device"]
    extent = max(e["start"] + e["dur"] for e in d) - min(e["start"]
                                                         for e in d)
    out = T.reduce(ev, extent, KERNELS)
    assert out["idle_gaps"] == [
        ["input_prep x1", pytest.approx(0.0040697)],
        ["none x35", pytest.approx(9.11e-06)],
        ["dispatch x4", pytest.approx(7e-09)]]
    assert [k for k, _ in out["device_ops"]] == [
        "sort", "constant_dynamic-slice_fusion", "copy_bitcast_fusion",
        "copy", "copy_dynamic-update-slice_fusion", "paged_attention",
        "fusion", "iota_reduce_fusion", "rev", "bitcast_add_fusion"]
    assert out["device_ops"][0][1] == pytest.approx(0.00703459)
    assert out["kernel_s"] == {"paged_attention": pytest.approx(0.001880083),
                               "paged_prefill": 0.0}
    prog = P.reduce(d, [])
    assert prog["step_spans"] == 0 and prog["steps"] == []
    # the module of the decode step, were its launch in the slice
    launch = span(0, 0, "runner.launch", step=5, program="decode_batch")
    launch["start"] = min(e["start"] for e in d)
    assert P.reduce(d, [launch])["steps"] == [
        [5, "decode_batch", pytest.approx(117757033e-9)]]


def test_load_from_a_cpu_trace():
    """The CPU profiler records a tiny engine's spans: ``load`` keeps
    each with its stats, one ``engine.step`` and one launch a step."""
    import jax
    from repro.configs import get_reduced_config
    from repro.engine import (EngineConfig, InferenceEngine, Request,
                              SamplingParams)
    eng = InferenceEngine(get_reduced_config("qwen3-0.6b"), EngineConfig(
        page_size=8, num_pages=32, max_batch=2, max_pages_per_seq=8,
        chunk_size=16))
    eng.tracer.enable()
    eng.submit(Request(prompt_tokens=list(range(1, 20)),
                       sampling=SamplingParams(max_new_tokens=3)))
    eng.step()                              # compile outside the trace
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        eng.run_until_idle()
        jax.profiler.stop_trace()
        spans = P.load(d)
    recs = list(eng.tracer.records)[1:]
    steps = [s["stats"]["step"] for s in spans if s["name"] == "engine.step"]
    assert steps == [r.step for r in recs]
    launch = [s["stats"] for s in spans if s["name"] == "runner.launch"]
    assert [(x["step"], x["program"], x["chunks"]) for x in launch] == [
        (r.step, r.program, len(r.chunks)) for r in recs if r.program]
    assert {s["name"] for s in spans} >= {"engine.schedule", "runner.prep",
                                          "runner.upload", "runner.sample",
                                          "runner.readback", "engine.post"}
