"""The per-layer readers (chipbench/metrics/*.py) on hand-made spans,
counters and trace reductions: each number against one worked by hand,
and nothing returned where there is nothing to read."""
import importlib
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import work
from chipbench.bench import StepLog
from repro.engine.scheduler import EngineMetrics

ROOT = Path(__file__).resolve().parents[2]
Q3 = json.load(open(ROOT / "chipbench" / "configs" / "qwen3-0.6b.json"))
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NAMES = [m["name"] for m in json.load(open(ROOT / "BENCHMARK.json"))
         ["per_layer"]]


def read(name, ctx):
    return importlib.import_module(f"chipbench.metrics.{name}").read(ctx)


def ctx(**kw):
    steps = [StepLog(0.0, "decode", [1000, 2000], []),
             StepLog(0.1, "mixed", [3000], [(512, 256)]),
             StepLog(0.2, "prefill", [], [(0, 256)])]
    trace = {"busy_s": 0.05, "window_s": 0.2, "op_events": 10,
             "kernel_s": {"paged_attention": 0.004, "paged_prefill": 0.001},
             "step_busy_by": {"mixed_step": 0.01, "decode_batch": 0.02,
                              "prefill_step": 0.005}}
    base = dict(cdoc=Q3, peak=PEAK, trace=trace, traced_steps=steps,
                window_steps=steps, records=[], memory={},
                serve=NS(step_count=0, window_compiles=0), step_records=[],
                counters_open=EngineMetrics(), counters_close=EngineMetrics())
    base.update(kw)
    return NS(**base)


def test_step_mfu_over_all_busy_time():
    c = ctx()
    flops = sum(work.step_flops(Q3, s.dec_lens, s.chunks)
                for s in c.traced_steps)
    assert read("step_mfu", c) == pytest.approx(
        100 * flops / (0.05 * 197e12))


def test_prefill_step_mfu_over_its_programs():
    c = ctx()
    flops = (work.step_flops(Q3, [3000], [(512, 256)])
             + work.step_flops(Q3, [], [(0, 256)]))
    assert read("prefill_step_mfu", c) == pytest.approx(
        100 * flops / (0.015 * 197e12))


def test_kernel_rooflines():
    c = ctx()
    need = 28 * (work.roofline_s(*work.decode_attention(Q3, [1000, 2000]),
                                 PEAK)[0]
                 + work.roofline_s(*work.decode_attention(Q3, [3000]),
                                   PEAK)[0])
    assert read("paged_attention_roofline", c) == pytest.approx(
        100 * need / 0.004)
    need = 28 * (work.roofline_s(*work.prefill_attention(Q3, [(512, 256)]),
                                 PEAK)[0]
                 + work.roofline_s(*work.prefill_attention(Q3, [(0, 256)]),
                                   PEAK)[0])
    assert read("paged_prefill_roofline", c) == pytest.approx(
        100 * need / 0.001)


def test_device_and_step_counts():
    c = ctx(memory={"peak_bytes_in_use": 4e9, "bytes_limit": 16e9})
    assert read("device_idle_share", c) == pytest.approx(75.0)
    assert read("hbm_peak_share", c) == pytest.approx(25.0)
    assert read("decode_rows_per_step", c) == pytest.approx(1.0)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read(name):
    """No trace, no steps, no requests: every reader but the compile
    count returns nothing, never a zero share."""
    c = ctx(trace=None, traced_steps=[], window_steps=[])
    value = read(name, c)
    if name == "window_compiles":
        assert value == 0.0
    else:
        assert value is None
