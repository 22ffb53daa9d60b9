"""The trace reduction (chipbench/trace.py): busy union, kernel and step
time, top ops and idle gaps labelled by the harness's host spans."""
import json
from pathlib import Path

import pytest

from chipbench import trace as T
from chipbench.arch.qwen_dense import KERNELS

DATA = Path(__file__).resolve().parent / "data"


def op(start, dur, name="fusion.1", meta="", line=T.OPS_LINE):
    return {"plane": "/device:TPU:0", "line": line, "name": name,
            "start": float(start), "dur": float(dur), "meta": meta}


def test_busy_union_kernels_and_gaps():
    ms = 1e6
    events = {"device": [
        op(0, 2 * ms, "%paged_attention.3 = bf16[32,8,2,128] custom-call()"),
        op(1 * ms, 2 * ms, "fusion.7"),                 # overlaps: 0-3 ms
        op(5 * ms, 1 * ms, "%paged_prefill.9 = bf16[2,2,6,256,128]"),
        op(0, 3 * ms, "%while.14 = (s32[], bf16[32,1,1024])"),
        op(9 * ms, 1 * ms, "fusion.8"),
        op(0, 10 * ms, "jit_mixed_step", line=T.MODULES_LINE),
    ], "host": [
        {"name": "step", "start": 0.0, "dur": 10 * ms},
        {"name": "schedule", "start": 3.5 * ms, "dur": 1.2 * ms},
        {"name": "sample", "start": 6.5 * ms, "dur": 2.0 * ms},
    ]}
    out = T.reduce(events, 12 * ms, KERNELS)
    # busy 0-3, 5-6 and 9-10 ms; the while loop only contains others
    assert out["busy_s"] == pytest.approx(5e-3)
    assert out["window_s"] == pytest.approx(12e-3)
    assert out["kernel_s"]["paged_attention"] == pytest.approx(2e-3)
    assert out["kernel_s"]["paged_prefill"] == pytest.approx(1e-3)
    assert out["step_programs"] == 1
    assert out["step_busy_s"] == pytest.approx(10e-3)
    assert out["step_busy_by"] == {"mixed_step": pytest.approx(10e-3),
                                   "decode_batch": 0.0, "prefill_step": 0.0}
    assert out["device_ops"][0] == ["fusion", pytest.approx(3e-3)]
    # gaps 3-5 ms (middle 4 ms: in schedule) and 6-9 ms (7.5: in sample)
    assert out["idle_gaps"] == [["sample x1", pytest.approx(3e-3)],
                                ["schedule x1", pytest.approx(2e-3)]]


def test_no_device_events():
    out = T.reduce({"device": [], "host": []}, 1e9, KERNELS)
    assert out["busy_s"] == 0.0 and out["op_events"] == 0
    assert out["kernel_s"] == {"paged_attention": 0.0, "paged_prefill": 0.0}


def test_recorded_trace():
    """30 ms recorded from a traced run on a v5e: one decode step's ops
    (the layer scan's while loop, the decode kernel, the pool copies,
    the sampler's sort) and the host spans around them."""
    ev = json.load(open(DATA / "trace-short-chat.json"))
    dev = ev["device"]
    extent = (max(e["start"] + e["dur"] for e in dev)
              - min(e["start"] for e in dev))
    out = T.reduce(ev, extent, KERNELS)
    assert 0 < out["busy_s"] <= out["window_s"]
    kernel = [e for e in dev if e["line"] == T.OPS_LINE
              and e["name"].startswith("%paged_attention.")]
    assert out["kernel_calls"]["paged_attention"] == len(kernel) == 5
    assert out["kernel_s"]["paged_attention"] == pytest.approx(
        sum(e["dur"] for e in kernel) / 1e9)
    assert out["kernel_calls"]["paged_prefill"] == 0
    assert out["step_programs"] == 1
    names = [k for k, _ in out["device_ops"]]
    assert "while" not in names and names[0] == "sort"
    secs = [v for _, v in out["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    labels = {k.split(" x")[0] for k, _ in out["idle_gaps"]}
    assert labels <= set(T.SPANS) | {"none"}
    assert out["idle_gaps"][0][0] == "input_prep x1"
