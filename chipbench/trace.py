"""From a profiler trace to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
a flat list of events; ``reduce`` is plain Python over that list, so
the tests check it on a small recorded trace.

- Device events: every event on a ``/device:TPU:*`` plane, with its line
  (``XLA Ops``, ``XLA Modules``, ...), name, start and duration in ns,
  and its string stats joined into ``meta``.
- Host spans: events on host threads, among them the harness's own
  ``TraceAnnotation`` spans.
- The program's own spans (``chipbench/program_trace.py``), with their
  stats, which ``reduce`` hands to ``program_trace.reduce``.

``reduce`` computes, over the traced window: the union of device-op
intervals (busy), the time of each named kernel and of each step
program, the device ops that took the most time, and the idle gaps
between device ops, each labelled with the innermost harness span the
host was in at the gap's middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from collections import defaultdict
from typing import Dict, List, Optional

from chipbench import program_trace

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# harness spans that may label an idle gap (chipbench/run.py opens them)
SPANS = ("route", "submit", "step", "schedule", "input_prep", "dispatch",
         "sample", "readback", "wait_arrival")


def load(trace_dir: str, cleanup: bool = True) -> dict:
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    device, host = [], []
    for plane in pd.planes:
        on_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            for ev in line.events:
                start, dur = float(ev.start_ns), float(ev.duration_ns)
                if on_device:
                    meta = " ".join(str(v) for _k, v in ev.stats
                                    if isinstance(v, str))
                    device.append({"plane": plane.name, "line": line.name,
                                   "name": ev.name, "start": start,
                                   "dur": dur, "meta": meta})
                elif ev.name in SPANS:
                    host.append({"name": ev.name, "start": start,
                                 "dur": dur})
    program = program_trace.spans(pd)
    if cleanup:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {"device": device, "host": host, "program": program}


def _union(intervals) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


# the step programs, by the name of the jitted function on the modules
# line (``jit_mixed_step(...)``)
STEP_PROGRAMS = ("mixed_step", "decode_batch", "prefill_step")
# ops that only contain others (a layer scan's while loop): they count
# in the busy union, not among the top ops
CONTAINERS = ("while", "conditional", "call")


def op_label(ev: dict) -> str:
    """An op's HLO name without its number: ``%paged_attention.13 = ...``
    is ``paged_attention`` (a Pallas kernel carries its wrapper's name)."""
    m = re.match(r"%?([A-Za-z_][\w-]*?)(?:\.\d+)?(?:[\s=]|$)", ev["name"])
    return m.group(1) if m else ev["name"]


def matches(ev: dict, pattern: str) -> bool:
    return re.search(pattern, op_label(ev)) is not None


def _busy_s(events) -> float:
    return sum(e - s for s, e in _union(
        (m["start"], m["start"] + m["dur"]) for m in events)) / 1e9


def reduce(events: dict, window_ns: float,
           kernels: Optional[Dict[str, str]] = None,
           top: int = 10) -> dict:
    """Busy and idle time, kernel time, each step program's time, top
    ops, labelled idle gaps, and under ``program`` the reduction of the
    program's own spans (``program_trace.reduce``).  ``kernels`` maps a
    kernel's name to the regex that finds its ops."""
    dev = events["device"]
    planes = sorted({e["plane"] for e in dev})
    ops = [e for e in dev if e["line"] == OPS_LINE]
    modules = [e for e in dev if e["line"] == MODULES_LINE]
    n_dev = max(len(planes), 1)
    busy = _union((e["start"], e["start"] + e["dur"]) for e in ops)
    busy_ns = sum(e - s for s, e in busy)
    out = {"busy_s": busy_ns / n_dev / 1e9, "window_s": window_ns / 1e9,
           "devices": len(planes), "op_events": len(ops)}
    out["kernel_s"] = {
        k: sum(e["dur"] for e in ops if matches(e, pat)) / 1e9
        for k, pat in (kernels or {}).items()}
    out["kernel_calls"] = {
        k: sum(1 for e in ops if matches(e, pat))
        for k, pat in (kernels or {}).items()}
    steps = {p: [m for m in modules if p in m["name"]]
             for p in STEP_PROGRAMS}
    out["step_programs"] = sum(len(v) for v in steps.values())
    out["step_busy_s"] = _busy_s(m for v in steps.values() for m in v)
    out["step_busy_by"] = {p: _busy_s(v) for p, v in steps.items()}
    by_op = defaultdict(float)
    for e in ops:
        label = op_label(e)
        if label not in CONTAINERS:
            by_op[label] += e["dur"]
    out["device_ops"] = [[k, v / 1e9] for k, v in sorted(
        by_op.items(), key=lambda kv: -kv[1])[:top]]
    out["idle_gaps"] = label_gaps(busy, events["host"], top)
    out["program"] = program_trace.reduce(dev, events.get("program", []),
                                          top)
    return out


def label_gaps(busy: List[tuple], host: List[dict], top: int = 10):
    """Idle time between device ops, summed by the innermost harness
    span open at each gap's middle (``none`` when no span was open)."""
    spans = sorted(host, key=lambda h: h["start"])
    starts = [h["start"] for h in spans]
    longest = max((h["dur"] for h in spans), default=0.0)
    by = defaultdict(lambda: [0.0, 0])
    for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
        gap = s1 - e0
        if gap <= 0:
            continue
        mid = e0 + gap / 2
        best = None
        j = bisect.bisect_right(starts, mid) - 1
        while j >= 0 and starts[j] >= mid - longest:
            h = spans[j]
            if mid < h["start"] + h["dur"] and (
                    best is None or h["dur"] < best["dur"]):
                best = h
            j -= 1
        name = best["name"] if best else "none"
        by[name][0] += gap
        by[name][1] += 1
    ranked = sorted(by.items(), key=lambda kv: -kv[1][0])[:top]
    return [[f"{k} x{n}", v / 1e9] for k, (v, n) in ranked]
