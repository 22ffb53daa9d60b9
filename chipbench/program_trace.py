"""From the program's own spans in a profiler trace to per-step numbers.

The engine's tracer (``repro.engine.tracing``, on through
``engine.tracer.enable()``) writes host spans named ``engine.*`` and
``runner.*`` into the profiler's trace: ``engine.step``
around every step, and ``runner.launch`` around each jitted step call
with the step number, the program and the live chunk count as stats.

``load`` keeps those spans, with their int and string stats, from the
``.xplane.pb`` that ``jax.profiler`` wrote.  ``reduce`` is plain Python
over them and the device events of ``chipbench/trace.py``'s ``load``:

- host idle: the device time outside every ``XLA Modules`` interval
  (between programs, not between the ops of one) while ``engine.step``
  was open, each piece labelled by the innermost program span open at
  its middle;
- each step program's device time, joined to its step in launch
  order: one device stream runs modules in the order they were
  launched, so the k-th step-program module is the k-th
  ``runner.launch``.  The order of each stream is all it takes from
  the profiler's clock, so an offset between the host's and the
  device's timestamps cannot move a module to another step.  The
  harness starts the trace with no program in flight (the synchronous
  loop); a pair whose programs disagree is counted and not joined.

Every join is on the profiler's clock and the step number, never on
the host's ``perf_counter``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import List

MODULES_LINE = "XLA Modules"
PREFIXES = ("engine.", "runner.")
# the engine's jitted step programs, by the function name a module
# carries on the modules line (``jit_decode_batch(...)``)
STEP_PROGRAMS = ("mixed_step", "decode_batch", "prefill_step",
                 "spec_decode_step", "spec_mixed_step")


def load(trace_dir: str) -> List[dict]:
    """The program's host spans in a trace directory, by start."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    spans.append({
                        "name": ev.name, "start": float(ev.start_ns),
                        "dur": float(ev.duration_ns),
                        "stats": {k: v for k, v in ev.stats
                                  if isinstance(v, (int, str))}})
    return sorted(spans, key=lambda s: s["start"])


def _union(intervals) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def program_of(module_name: str) -> str:
    """``jit_decode_batch(123)`` -> ``decode_batch``."""
    m = re.match(r"jit_(\w+)", module_name)
    return m.group(1) if m else ""


def _innermost(spans: List[dict], starts: List[float], longest: float,
               t: float):
    best = None
    j = bisect.bisect_right(starts, t) - 1
    while j >= 0 and starts[j] >= t - longest:
        s = spans[j]
        if t < s["start"] + s["dur"] and (
                best is None or s["dur"] < best["dur"]):
            best = s
        j -= 1
    return best


def reduce(device: List[dict], program: List[dict], top: int = 10) -> dict:
    """``device``: the device events of ``chipbench/trace.py``'s
    ``load``; ``program``: the spans of ``load`` above.  Times in the
    result are seconds."""
    modules = sorted((e for e in device if e["line"] == MODULES_LINE),
                     key=lambda e: e["start"])
    busy = _union((m["start"], m["start"] + m["dur"]) for m in modules)
    steps_open = _union((s["start"], s["start"] + s["dur"])
                        for s in program if s["name"] == "engine.step")
    starts = [s["start"] for s in program]
    longest = max((s["dur"] for s in program), default=0.0)
    by = defaultdict(lambda: [0.0, 0])
    for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
        for a, b in steps_open:
            lo, hi = max(e0, a), min(s1, b)
            if hi <= lo:
                continue
            span = _innermost(program, starts, longest, (lo + hi) / 2)
            label = span["name"] if span else "none"
            by[label][0] += hi - lo
            by[label][1] += 1
    ranked = sorted(by.items(), key=lambda kv: -kv[1][0])
    ran = [(program_of(m["name"]), m["dur"]) for m in modules
           if program_of(m["name"]) in STEP_PROGRAMS]
    launched = [(s["stats"].get("program"), s["stats"]["step"])
                for s in program
                if s["name"] == "runner.launch" and "step" in s["stats"]]
    pairs = list(zip(launched, ran))        # module i ran launch i
    steps = [[step, prog, dur / 1e9]
             for (want, step), (prog, dur) in pairs if want == prog]
    return {"modules": len(modules),
            "step_spans": sum(1 for s in program
                              if s["name"] == "engine.step"),
            "host_idle_s": sum(v for v, _n in by.values()) / 1e9,
            "host_gaps": [[f"{k} x{n}", v / 1e9]
                          for k, (v, n) in ranked[:top]],
            "steps": steps,
            "mismatched": len(pairs) - len(steps)}
