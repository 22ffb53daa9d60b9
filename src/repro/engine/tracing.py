"""In-program spans and step records of one engine.

Each ``InferenceEngine`` owns an ``EngineTracer`` (``engine.tracer``).
It is off until ``enable()``, its one switch.

Always kept, on or off: the number of steps, the host seconds spent
inside ``step()``, and the host seconds of each phase of a step
(``PHASES``).  A phase counts its self time, its wall time less that of
the phases opened inside it, so the phases of a step sum to no more
than the step (the async loop's drain, outside any step, counts in its
phases too).  These cumulative counters reach ``EngineMetrics`` and
the runtime sidecar's ``scrape()``.

When on, every ``step()`` runs under
``StepTraceAnnotation("engine_step", step_num=n)`` and an
``engine.step`` span, every phase opens a ``TraceAnnotation`` named by
``PHASES``, and every step appends a ``StepRecord`` to a bounded deque
that is read after the run and never written out on the hot path.  The
``runner.launch`` span carries the step number and the program it
launched, which joins a step program's device time in a profiler trace
to the step's record.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass, field
from time import perf_counter
from typing import List, Optional, Tuple

import jax

# phase -> the span it opens when the tracer is on
PHASES = {
    "schedule": "engine.schedule",  # Scheduler.schedule
    "wait": "runner.wait",          # blocked on the previous step's outputs
    "prep": "runner.prep",          # host fill of the step's input buffers
    "upload": "runner.upload",      # the step's host inputs to the device
    "launch": "runner.launch",      # the jitted step call (dispatch)
    "sample": "runner.sample",      # sampling (host prep and dispatch)
    "readback": "runner.readback",  # blocked on sampled tokens
    "post": "engine.post",          # scheduler bookkeeping of the outputs
}

MAX_RECORDS = 65536     # records kept: the newest, about 2 h of 100 ms steps


@dataclass
class StepRecord:
    """What one ``engine.step()`` launched."""
    step: int
    program: str = ""       # the jitted step program; "" when none ran
    # (start, length) of each live prefill chunk the program carried
    chunks: List[Tuple[int, int]] = field(default_factory=list)


class EngineTracer:
    def __init__(self):
        self.on = False
        self.steps = 0
        self.step_s = 0.0
        self.phase_s = dict.fromkeys(PHASES, 0.0)
        self.records: deque = deque(maxlen=MAX_RECORDS)
        self._rec: Optional[StepRecord] = None
        self._open: List[_Phase] = []       # phases open now, innermost last

    def enable(self) -> None:
        self.on = True

    def step(self) -> "_Step":
        return _Step(self)

    def phase(self, name: str) -> "_Phase":
        return _Phase(self, name)

    def launch(self, program: str, widths: Tuple[int, int],
               prefills=()) -> "_Phase":
        """The phase around one jitted step call; when on, notes the
        program and its live chunks in the step's record and on the
        span."""
        rec = self._rec
        if rec is None:
            return _Phase(self, "launch")
        rec.program = program
        rec.chunks = [(w.start, w.chunk_len) for w in prefills
                      if w.chunk_len]
        return _Phase(self, "launch", dict(
            step=rec.step, program=program, dec_width=int(widths[0]),
            pre_width=int(widths[1]), chunks=len(rec.chunks)))


def phased(name: str):
    """Run a method of an object that has a ``tracer`` inside phase
    ``name``."""
    def deco(method):
        @functools.wraps(method)
        def wrapped(self, *args, **kwargs):
            with self.tracer.phase(name):
                return method(self, *args, **kwargs)
        return wrapped
    return deco


class _Step:
    __slots__ = ("tr", "t0", "ann")

    def __init__(self, tr: EngineTracer):
        self.tr = tr

    def __enter__(self):
        tr = self.tr
        n = tr.steps
        tr.steps += 1
        self.ann = ()
        if tr.on:
            tr._rec = StepRecord(n)
            self.ann = (jax.profiler.StepTraceAnnotation("engine_step",
                                                         step_num=n),
                        jax.profiler.TraceAnnotation("engine.step", step=n))
            for a in self.ann:
                a.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = perf_counter()
        tr = self.tr
        tr.step_s += t1 - self.t0
        for a in reversed(self.ann):
            a.__exit__(*exc)
        rec, tr._rec = tr._rec, None
        if rec is not None:
            tr.records.append(rec)


class _Phase:
    __slots__ = ("tr", "name", "stats", "ann", "t0", "inner", "dt")

    def __init__(self, tr: EngineTracer, name: str,
                 stats: Optional[dict] = None):
        self.tr, self.name, self.stats = tr, name, stats

    def __enter__(self):
        tr = self.tr
        self.ann = None
        if tr.on:
            self.ann = jax.profiler.TraceAnnotation(PHASES[self.name],
                                                    **(self.stats or {}))
            self.ann.__enter__()
        self.inner = 0.0
        tr._open.append(self)
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        self.dt = dt = perf_counter() - self.t0
        tr = self.tr
        tr._open.pop()
        if tr._open:
            tr._open[-1].inner += dt
        tr.phase_s[self.name] += dt - self.inner
        if self.ann is not None:
            self.ann.__exit__(*exc)
