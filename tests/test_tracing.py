"""The engine's tracer (repro.engine.tracing): spans and step records when
on, nothing but the cumulative counters when off, the same greedy
tokens either way, one record per step on every step path, and a
request's first admission kept across preemption."""
import itertools

import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.engine import (EngineConfig, InferenceEngine, Request,
                          SamplingParams)
from repro.engine.tracing import PHASES

REP_PROMPT = [5, 6, 7, 8] * 6
PATHS = {"sync": {}, "two_phase": {"mixed_batching": False},
         "async_loop": {"async_loop": True}, "spec_tokens": {"spec_tokens": 4}}


def _engine(**kw):
    cfg = get_reduced_config("qwen3-0.6b")
    ecfg = dict(page_size=8, num_pages=64, max_batch=4, max_pages_per_seq=16,
                chunk_size=16)
    ecfg.update(kw)
    return cfg, InferenceEngine(cfg, EngineConfig(**ecfg), seed=0)


def _prompts():
    rng = np.random.default_rng(3)
    return [REP_PROMPT, rng.integers(0, 64, 20).tolist(),
            [9, 9, 3, 9, 9, 3, 9, 9]]


def _serve(eng, max_new=10):
    for i, p in enumerate(_prompts()):
        eng.submit(Request(request_id=f"r{i}", prompt_tokens=list(p),
                           sampling=SamplingParams(max_new_tokens=max_new)))
    eng.run_until_idle()
    return {r.request_id: list(r.output_tokens) for r in eng.finished}


class _Annotations:
    """Stands in for jax.profiler's annotations and notes each one."""

    def __init__(self, monkeypatch):
        self.opened = []
        log = self.opened

        class Ann:
            def __init__(self, name, **stats):
                log.append((name, stats))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Ann)


@pytest.fixture(scope="module")
def greedy_off():
    """Tracer off: tokens, and what it left behind."""
    _, eng = _engine()
    return _serve(eng), eng


def test_off_leaves_counters_only(greedy_off, monkeypatch):
    _, eng = greedy_off
    tr = eng.tracer
    assert not tr.on and len(tr.records) == 0
    assert tr.steps > 0 and tr.step_s > 0
    assert set(tr.phase_s) == set(PHASES)
    assert tr.phase_s["launch"] > 0 and tr.phase_s["schedule"] > 0
    assert sum(tr.phase_s.values()) <= tr.step_s
    m = eng.metrics()
    assert m.steps == tr.steps and m.step_host_s == tr.phase_s
    # a second run opens no annotation at all
    anns = _Annotations(monkeypatch)
    _, eng2 = _engine()
    _serve(eng2, max_new=4)
    assert anns.opened == [] and len(eng2.tracer.records) == 0


# the runner call that launches each path's own step program
LAUNCHER = {"sync": "run_mixed", "two_phase": "run_prefill",
            "async_loop": "run_decode_async", "spec_tokens": "run_spec"}
PROGRAMS = {"run_mixed": {"mixed_step"}, "run_prefill": {"prefill_step"},
            "run_decode_async": {"decode_batch"},
            "run_spec": {"spec_decode_step", "spec_mixed_step"}}


@pytest.mark.parametrize("path", list(PATHS))
def test_one_record_per_step(greedy_off, path):
    """On: the same greedy tokens as off, and every step leaves one record
    whose live chunks are those of the schedule it ran (the last one the
    step made: the async loop re-plans after a drain), and whose program
    is the one its path launched."""
    base, _ = greedy_off
    _, eng = _engine(**PATHS[path])
    eng.tracer.enable()
    planned, launched = {}, {}
    schedule = eng.sched.schedule

    def noted(now):
        out = schedule(now)
        planned[len(eng.tracer.records)] = (
            out.mode != "idle",
            [(w.start, w.chunk_len) for w in out.prefills if w.chunk_len])
        return out
    eng.sched.schedule = noted
    name = LAUNCHER[path]
    call = getattr(eng.runner, name)

    def launch(*a, **kw):
        launched[eng.tracer.steps - 1] = name
        return call(*a, **kw)
    setattr(eng.runner, name, launch)
    tr, step, over = eng.tracer, eng.step, []

    def timed():        # phase seconds beyond the step's own wall time
        p0, s0 = sum(tr.phase_s.values()), tr.step_s
        n = step()
        over.append(sum(tr.phase_s.values()) - p0 - (tr.step_s - s0))
        return n
    eng.step = timed
    assert _serve(eng) == base
    recs = list(eng.tracer.records)
    assert [r.step for r in recs] == list(range(eng.tracer.steps))
    assert len(recs) == len(planned)
    for rec in recs:
        busy, chunks = planned[rec.step]
        assert rec.chunks == chunks and bool(rec.program) == busy
        if rec.step in launched:
            assert rec.program in PROGRAMS[launched[rec.step]]
    assert launched                 # the path's own steps were recorded
    assert max(over) <= 1e-9


def test_spans_when_on(monkeypatch):
    anns = _Annotations(monkeypatch)
    _, eng = _engine()
    eng.tracer.enable()
    _serve(eng, max_new=4)
    names = [n for n, _ in anns.opened]
    assert set(names) == ({"engine_step", "engine.step"}
                          | set(PHASES.values()))
    steps = [s["step_num"] for n, s in anns.opened if n == "engine_step"]
    assert steps == list(range(eng.tracer.steps))
    launch = [s for n, s in anns.opened if n == "runner.launch"]
    recs = {r.step: r for r in eng.tracer.records}
    for s in launch:
        rec = recs[s["step"]]
        assert s["program"] == rec.program
        assert s["dec_width"] % 4 == 0 and s["pre_width"] % 4 == 0
        assert s["chunks"] == len(rec.chunks)


def test_admit_time_survives_preemption():
    cfg = get_reduced_config("qwen3-0.6b")
    tick = itertools.count(1)
    eng = InferenceEngine(cfg, EngineConfig(
        page_size=8, num_pages=64, max_batch=4, max_pages_per_seq=16,
        chunk_size=16), seed=0, clock=lambda: float(next(tick)))
    req = Request(prompt_tokens=list(REP_PROMPT),
                  sampling=SamplingParams(max_new_tokens=6))
    eng.submit(req)
    while len(req.output_tokens) < 2:
        eng.step()
    first = req.admit_time
    assert first == req.schedule_time
    eng.sched.preempt(req, eng.clock())       # drop and recompute
    eng.run_until_idle()
    assert req.preempt_count == 1 and len(req.output_tokens) == 6
    assert req.admit_time == first < req.schedule_time
