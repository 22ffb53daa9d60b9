"""The harness end to end on the CPU at the tiny size, past its look for
a chip: a sound run comes out correct, and each fault a one-chip serving
cell can have, planted in the timed path, makes ``correct`` false.

The faults: a step that leaves its state unchanged (no K/V reaches the
pages), half of the decode batch left out (its rows get the other
half's logits), and a token altered where it is produced.  The fourth
fault of the contract, an exchange between chips left out, has no
place in a one-chip cell.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import correctness, run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CDOC = json.load(open(DATA / "tiny-qwen3.json"))
MIX = json.load(open(DATA / "tiny-requests.json"))
# a float32 program serves the float32 reference's own argmax; the
# int8 and fp8 controls read well above this (test_control_fails_the_limit)
LIMITS = {"served_logit_gap_max": 1e-4, "min_served_tokens": 30}
SECONDS = 3.0


def tiny_run(seed=11, faults=(), controls=()):
    out = run.run_cell("tiny", {"chips": 1}, CDOC, MIX, LIMITS, seed,
                       SECONDS, False, jax.devices()[0], time.perf_counter(),
                       faults=faults, controls=controls)
    return out


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen3-0.6b.chat-sessions", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


@pytest.fixture(scope="module")
def sound():
    return tiny_run(seed=11, controls=("int8", "fp8"))


def test_sound_run_is_correct(sound):
    out = dict(sound)
    out.pop("_diag")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    e2e = json.load(open(ROOT / "BENCHMARK.json"))["end_to_end"]
    assert list(out["metrics"]) == [m["name"] for m in e2e]


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_control_fails_the_limit(sound, quant):
    r = sound["_diag"]["readings"]
    assert r["served_gap_max"] <= LIMITS["served_logit_gap_max"]
    assert r[f"control_{quant}_gap_max"] > LIMITS["served_logit_gap_max"]
    # decided as the benchmark decides, the control's tokens in the
    # served tokens' place
    correct, checks = correctness.decide(
        r[f"control_{quant}_gap_max"], r["served_tokens"], LIMITS)
    assert not correct, checks


def state_unchanged(bench):
    """Every step returns the pages as they came: no K/V is written."""
    import repro.engine.paged_model as PM
    orig = PM._write_kv
    PM._write_kv = lambda pages, pidx, slot, x: pages
    bench._restore = lambda: setattr(PM, "_write_kv", orig)


def half_batch_left_out(bench):
    """The second half of the decode rows get the first half's logits."""
    r = bench.engine.runner

    def halve(logits, n):
        h = -(-n // 2)
        return jnp.concatenate([logits[:h], logits[:n - h], logits[n:]])

    mixed, decode = r.run_mixed, r.run_decode

    def run_mixed(out):
        d, p = mixed(out)
        return halve(d, len(out.decode)), p
    r.run_mixed = run_mixed
    r.run_decode = lambda reqs: halve(decode(reqs), len(reqs))


def token_altered(bench):
    """Every fifth sampling call emits a different first token."""
    r = bench.engine.runner
    sample, calls = r.sample, [0]
    vocab = CDOC["vocab_size"]

    def altered(logits, reqs, positions=None):
        toks = sample(logits, reqs, positions=positions)
        calls[0] += 1
        if calls[0] % 5 == 0:
            toks = toks.copy()
            toks[0] = (toks[0] + 1) % vocab
        return toks
    r.sample = altered


@pytest.mark.parametrize("fault", [half_batch_left_out, token_altered,
                                   state_unchanged])
def test_fault_makes_run_incorrect(fault):
    planted = []

    def plant(bench):
        fault(bench)
        planted.append(bench)
    # the state fault changes what the step programs trace, so they are
    # traced afresh with it and again after it
    retrace = fault is state_unchanged
    if retrace:
        jax.clear_caches()
    try:
        out = tiny_run(seed=13, faults=(plant,))
    finally:
        for b in planted:
            getattr(b, "_restore", lambda: None)()
        if retrace:
            jax.clear_caches()
    assert not out["correct"], out["checks"]


def test_trace_slice_waits_for_a_prefill():
    """A slice that opens after the window's prefills are done stays
    open until a step carries a prefill chunk (the arrival at 1.5 s),
    and one that meets none closes at its cap."""
    from chipbench.bench import Bench
    from chipbench.traffic import Arrival

    bench = Bench(CDOC, 3, jax.devices()[0])
    bench.warm_programs(MIX["context_cap"])

    def arrival(due):
        return Arrival(due, list(range(1, 21)), 8, "u", None, 10.0)

    for later, lo, hi in (([arrival(1.5)], 1.5, 3.0), ([], 3.0, 9.0)):
        res = bench.serve([arrival(0.0)] + later, 0.0, 0.5, 5.0,
                          (0.6, 0.7, 3.0))
        t = res.trace
        assert lo <= t["t_stop"] - res.window_open < hi
        traced = [s for s in res.steps if t["t_start"] <= s.t < t["t_stop"]]
        assert any(s.chunks for s in traced) == bool(later)
