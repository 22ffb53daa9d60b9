"""Seeded open-loop traffic, read from a mix file ``chipbench/traffic/<mix>.json``.

One generator serves every mix; a mix file only holds parameters.  Times
are seconds relative to the opening of the measured window.  A run's
traffic has three segments: warm-up ``[-warmup_s, 0)``, the window
``[0, window_s)`` and the drain ``[window_s, window_s + drain_s)``.  The
drain's arrivals keep the load steady while the window's requests finish;
only requests due inside the window are measured.

Every seed gets the same work.  The arrival times, the request sizes and
the shape of every session (rounds, turn and answer lengths, think
times) come from the mix's ``base_seed``; the run's ``--seed`` draws
every token id.  So runs on different seeds measure the same schedule of
the same amount of work, and the seed changes what each request says:
its prompt, what the prefix cache holds, what the model answers.

Two kinds of mix:

- ``requests``: independent single-turn requests at ``rate_rps``.  Each
  prompt is ``shared_prefix`` tokens common to the whole run (0: none)
  followed by its own tokens.
- ``sessions``: multi-round chat sessions opening at ``rate_rps``.  A
  session has 1 to ``rounds_max`` rounds (zipf ``rounds_zipf_s``,
  truncated and renormalised), each a user turn and an answer; round
  ``r`` is due a lognormal think time after round ``r - 1`` was due
  (open loop) and its prompt is the shared system prompt plus every
  earlier turn and answer plus the new turn.  The answers inside later
  prompts are the generator's own tokens, not the served ones: the
  scheduler caches prompt pages only, so either gives the same prefix
  hits, and the work stays fixed by the seed.  A session ends at the
  first round whose prompt and answer would pass ``context_cap``.
  Sessions open from ``history_s`` before the window; rounds due before
  the warm-up are not sent, so the window starts among sessions of every
  depth.

Token ids are uniform over the whole vocabulary.  Every request decodes
greedily (temperature 0), so the served tokens can be checked against
the reference.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass
class Arrival:
    due: float                  # seconds from the window's opening
    prompt: List[int]
    max_new: int
    user: str
    session: Optional[str]
    ttft_limit_s: float


def load_mix(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def seed_words(seed: int) -> List[int]:
    """``--seed`` as 32-bit words: any whole number, negative or wider
    than 64 bits, maps to a distinct entropy for numpy and JAX."""
    seed = int(seed)
    words = [1 if seed < 0 else 0]
    seed = abs(seed)
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        seed_words(seed) + [int(t) for t in tags]))


def lognormal_lengths(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths, lognormal with the stated mean and sigma,
    clipped to ``[min, max]``."""
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - sigma ** 2 / 2
    x = rng.lognormal(mu, sigma, size=n)
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def ttft_limit_s(mix: dict, prompt_len: int) -> float:
    lim = mix["limits"]
    return (lim["ttft_ms"]
            + lim.get("ttft_ms_per_prompt_token", 0.0) * prompt_len) / 1e3


def _arrival_times(base, n: int, lo: float, hi: float) -> np.ndarray:
    """``n`` arrival times in ``[lo, hi)``: exponential gaps from the
    base stream, scaled to the segment (a Poisson process given its
    count)."""
    if n <= 0:
        return np.zeros(0)
    gaps = base.exponential(1.0, size=n + 1)
    t = np.cumsum(gaps)[:n] / gaps.sum()
    return lo + (hi - lo) * t


def _segments(mix: dict, window_s: float):
    drain = float(mix["drain_s"])
    first = -float(mix.get("history_s", mix["warmup_s"]))
    return [(0, first, 0.0), (1, 0.0, window_s),
            (2, window_s, window_s + drain)]


def timeline(mix: dict, seed: int, window_s: float,
             vocab: int) -> List[Arrival]:
    """Every arrival of a run, ordered by due time."""
    if mix["kind"] == "requests":
        out = _requests(mix, seed, window_s, vocab)
    elif mix["kind"] == "sessions":
        out = _sessions(mix, seed, window_s, vocab)
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    warm = -float(mix["warmup_s"])
    out = [a for a in out if a.due >= warm]
    out.sort(key=lambda a: a.due)
    return out


def _requests(mix, seed, window_s, vocab) -> List[Arrival]:
    rng = rng_for(seed, 0)
    shared = rng.integers(0, vocab, int(mix.get("shared_prefix", 0)))
    shared = shared.tolist()
    out = []
    for idx, lo, hi in _segments(mix, window_s):
        n = int(round(float(mix["rate_rps"]) * (hi - lo)))
        base = rng_for(int(mix["base_seed"]), idx, n)
        plen = lognormal_lengths(base, mix["prompt"], n)
        olen = lognormal_lengths(base, mix["output"], n)
        due = _arrival_times(base, n, lo, hi)
        for k in range(n):
            p, o = int(plen[k]), int(olen[k])
            prompt = shared + rng.integers(0, vocab, p).tolist()
            uid = f"r{idx}.{k}"
            out.append(Arrival(float(due[k]), prompt, o, uid, None,
                               ttft_limit_s(mix, len(prompt))))
    return out


def _rounds(base, spec: dict, n: int) -> np.ndarray:
    k = np.arange(1, int(spec["rounds_max"]) + 1)
    p = k ** -float(spec["rounds_zipf_s"])
    return base.choice(k, size=n, p=p / p.sum())


def _sessions(mix, seed, window_s, vocab) -> List[Arrival]:
    rng = rng_for(seed, 0)
    system = rng.integers(0, vocab, int(mix["shared_prefix"])).tolist()
    spec = mix["sessions"]
    cap = int(mix["context_cap"])
    think_sigma = float(spec["think_sigma"])
    think_mu = math.log(float(spec["think_mean_s"])) - think_sigma ** 2 / 2
    end = window_s + float(mix["drain_s"])
    out = []
    for idx, lo, hi in _segments(mix, window_s):
        n = int(round(float(mix["rate_rps"]) * (hi - lo)))
        base = rng_for(int(mix["base_seed"]), idx, n)
        rounds = _rounds(base, spec, n)
        m = int(spec["rounds_max"])
        turn = lognormal_lengths(base, mix["prompt"], n * m).reshape(n, m)
        answer = lognormal_lengths(base, mix["output"], n * m).reshape(n, m)
        think = base.lognormal(think_mu, think_sigma, size=(n, m))
        starts = _arrival_times(base, n, lo, hi)
        for j in range(n):
            sid = f"s{idx}.{j}"
            ctx = list(system)
            due = float(starts[j])
            for r in range(int(rounds[j])):
                user = rng.integers(0, vocab, int(turn[j, r])).tolist()
                prompt = ctx + user
                if len(prompt) + int(answer[j, r]) > cap or due >= end:
                    break
                out.append(Arrival(due, prompt, int(answer[j, r]), sid,
                                   sid, ttft_limit_s(mix, len(prompt))))
                ctx = prompt + rng.integers(0, vocab,
                                            int(answer[j, r])).tolist()
                due += float(think[j, r])
    return out
