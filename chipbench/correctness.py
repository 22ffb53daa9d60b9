"""Decides ``correct``: the tokens the timed path served, against the
plain reference.

Once the window has closed and the program's state is freed, a sample
of the window's finished requests is drawn from the seed, always with
the longest among them.  The reference runs once over each prompt
followed by its served tokens, and at the position that produced each
served token reads the gap by which that token's logit lies below the
reference's best.  The widest gap over the sample is compared with the
cell's limit.  Every request decodes greedily, so a sound path serves
the reference's best token up to rounding.

The control, in ``gap_readings``, reads the same positions with the
reference computed in a lower precision in the program's place: the
gap, in the float32 reference, of the token the control puts first.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from chipbench import arch, reference
from chipbench.traffic import rng_for

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
SAMPLE_REQUESTS = 8
MIN_SERVED_TOKENS = 100     # fewest served tokens a check may rest on


def load_limits(cell: str) -> dict:
    with open(LIMITS_DIR / f"{cell}.json") as f:
        return json.load(f)


def decide(gap, served: int, limits: dict) -> tuple:
    """``correct``, and the numbers compared, each beside its limit:
    the widest gap, over at least the limit's number of tokens."""
    limit = limits["served_logit_gap_max"]
    least = limits.get("min_served_tokens", MIN_SERVED_TOKENS)
    correct = gap is not None and gap <= limit and served >= least
    return bool(correct), {
        "served_logit_gap_max": {"value": gap, "limit": limit},
        "served_tokens": {"value": served, "limit": least}}


def pick(records, seed: int, n: int = SAMPLE_REQUESTS):
    """Finished window requests to compare: the longest (prompt plus
    served tokens), then others drawn from the seed."""
    done = [r for r in records if r.in_window and r.finished]
    if not done:
        return []
    done.sort(key=lambda r: (-(len(r.prompt) + len(r.output)), r.due))
    rest = done[1:]
    order = rng_for(seed, 7).permutation(len(rest))
    return [done[0]] + [rest[i] for i in order[:n - 1]]


def gap_readings(c: dict, weights, samples, seq_len: int, sel_len: int,
                 controls=()) -> dict:
    """Widest served-token gap, and for each control precision the
    widest gap of the token it puts first, over ``samples``."""
    block = arch.load(c).BLOCK_Q
    seq_len = -(-seq_len // block) * block
    served, ctl = [], {q: [] for q in controls}
    for r in samples:
        out = list(r.output)
        toks = list(r.prompt) + out[:-1]
        pad = np.zeros(seq_len, np.int32)
        pad[:len(toks)] = toks
        n = len(out)
        sel = np.zeros(sel_len, np.int32)
        sel[:n] = len(r.prompt) - 1 + np.arange(n)
        ref = np.asarray(reference.logits(c, weights, pad, sel))[:n]
        if not np.isfinite(ref).all():
            raise FloatingPointError("non-finite reference logits")
        best = ref.max(axis=-1)
        served.append(best - ref[np.arange(n), out])
        for q in controls:
            low = np.asarray(reference.logits(c, weights, pad, sel,
                                              quant=q))[:n]
            top = low.argmax(axis=-1)
            ctl[q].append(best - ref[np.arange(n), top])
    served = np.concatenate(served) if served else np.zeros(0)
    return {"served_gap_max": float(served.max()) if served.size else None,
            "served_tokens": int(served.size),
            **{f"control_{q}_gap_max": float(np.concatenate(v).max())
               for q, v in ctl.items() if v}}
