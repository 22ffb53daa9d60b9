"""Each traffic mix, those no cell runs yet included: seeded
determinism, fixed work across seeds, and every length inside the mix's
ranges."""
from pathlib import Path

import pytest

from chipbench import traffic

ROOT = Path(__file__).resolve().parents[2]
MIXES = sorted(p.stem for p in (ROOT / "chipbench" / "traffic").glob("*.json"))
VOCAB = 151_936
WINDOW_S = 30.0


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    mix = traffic.load_mix(name)
    a = traffic.timeline(mix, 2 ** 31 + 17, WINDOW_S, VOCAB)
    b = traffic.timeline(mix, 2 ** 31 + 17, WINDOW_S, VOCAB)
    assert [(x.due, x.prompt, x.max_new) for x in a] == \
        [(x.due, x.prompt, x.max_new) for x in b]
    c = traffic.timeline(mix, 5, WINDOW_S, VOCAB)
    assert [x.prompt for x in a] != [x.prompt for x in c]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_ranges(name):
    mix = traffic.load_mix(name)
    arr = traffic.timeline(mix, 123456789012, WINDOW_S, VOCAB)
    assert arr and all(-mix["warmup_s"] <= a.due
                       < WINDOW_S + mix["drain_s"] for a in arr)
    lo, hi = mix["output"]["min"], mix["output"]["max"]
    for a in arr:
        assert lo <= a.max_new <= hi
        assert len(a.prompt) + a.max_new <= mix["context_cap"]
        assert all(0 <= t < VOCAB for t in a.prompt[:64])
    if mix["kind"] == "requests":
        p = mix["prompt"]
        shared = mix.get("shared_prefix", 0)
        assert all(p["min"] <= len(a.prompt) - shared <= p["max"]
                   for a in arr)
    else:
        first = arr[0].prompt[:mix["shared_prefix"]]
        assert all(a.prompt[:mix["shared_prefix"]] == first for a in arr)
        rounds = {}
        for a in arr:
            rounds.setdefault(a.session, []).append(a)
        assert max(len(v) for v in rounds.values()) <= \
            mix["sessions"]["rounds_max"]
        for turns in rounds.values():     # each round extends the last
            for x, y in zip(turns, turns[1:]):
                assert y.prompt[:len(x.prompt)] == x.prompt
                assert y.due > x.due


@pytest.mark.parametrize("name", MIXES)
def test_work_fixed_across_seeds(name):
    """Seeds change what requests say, never when they come or how long
    they are."""
    mix = traffic.load_mix(name)
    shapes = []
    for seed in (1, 99):
        arr = traffic.timeline(mix, seed, WINDOW_S, VOCAB)
        shapes.append([(a.due, len(a.prompt), a.max_new) for a in arr])
    assert shapes[0] == shapes[1]


def test_seed_words_distinct():
    seen = {tuple(traffic.seed_words(s)) for s in
            (0, 1, -1, 2 ** 32, 2 ** 32 + 1, 2 ** 64 + 5)}
    assert len(seen) == 6
