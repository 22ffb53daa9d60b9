"""chipbench/reference.py against the program's paged path (``impl="ref"``
on the CPU, tiny widths): a prompt prefilled in chunks through the pages,
then greedy decode steps through the pages, logits compared at every
position that produced a token."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arch, reference, weights

DATA = Path(__file__).resolve().parent / "data"
PAGE, CHUNK, NB = 8, 16, 8


def program_logits(cdoc, w, prompt, n_decode):
    from repro.configs import get_reduced_config
    from repro.engine import paged_model as PM
    cfg = get_reduced_config(cdoc["program"]["model"])
    pool = PM.init_pool(cfg, NB + 1, PAGE, jnp.float32)
    bt = jnp.arange(NB, dtype=jnp.int32)[None]
    kw = dict(cfg=cfg, page_size=PAGE, impl="ref")
    for start in range(0, len(prompt), CHUNK):
        chunk = prompt[start:start + CHUNK]
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :len(chunk)] = chunk
        logits, pool = PM.prefill_step(w, pool, jnp.asarray(toks), bt,
                                       jnp.int32(start),
                                       jnp.int32(len(chunk)), **kw)
    out, seq = [np.asarray(logits[0])], list(prompt)
    for _ in range(n_decode):
        seq.append(int(np.argmax(out[-1])))
        logits, pool = PM.decode_batch(
            w, pool, jnp.asarray([seq[-1]], jnp.int32),
            jnp.asarray([len(seq) - 1], jnp.int32), bt,
            jnp.asarray([True]), **kw)
        out.append(np.asarray(logits[0]))
    return np.stack(out), seq


@pytest.mark.parametrize("conf", ["tiny-qwen3", "tiny-qwen2"])
def test_reference_matches_paged_prefill_then_decode(conf):
    cdoc = json.load(open(DATA / f"{conf}.json"))
    w = weights.make(cdoc, jax.random.PRNGKey(3), jnp.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cdoc["vocab_size"], 37).tolist()
    got, seq = program_logits(cdoc, w, prompt, n_decode=6)
    toks = np.zeros(arch.load(cdoc).BLOCK_Q, np.int32)
    toks[:len(seq)] = seq
    sel = len(prompt) - 1 + np.arange(len(got))
    want = np.asarray(reference.logits(cdoc, w, toks, sel))
    scale = np.abs(want).max()
    assert scale > 0.1
    assert np.abs(got - want).max() <= 1e-4 * scale


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_control_departs_from_reference(quant):
    cdoc = json.load(open(DATA / "tiny-qwen3.json"))
    w = weights.make(cdoc, jax.random.PRNGKey(4), jnp.float32)
    toks = np.random.default_rng(1).integers(
        0, cdoc["vocab_size"], arch.load(cdoc).BLOCK_Q).astype(np.int32)
    sel = np.arange(64)
    ref = np.asarray(reference.logits(cdoc, w, toks, sel))
    low = np.asarray(reference.logits(cdoc, w, toks, sel, quant=quant))
    err = np.abs(low - ref).max() / np.abs(ref).max()
    assert 1e-4 < err < 0.5
