"""The architecture modules (chipbench/arch): the dense Qwen block gives
bit for bit the weights, reference logits and counts it gave before it
moved behind the interface, and an architecture the harness has never
seen plugs in as a module of its own."""
import hashlib
import json
import re
import sys
import types
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arch, correctness, reference, weights, work
from chipbench import trace as T
from chipbench.arch import qwen_dense

HERE = Path(__file__).resolve().parent
CHIPBENCH = HERE.parent
DATA = HERE / "data"
CONF = CHIPBENCH / "configs"


def digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()
                          ).hexdigest()[:16]


# sha256 of each float32 leaf from PRNGKey(0), and of the reference's
# logits, as chipbench/weights.py and chipbench/reference.py gave them
# before the dense block moved into chipbench/arch/qwen_dense.py
SHARED = {"['embedding']": "b8c9e5a6450ce536",
          "['final_norm']": "34a41a1329643be0"}
PINNED_WEIGHTS = {
    "tiny-qwen3": dict(SHARED, **{
        "['run_0']['attn']['k_norm']": "fc98158a170c7032",
        "['run_0']['attn']['q_norm']": "435fac3f4fe21946",
        "['run_0']['attn']['wk']": "3555d46864af2e09",
        "['run_0']['attn']['wo']": "cfe4345a6e9e1841",
        "['run_0']['attn']['wq']": "8077115f17104bc0",
        "['run_0']['attn']['wv']": "5d5d42365a6d1651",
        "['run_0']['ln1']": "af6e3b82bf414a9c",
        "['run_0']['ln2']": "07d92bfa8ed356bb",
        "['run_0']['mlp']['w_gate']": "5a23d582ce2b4256",
        "['run_0']['mlp']['w_in']": "92d8b0b95a0a4ae8",
        "['run_0']['mlp']['w_out']": "a7a6eeb6a6bf5a52"}),
    "tiny-qwen2": dict(SHARED, **{
        "['run_0']['attn']['bk']": "dd9ddfb18030713d",
        "['run_0']['attn']['bq']": "afb34e16d13b30c5",
        "['run_0']['attn']['bv']": "ada9d357664956eb",
        "['run_0']['attn']['wk']": "ca96dca2bcdd9ae7",
        "['run_0']['attn']['wo']": "8077115f17104bc0",
        "['run_0']['attn']['wq']": "5d5d42365a6d1651",
        "['run_0']['attn']['wv']": "21b5cdd96756c437",
        "['run_0']['ln1']": "07d92bfa8ed356bb",
        "['run_0']['ln2']": "873090b2a828043a",
        "['run_0']['mlp']['w_gate']": "92d8b0b95a0a4ae8",
        "['run_0']['mlp']['w_in']": "2d58675909b1eb17",
        "['run_0']['mlp']['w_out']": "41ab2e7ba6e45f55"}),
}
PINNED_LOGITS = {("tiny-qwen3", "none"): "51f31f1b9b6fd54f",
                 ("tiny-qwen3", "fp8"): "1c5bceab9e6def50",
                 ("tiny-qwen2", "none"): "af8d7222ac3e6ace",
                 ("tiny-qwen2", "fp8"): "814fe226e20dc28e"}
# at published size the weights are fixed by the key and by the order,
# shape and scale of the leaves ``weights._make`` is given; these are
# pinned, since making the weights would not fit a test run
PINNED_SPEC_ITEMS = {"qwen3-0.6b": "0da13517647550dd",
                     "qwen2-1.5b": "675b8dedc7c1dd16"}


def tiny(name):
    return json.load(open(DATA / f"{name}.json"))


@pytest.mark.parametrize("name", sorted(PINNED_WEIGHTS))
def test_weights_are_the_parents(name):
    w = weights.make(tiny(name), jax.random.PRNGKey(0), jnp.float32)
    got = {jax.tree_util.keystr(p): digest(x)
           for p, x in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == PINNED_WEIGHTS[name]


@pytest.mark.parametrize("name", sorted(PINNED_SPEC_ITEMS))
def test_published_spec_items_are_the_parents(name):
    cdoc = json.load(open(CONF / f"{name}.json"))
    leaves, _ = jax.tree.flatten(arch.load(cdoc).specs(cdoc),
                                 is_leaf=weights._is_spec)
    got = hashlib.sha256(repr(leaves).encode()).hexdigest()[:16]
    assert got == PINNED_SPEC_ITEMS[name]


@pytest.mark.parametrize("name, quant", sorted(PINNED_LOGITS))
def test_reference_logits_are_the_parents(name, quant):
    cdoc = tiny(name)
    w = weights.make(cdoc, jax.random.PRNGKey(3), jnp.float32)
    toks = np.random.default_rng(5).integers(
        0, cdoc["vocab_size"], 512).astype(np.int32)
    got = reference.logits(cdoc, w, toks, np.arange(100, 164), quant=quant)
    assert digest(got) == PINNED_LOGITS[name, quant]


Q3 = json.load(open(CONF / "qwen3-0.6b.json"))
Q2 = json.load(open(CONF / "qwen2-1.5b.json"))


# the counts test_chipbench_work.py works by hand
@pytest.mark.parametrize("name, args, want", [
    ("decode_attention", (Q3, [1000]), (8_192_000, 4_104_192)),
    ("prefill_attention", (Q2, [(768, 256)]),
     (4 * 12 * 128 * (256 * 768 + 256 * 257 / 2),
      2 * (2 * 1024 * 2 * 128 + 2 * 256 * 12 * 128))),
    ("step_flops", (Q3, [100, 300], [(0, 256)]),
     2 * 258 * 28 * (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
                     + 3 * 1024 * 3072)
     + 28 * (4 * 16 * 128 * 400 + 4 * 16 * 128 * (256 * 257 / 2))
     + 2 * 3 * 1024 * 151_936),
])
def test_work_counts_are_the_parents(name, args, want):
    got = getattr(work, name)(*args)
    assert got == getattr(qwen_dense, name)(*args)
    assert got == pytest.approx(want, rel=1e-12)


# ------------------------------------------------- a module never seen
class Toy:
    """A test-only architecture: one dense layer and then expert layers
    (a soft mixture of every expert held), no attention, an untied
    head.  Its file cuts the depth and the experts held."""
    KERNELS = {"toy_mix": r"^toy_mix$"}
    BLOCK_Q = 16
    seen = []           # sequence lengths the reference was given

    @staticmethod
    def specs(c):
        L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
        f, fe, E = c["intermediate_size"], c["expert_width"], \
            c["n_routed_experts"]
        return {"embedding": ((V, d), 0.5),
                "run_0": {"ln": ((1, d), 0.1),
                          "mlp": {"w_in": ((1, d, f), d ** -0.5),
                                  "w_out": ((1, f, d), f ** -0.5)}},
                "run_1": {"ln": ((L - 1, d), 0.1),
                          "router": ((L - 1, d, E), d ** -0.5),
                          "experts": {"w_in": ((L - 1, E, d, fe), d ** -0.5),
                                      "w_out": ((L - 1, E, fe, d),
                                                fe ** -0.5)}},
                "final_norm": ((d,), 0.1), "head": ((V, d), d ** -0.5)}

    @staticmethod
    def logits(c, w, tokens, sel, quant="none"):
        Toy.seen.append(len(tokens))
        low = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) \
            if quant != "none" else (lambda a: a)

        def rms(x, g):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + 1e-6) * (1 + g)
        x = w["embedding"][jnp.asarray(tokens)]
        for i in range(w["run_0"]["ln"].shape[0]):
            m = w["run_0"]["mlp"]
            h = rms(x, w["run_0"]["ln"][i])
            x = x + jax.nn.relu(h @ low(m["w_in"][i])) @ low(m["w_out"][i])
        r = w["run_1"]
        for i in range(r["ln"].shape[0]):
            h = rms(x, r["ln"][i])
            p = jax.nn.softmax(h @ r["router"][i], -1)
            e = jnp.einsum("sd,edf->sef", h, low(r["experts"]["w_in"][i]))
            e = jnp.einsum("sef,efd->sed", jax.nn.relu(e),
                           low(r["experts"]["w_out"][i]))
            x = x + jnp.einsum("se,sed->sd", p, e)
        x = rms(x[jnp.asarray(sel)], w["final_norm"])
        return x @ low(w["head"]).T

    @staticmethod
    def decode_attention(c, lengths):
        return 0.0, 2.0 * c["hidden_size"] * sum(lengths)

    @staticmethod
    def prefill_attention(c, chunks):
        return 0.0, 2.0 * c["hidden_size"] * sum(s + n for s, n in chunks)

    @staticmethod
    def step_flops(c, dec_lens, chunks):
        tokens = len(dec_lens) + sum(n for _, n in chunks)
        d = c["hidden_size"]
        per_token = 2 * d * c["intermediate_size"] + 2 * (
            c["num_hidden_layers"] - 1) * d * c["n_routed_experts"] * (
            c["expert_width"] * 2 + 1)
        return 2.0 * tokens * per_token + 2.0 * (
            len(dec_lens) + len(chunks)) * d * c["vocab_size"]

    @staticmethod
    def program_config(cdoc):
        from repro.models.config import MoEConfig, ModelConfig
        pub = cdoc["published"]
        cfg = ModelConfig(
            name="toy", family="moe", n_layers=pub["num_hidden_layers"],
            d_model=cdoc["hidden_size"], n_heads=1, n_kv_heads=1,
            d_ff=cdoc["intermediate_size"], vocab_size=cdoc["vocab_size"],
            layer_pattern=("dense",) + ("moe",) * (
                pub["num_hidden_layers"] - 1),
            moe=MoEConfig(num_experts=pub["n_routed_experts"], top_k=2,
                          d_expert=cdoc["expert_width"]),
            tie_embeddings=False)
        return arch.cut(cfg, cdoc)

    @staticmethod
    def check(cdoc, cfg):
        if (cfg.n_layers, cfg.moe.num_experts, cfg.tie_embeddings) != (
                cdoc["num_hidden_layers"], cdoc["n_routed_experts"],
                cdoc["tie_word_embeddings"]):
            raise ValueError(f"program config {cfg.name} departs from "
                             f"{cdoc['name']}")


TOY = {"name": "toy", "arch": "toy_untied", "hidden_size": 32,
       "intermediate_size": 64, "expert_width": 16, "vocab_size": 48,
       "num_hidden_layers": 3, "n_routed_experts": 4,
       "tie_word_embeddings": False,
       "reduced": ["num_hidden_layers", "n_routed_experts"],
       "published": {"num_hidden_layers": 5, "n_routed_experts": 8},
       "program": {"overrides": {"num_hidden_layers": "n_layers",
                                 "n_routed_experts": "moe.num_experts"}}}


@pytest.fixture
def toy(monkeypatch):
    mod = types.ModuleType("chipbench.arch.toy_untied")
    for k, v in vars(Toy).items():
        if not k.startswith("__"):
            setattr(mod, k, v.__func__ if isinstance(v, staticmethod) else v)
    Toy.seen.clear()
    monkeypatch.setitem(sys.modules, "chipbench.arch.toy_untied", mod)
    return TOY


def test_toy_weights_and_reference(toy):
    assert arch.load(toy) is sys.modules["chipbench.arch.toy_untied"]
    w = weights.make(toy, jax.random.PRNGKey(1), jnp.float32)
    shapes = weights.shapes(toy)
    assert shapes["run_0"]["mlp"]["w_in"] == (1, 32, 64)
    assert shapes["run_1"]["experts"]["w_in"] == (2, 4, 32, 16)
    assert jax.tree.map(lambda a: a.shape, w) == shapes
    toks = np.arange(16) % 48
    got = np.asarray(reference.logits(toy, w, toks, [3, 7]))
    assert got.shape == (2, 48)
    # the head is its own leaf: the tied unembedding reads otherwise
    tied = dict(w, head=w["embedding"])
    assert not np.allclose(got, reference.logits(toy, tied, toks, [3, 7]))


def test_toy_gap_readings(toy):
    w = weights.make(toy, jax.random.PRNGKey(2), jnp.float32)
    prompt = [5, 9, 1, 30, 44, 2, 7, 7, 12, 40]
    seq, out = list(prompt), []
    for _ in range(5):                  # greedy through the reference
        toks = np.zeros(16, np.int32)
        toks[:len(seq)] = seq
        nxt = int(np.argmax(reference.logits(toy, w, toks,
                                             [len(seq) - 1])[0]))
        out.append(nxt)
        seq.append(nxt)
    Toy.seen.clear()
    sound = NS(prompt=prompt, output=out)
    r = correctness.gap_readings(toy, w, [sound], 20, 8, controls=("bf16",))
    assert r["served_gap_max"] == 0.0 and r["served_tokens"] == 5
    assert "control_bf16_gap_max" in r
    # padded to the module's own query block: 20 tokens to 32, not 512
    assert set(Toy.seen) == {32}
    wrong = NS(prompt=prompt, output=out[:2] + [(out[2] + 1) % 48] + out[3:])
    assert correctness.gap_readings(toy, w, [wrong], 20, 8)[
        "served_gap_max"] > 0.0


def test_toy_work_and_kernels(toy):
    dec, chunks = [10, 20], [(4, 8)]
    assert work.step_flops(toy, dec, chunks) == Toy.step_flops(
        toy, dec, chunks)
    assert work.decode_attention(toy, dec) == (0.0, 64.0 * 30)
    assert work.prefill_attention(toy, chunks) == (0.0, 64.0 * 12)
    ev = {"device": [{"plane": "/device:TPU:0", "line": T.OPS_LINE,
                      "name": "%toy_mix.7 = bf16[8]", "start": 0.0,
                      "dur": 2e6, "meta": ""}], "host": []}
    out = T.reduce(ev, 4e6, arch.load(toy).KERNELS)
    assert out["kernel_s"] == {"toy_mix": pytest.approx(2e-3)}


def test_toy_cut_reaches_the_program(toy):
    mod = arch.load(toy)
    cfg = mod.program_config(toy)
    assert cfg.n_layers == 3 and cfg.layer_pattern == ("dense", "moe", "moe")
    assert cfg.moe.num_experts == 4 and cfg.moe.d_expert == 16
    mod.check(toy, cfg)
    uncut = dict(toy, program={"overrides": {}})
    with pytest.raises(ValueError):
        mod.check(toy, mod.program_config(uncut))
    for key in toy["reduced"]:
        assert key in toy["published"] and key in toy["program"]["overrides"]


WIDTH_KEYS = ("intermediate_size", "num_key_value_heads", "head_dim",
              "qk_norm", "attention_bias")


def test_no_width_read_outside_arch():
    files = list(CHIPBENCH.glob("*.py")) + list(CHIPBENCH.glob("metrics/*.py"))
    pattern = re.compile(r"\b(%s)\b" % "|".join(WIDTH_KEYS))
    assert len(files) > 20
    assert [p.name for p in files if pattern.search(p.read_text())] == []
