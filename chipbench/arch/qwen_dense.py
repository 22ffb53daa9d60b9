"""The dense Qwen decoder with tied embeddings (Qwen2, Qwen3): its
weight layout, plain reference, operation and byte counts, kernels, and
the program's config for it.  The interface is ``chipbench/arch``'s.

Reference, written from the published architecture (Qwen2:
arXiv:2407.10671 and hf:Qwen/Qwen2-1.5B; Qwen3: hf:Qwen/Qwen3-0.6B) and
independent of the program: ``logits`` imports nothing of ``repro`` and
reads only the configuration file and the weights ``chipbench/weights.py``
made (only ``program_config`` and ``check`` touch the program, and only
its config).  One full causal forward pass over a whole sequence, no
cache, no paging, no batching, every matmul at ``Precision.HIGHEST``:

    x = E[tokens]
    per layer:  h = RMSNorm(x) * g1
                q, k, v = h Wq (+ bq), h Wk (+ bk), h Wv (+ bv)
                Qwen3: q, k = RMSNorm(q) * gq, RMSNorm(k) * gk, per head
                q, k = RoPE(q), RoPE(k)      (rotate-half, base rope_theta)
                x = x + softmax(q k^T / sqrt(D), causal) v Wo   (GQA)
                h = RMSNorm(x) * g2
                x = x + (silu(h Wg) * (h Wi)) Wout
    logits = (RMSNorm(x) * gf) E^T              (tied embeddings)

Each RMSNorm weight ``g`` is 1 plus the stored offset.  Attention runs in
blocks of query rows so an 8,192-token sequence fits beside the weights.

``quant`` switches the control on: every projection and the embedding
rounded per output channel to int8 (or fp8 e4m3), the low-precision
serving path a later change might be tempted by; norms and biases stay
float32.  Trailing padding never changes earlier rows (causal mask), so
sequences are padded to one length and compiled once.

Weights: the tree the program's paged engine takes (``embedding``,
``run_0`` with every layer stacked on a leading axis, ``final_norm``).
RMSNorm offsets and the QKV biases are random and non-zero, so the
comparison with the reference sees whether they are applied.

Operations and bytes, over live tokens only (a multiply-add is two
operations):

- Decode attention, one layer, one row attending over ``n`` tokens:
  ``4 H D n`` operations (scores and values); the row's K and V read
  once, ``2 n Hkv D`` elements, plus its query and output.
- Chunked-prefill attention, one layer, a chunk of ``c`` tokens after
  ``s`` already in the pages: query ``j`` sees ``s + j + 1`` keys, so
  ``4 H D (c s + c (c + 1) / 2)`` operations; K and V of ``s + c``
  tokens read once, plus the chunk's queries and outputs.
- The whole step: every projection and MLP matmul for each live token,
  the attention above in every layer, and the unembedding of each row
  that yields logits (every decode row, one per chunk).  Norms, RoPE
  and sampling are left out: a lower bound.
"""
from __future__ import annotations

import functools
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp

from chipbench.arch import cut

# kernels by the HLO op name they carry in the trace (their jitted
# wrapper's name: %paged_attention.13, %paged_prefill.15)
KERNELS = {"paged_attention": r"^paged_attention$",
           "paged_prefill": r"^paged_prefill$"}

HI = jax.lax.Precision.HIGHEST
BLOCK_Q = 512

EMBED_STD = 0.02        # embedding and tied unembedding
NORM_STD = 0.1          # RMSNorm offsets from 1
BIAS_STD = 0.2          # q/k/v projection biases


def widths(c: dict) -> dict:
    return dict(L=c["num_hidden_layers"], d=c["hidden_size"],
                H=c["num_attention_heads"], Hkv=c["num_key_value_heads"],
                D=c["head_dim"], f=c["intermediate_size"],
                V=c["vocab_size"], b=2)


# ------------------------------------------------------------ program
def program_config(cdoc: dict):
    """The program's config of this model, cut as the file says."""
    from repro.configs import get_config, get_reduced_config
    prog = cdoc["program"]
    cfg = (get_reduced_config(prog["model"])
           if prog.get("reduced") else get_config(prog["model"]))
    return cut(cfg, cdoc)


def check(cdoc: dict, cfg) -> None:
    """The program must run the configuration as the file states it."""
    want = {"n_layers": cdoc["num_hidden_layers"],
            "d_model": cdoc["hidden_size"],
            "n_heads": cdoc["num_attention_heads"],
            "n_kv_heads": cdoc["num_key_value_heads"],
            "head_dim": cdoc["head_dim"], "d_ff": cdoc["intermediate_size"],
            "vocab_size": cdoc["vocab_size"],
            "qk_norm": cdoc["qk_norm"], "qkv_bias": cdoc["attention_bias"],
            "tie_embeddings": cdoc["tie_word_embeddings"],
            "norm_eps": cdoc["rms_norm_eps"],
            "rope_theta": float(cdoc["rope_theta"])}
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad or cfg.family != "dense" or cfg.act != cdoc["hidden_act"]:
        raise ValueError(f"program config {cfg.name} departs from "
                         f"{cdoc['name']}: {bad}")


# ------------------------------------------------------------ weights
def specs(c: dict) -> dict:
    """Leaf -> (shape, std) in the program's parameter layout."""
    n = widths(c)
    L, d, H, Hkv, D, f, V = (n[k] for k in "L d H Hkv D f V".split())
    attn = {"wq": ((L, d, H, D), d ** -0.5),
            "wk": ((L, d, Hkv, D), d ** -0.5),
            "wv": ((L, d, Hkv, D), d ** -0.5),
            "wo": ((L, H, D, d), (H * D) ** -0.5)}
    if c["attention_bias"]:
        attn.update(bq=((L, H, D), BIAS_STD), bk=((L, Hkv, D), BIAS_STD),
                    bv=((L, Hkv, D), BIAS_STD))
    if c["qk_norm"]:
        attn.update(q_norm=((L, D), NORM_STD), k_norm=((L, D), NORM_STD))
    layer = {"ln1": ((L, d), NORM_STD), "attn": attn,
             "ln2": ((L, d), NORM_STD),
             "mlp": {"w_gate": ((L, d, f), d ** -0.5),
                     "w_in": ((L, d, f), d ** -0.5),
                     "w_out": ((L, f, d), f ** -0.5)}}
    return {"embedding": ((V, d), EMBED_STD), "run_0": layer,
            "final_norm": ((d,), NORM_STD)}


# ---------------------------------------------------------- reference
def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, pos, theta):
    """x: (S, heads, D); rotate-half RoPE at positions ``pos`` (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _quantize(w, reduce_axes, quant):
    """Round ``w`` per output channel (max over ``reduce_axes``) and
    return the dequantized float32 array."""
    if quant == "none":
        return w
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    if quant == "int8":
        q = jnp.clip(jnp.round(w / scale), -127, 127)
    else:
        q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _attention(q, k, v):
    """Causal GQA over the whole sequence, in blocks of query rows.
    q: (S, H, D); k, v: (S, Hkv, D)."""
    s, h, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    nb = s // BLOCK_Q
    qb = q.reshape(nb, BLOCK_Q, hkv, g, d) * (d ** -0.5)
    kpos = jnp.arange(s)

    def block(args):
        i, qi = args
        logits = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI)
        qpos = i * BLOCK_Q + jnp.arange(BLOCK_Q)
        mask = kpos[None, :] <= qpos[:, None]
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(s, h, d)


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _logits(w, tokens, sel, *, arch, quant):
    eps, theta, qk_norm, bias = arch
    f32 = lambda a: a.astype(jnp.float32)          # noqa: E731
    emb = _quantize(f32(w["embedding"]), (1,), quant)
    x = emb[tokens]
    pos = jnp.arange(tokens.shape[0])

    def layer(x, lw):
        a, m = lw["attn"], lw["mlp"]
        h = _rms(x, f32(lw["ln1"]), eps)
        wq = _quantize(f32(a["wq"]), (0,), quant)
        wk = _quantize(f32(a["wk"]), (0,), quant)
        wv = _quantize(f32(a["wv"]), (0,), quant)
        q = jnp.einsum("sd,dhk->shk", h, wq, precision=HI)
        k = jnp.einsum("sd,dhk->shk", h, wk, precision=HI)
        v = jnp.einsum("sd,dhk->shk", h, wv, precision=HI)
        if bias:
            q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
        if qk_norm:
            q = _rms(q, f32(a["q_norm"]), eps)
            k = _rms(k, f32(a["k_norm"]), eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q, k, v)
        wo = _quantize(f32(a["wo"]), (0, 1), quant)
        x = x + jnp.einsum("shk,hkd->sd", o, wo, precision=HI)
        h = _rms(x, f32(lw["ln2"]), eps)
        wg = _quantize(f32(m["w_gate"]), (0,), quant)
        wi = _quantize(f32(m["w_in"]), (0,), quant)
        wout = _quantize(f32(m["w_out"]), (0,), quant)
        gate = jnp.einsum("sd,df->sf", h, wg, precision=HI)
        up = jnp.einsum("sd,df->sf", h, wi, precision=HI)
        x = x + jnp.einsum("sf,fd->sd", jax.nn.silu(gate) * up, wout,
                           precision=HI)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["run_0"])
    x = _rms(x[sel], f32(w["final_norm"]), eps)
    return jnp.einsum("nd,vd->nv", x, emb, precision=HI)


def arch_key(c: dict) -> tuple:
    return (float(c["rms_norm_eps"]), float(c["rope_theta"]),
            bool(c["qk_norm"]), bool(c["attention_bias"]))


def logits(c: dict, w, tokens, sel, quant: str = "none"):
    """Reference logits (n, V) float32 at rows ``sel`` of the sequence
    ``tokens`` (length a multiple of the query block)."""
    return _logits(w, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(sel, jnp.int32), arch=arch_key(c),
                   quant=quant)


# ------------------------------------------------- operations and bytes
def decode_attention(c: dict, lengths: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of one decode-attention call (one layer)."""
    w = widths(c)
    flops = nbytes = 0.0
    for n in lengths:
        flops += 4.0 * w["H"] * w["D"] * n
        nbytes += w["b"] * (2.0 * n * w["Hkv"] * w["D"] + 2.0 * w["H"] * w["D"])
    return flops, nbytes


def prefill_attention(c: dict, chunks: Iterable[Tuple[int, int]]
                      ) -> Tuple[float, float]:
    """(operations, bytes) of one chunked-prefill attention call (one
    layer); ``chunks`` holds (tokens before the chunk, chunk length)."""
    w = widths(c)
    flops = nbytes = 0.0
    for s, cl in chunks:
        flops += 4.0 * w["H"] * w["D"] * (cl * s + cl * (cl + 1) / 2.0)
        nbytes += w["b"] * (2.0 * (s + cl) * w["Hkv"] * w["D"]
                            + 2.0 * cl * w["H"] * w["D"])
    return flops, nbytes


def matmul_params_per_layer(c: dict) -> int:
    w = widths(c)
    d, H, Hkv, D, f = w["d"], w["H"], w["Hkv"], w["D"], w["f"]
    return d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * f


def step_flops(c: dict, dec_lens, chunks) -> float:
    """Model operations of one step with these live decode rows and
    prefill chunks."""
    w = widths(c)
    tokens = len(dec_lens) + sum(cl for _, cl in chunks)
    rows = len(dec_lens) + len(chunks)
    dense = 2.0 * tokens * w["L"] * matmul_params_per_layer(c)
    attn = w["L"] * (decode_attention(c, dec_lens)[0]
                     + prefill_attention(c, chunks)[0])
    return dense + attn + 2.0 * rows * w["d"] * w["V"]
