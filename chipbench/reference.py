"""Plain float32 reference of the dense Qwen decoder, and its low-precision
control.

Written from the published architecture (Qwen2: arXiv:2407.10671 and
hf:Qwen/Qwen2-1.5B; Qwen3: hf:Qwen/Qwen3-0.6B) and independent of the
program: it imports nothing of ``repro`` and reads only the benchmark's
configuration file and the weights ``chipbench/weights.py`` made.  One
full causal forward pass over a whole sequence, no cache, no paging, no
batching, every matmul at ``Precision.HIGHEST``:

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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BLOCK_Q = 512


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
