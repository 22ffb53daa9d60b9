"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the program's paged engine takes (``embedding``,
``run_0`` with every layer stacked on a leading axis, ``final_norm``), in
the served dtype.  The benchmark makes the weights, not the program: the
plain reference reads the same arrays, and nothing the program computed.

RMSNorm weights are stored as offsets from 1 (the program computes
``x * (1 + w)``); they and the QKV biases are random and non-zero, so
the comparison with the reference sees whether they are applied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EMBED_STD = 0.02        # embedding and tied unembedding
NORM_STD = 0.1          # RMSNorm offsets from 1
BIAS_STD = 0.2          # q/k/v projection biases


def dims(c: dict) -> dict:
    return dict(L=c["num_hidden_layers"], d=c["hidden_size"],
                H=c["num_attention_heads"], Hkv=c["num_key_value_heads"],
                D=c["head_dim"], f=c["intermediate_size"], V=c["vocab_size"])


def specs(c: dict) -> dict:
    """Leaf -> (shape, std) in the program's parameter layout."""
    n = dims(c)
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


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def shapes(c: dict):
    return jax.tree.map(lambda s: s[0], specs(c), is_leaf=_is_spec)


@functools.partial(jax.jit, static_argnames=("spec_items", "dtype"))
def _make(key, *, spec_items, dtype):
    out = []
    for i, (shape, std) in enumerate(spec_items):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out.append((x * std).astype(dtype))
    return out


def make(c: dict, key, dtype=jnp.bfloat16):
    """All weights from ``key`` in one jitted call on the default device."""
    tree = specs(c)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)
    arrays = _make(key, spec_items=tuple(leaves), dtype=jnp.dtype(dtype))
    return jax.tree.unflatten(treedef, arrays)
