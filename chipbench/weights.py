"""Seeded random weights, made on the device in one jitted call.

The tree is the architecture module's ``specs`` (``chipbench/arch``):
the layout the program's paged engine takes, in the served dtype, leaf
``i`` of the flattened tree drawn from ``fold_in(key, i)``.  The
benchmark makes the weights, not the program: the plain reference reads
the same arrays, and nothing the program computed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import arch


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def shapes(c: dict):
    return jax.tree.map(lambda s: s[0], arch.load(c).specs(c),
                        is_leaf=_is_spec)


@functools.partial(jax.jit, static_argnames=("spec_items", "dtype"))
def _make(key, *, spec_items, dtype):
    out = []
    for i, (shape, std) in enumerate(spec_items):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out.append((x * std).astype(dtype))
    return out


def make(c: dict, key, dtype=jnp.bfloat16):
    """All weights from ``key`` in one jitted call on the default device."""
    tree = arch.load(c).specs(c)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)
    arrays = _make(key, spec_items=tuple(leaves), dtype=jnp.dtype(dtype))
    return jax.tree.unflatten(treedef, arrays)
