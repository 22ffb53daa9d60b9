"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Nothing runs: each test lowers one Pallas kernel with ``interpret=False``
at Qwen3-0.6B serving widths (16 query heads, 8 KV heads, head_dim 128,
bf16, 16-token pages) against a ``v5e:2x2`` topology description and
compiles it with the TPU compiler, which raises what the chip's compiler
would raise (block-shape tiling, VMEM limits).  The topology is described
inside a fixture, never at import time, so every test worker collects the
same tests and only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_prefill import paged_prefill

H, HKV, D, PAGE, POOL_PAGES = 16, 8, 128, 16, 512
NB = 32                                 # block-table width: 512 tokens


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool(sharding):
    return _shape(sharding, (HKV, POOL_PAGES, PAGE, D))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles_for_v5e(one_chip):
    b = 8
    compiled = paged_attention.lower(
        _shape(one_chip, (b, H, D)), _pool(one_chip), _pool(one_chip),
        _shape(one_chip, (b, NB), jnp.int32),
        _shape(one_chip, (b,), jnp.int32), interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("block_q", [128, 8],
                         ids=["chunk", "speculative_verify"])
def test_paged_prefill_compiles_for_v5e(one_chip, block_q):
    b = 2
    compiled = paged_prefill.lower(
        _shape(one_chip, (b, block_q, H, D)), _pool(one_chip),
        _pool(one_chip), _shape(one_chip, (b, NB), jnp.int32),
        _shape(one_chip, (b,), jnp.int32), _shape(one_chip, (b,), jnp.int32),
        block_q=block_q, interpret=False).compile()
    _assert_kernel(compiled)


def test_flash_prefill_compiles_for_v5e(one_chip):
    s = 512
    compiled = flash_prefill.lower(
        _shape(one_chip, (1, s, H, D)), _shape(one_chip, (1, HKV, s, D)),
        _shape(one_chip, (1, HKV, s, D)), _shape(one_chip, (1,), jnp.int32),
        block_q=128, block_k=128, interpret=False).compile()
    _assert_kernel(compiled)
