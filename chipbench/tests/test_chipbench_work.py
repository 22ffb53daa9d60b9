"""chipbench/work.py against counts worked by hand at the published
widths of Qwen3-0.6B and Qwen2-1.5B."""
import json
from pathlib import Path

import pytest

from chipbench import work
from chipbench.arch import qwen_dense

CONF = Path(__file__).resolve().parents[1] / "configs"
Q3 = json.load(open(CONF / "qwen3-0.6b.json"))
Q2 = json.load(open(CONF / "qwen2-1.5b.json"))
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_decode_attention_qwen3():
    # 16 heads x 128 dims x 4 ops x 1000 tokens; K+V: 2 x 1000 x 8 x 128
    # bf16, plus q and o: 2 x 16 x 128 bf16
    f, b = work.decode_attention(Q3, [1000])
    assert f == 4 * 16 * 128 * 1000 == 8_192_000
    assert b == 2 * (2 * 1000 * 8 * 128 + 2 * 16 * 128) == 4_104_192


def test_prefill_attention_qwen2():
    # chunk of 256 after 768: keys seen sum to 256*768 + 256*257/2
    f, b = work.prefill_attention(Q2, [(768, 256)])
    assert f == 4 * 12 * 128 * (256 * 768 + 256 * 257 / 2)
    assert b == 2 * (2 * 1024 * 2 * 128 + 2 * 256 * 12 * 128)


@pytest.mark.parametrize("conf, per_layer", [
    # q 1024x2048, k and v 1024x1024 each, o 2048x1024, MLP 3x1024x3072
    (Q3, 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072),
    # q 1536x1536, k and v 1536x256 each, o 1536x1536, MLP 3x1536x8960
    (Q2, 1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536 + 3 * 1536 * 8960),
])
def test_matmul_params(conf, per_layer):
    assert qwen_dense.matmul_params_per_layer(conf) == per_layer


def test_step_flops_qwen3_two_rows_one_chunk():
    dec, chunks = [100, 300], [(0, 256)]
    dense = 2 * 258 * 28 * qwen_dense.matmul_params_per_layer(Q3)
    attn = 28 * (4 * 16 * 128 * 400 + 4 * 16 * 128 * (256 * 257 / 2))
    unembed = 2 * 3 * 1024 * 151_936
    assert work.step_flops(Q3, dec, chunks) == pytest.approx(
        dense + attn + unembed, rel=1e-12)


def test_roofline_bound():
    t, bound = work.roofline_s(*work.decode_attention(Q3, [2048]), PEAK)
    assert bound == "memory"
    assert t == pytest.approx(2 * (2 * 2048 * 8 * 128 + 2 * 16 * 128)
                              / 819e9)
    t, bound = work.roofline_s(197e12, 1.0, PEAK)
    assert (t, bound) == (1.0, "compute")
