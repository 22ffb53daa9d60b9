"""Operations and bytes the algorithm needs, from shapes and live lengths.

Counted over live tokens only, so padding of the batch, of a chunk or of
the block-table grid shows as a lower share of the roofline.  A
multiply-add is two operations.  Widths come from the configuration
file's published keys.

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

from typing import Iterable, Tuple


def widths(c: dict) -> dict:
    return dict(L=c["num_hidden_layers"], d=c["hidden_size"],
                H=c["num_attention_heads"], Hkv=c["num_key_value_heads"],
                D=c["head_dim"], f=c["intermediate_size"],
                V=c["vocab_size"], b=2)


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


def roofline_s(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
