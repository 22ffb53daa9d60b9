"""Operations and bytes the algorithm needs, from shapes and live lengths.

Counted over live tokens only, so padding of the batch, of a chunk or of
the block-table grid shows as a lower share of the roofline.  A
multiply-add is two operations.  The counts are the architecture
module's (``chipbench/arch``), from the configuration file's published
widths; ``roofline_s`` turns them into the least time the chip could
take.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from chipbench import arch


def decode_attention(c: dict, lengths: Iterable[int]) -> Tuple[float, float]:
    """(operations, bytes) of one decode-attention call (one layer)."""
    return arch.load(c).decode_attention(c, lengths)


def prefill_attention(c: dict, chunks: Iterable[Tuple[int, int]]
                      ) -> Tuple[float, float]:
    """(operations, bytes) of one chunked-prefill attention call (one
    layer); ``chunks`` holds (tokens before the chunk, chunk length)."""
    return arch.load(c).prefill_attention(c, chunks)


def step_flops(c: dict, dec_lens, chunks) -> float:
    """Model operations of one step with these live decode rows and
    prefill chunks."""
    return arch.load(c).step_flops(c, dec_lens, chunks)


def roofline_s(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
