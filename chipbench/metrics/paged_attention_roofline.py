"""Kernel layer: least time the decode-attention calls of the traced
steps could take (``chipbench/work.py``: the larger of operations over
peak and bytes over bandwidth, live rows only, every layer) over the
``paged_attention`` kernel's device time in the trace."""
from chipbench import work


def read(ctx):
    t = ctx.trace
    k = t["kernel_s"].get("paged_attention") if t else None
    if not k:
        return None
    layers = ctx.cdoc["num_hidden_layers"]
    need = 0.0
    for s in ctx.traced_steps:
        if s.kind in ("mixed", "decode"):
            need += layers * work.roofline_s(
                *work.decode_attention(ctx.cdoc, s.dec_lens), ctx.peak)[0]
    return 100.0 * need / k
