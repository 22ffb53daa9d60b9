"""Kernel layer: least time the chunked-prefill attention calls of the
traced steps could take (``chipbench/work.py``, live chunk rows only,
every layer) over the ``paged_prefill`` kernel's device time in the
trace."""
from chipbench import work


def read(ctx):
    t = ctx.trace
    k = t["kernel_s"].get("paged_prefill") if t else None
    if not k:
        return None
    layers = ctx.cdoc["num_hidden_layers"]
    need = 0.0
    for s in ctx.traced_steps:
        if s.kind in ("mixed", "prefill"):
            need += layers * work.roofline_s(
                *work.prefill_attention(ctx.cdoc, s.chunks), ctx.peak)[0]
    return 100.0 * need / k
