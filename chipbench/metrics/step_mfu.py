"""Step layer: model operations of the traced steps' live tokens
(``chipbench/work.py``) over the device's busy time in the trace (every
operation, the sampler and copies included) times the chip's bf16 peak:
the whole-step bound beside ``paged_attention_roofline``."""
from chipbench import work


def read(ctx):
    t = ctx.trace
    if not t or not t["busy_s"] or not ctx.traced_steps:
        return None
    flops = sum(work.step_flops(ctx.cdoc, s.dec_lens, s.chunks)
                for s in ctx.traced_steps)
    return 100.0 * flops / (t["busy_s"] * ctx.peak["bf16_flops"])
