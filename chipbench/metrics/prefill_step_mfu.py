"""Step layer: model operations of the traced steps that carry prefill
chunks (fused and lone-prefill steps, ``chipbench/work.py``) over those
step programs' device time in the trace times the chip's bf16 peak: the
whole-step bound beside ``paged_prefill_roofline``."""
from chipbench import work

KINDS = {"mixed": "mixed_step", "prefill": "prefill_step"}


def read(ctx):
    t = ctx.trace
    steps = [s for s in ctx.traced_steps if s.kind in KINDS]
    busy = sum(t["step_busy_by"][p] for p in KINDS.values()) if t else 0.0
    if not busy or not steps:
        return None
    flops = sum(work.step_flops(ctx.cdoc, s.dec_lens, s.chunks)
                for s in steps)
    return 100.0 * flops / (busy * ctx.peak["bf16_flops"])
