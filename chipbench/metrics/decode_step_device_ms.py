"""Step layer: median device time of the traced step programs whose
step carried no live prefill chunk, each program joined to the engine's
record of its step (``engine.tracer.records``) by step number
(``chipbench/program_trace.py``)."""
import statistics


def read(ctx):
    t = ctx.trace
    p = t.get("program") if t else None
    recs = {r.step: r for r in getattr(ctx, "step_records", ())}
    xs = [dev for step, _prog, dev in (p["steps"] if p else ())
          if step in recs and not recs[step].chunks]
    return 1e3 * statistics.median(xs) if xs else None
