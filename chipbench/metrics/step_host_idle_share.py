"""Runner layer: share of the traced window in which the device ran no
program while the engine was inside ``engine.step``: the idle between
step programs that the host's own work causes, not the gaps between the
ops of one program (``chipbench/program_trace.py``, over the program's
``engine.step`` spans)."""


def read(ctx):
    t = ctx.trace
    p = t.get("program") if t else None
    if not p or not p["modules"] or not p["step_spans"] \
            or not t["window_s"]:
        return None
    return 100.0 * p["host_idle_s"] / t["window_s"]
