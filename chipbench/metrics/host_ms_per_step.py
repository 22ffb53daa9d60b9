"""Runner layer: host milliseconds per engine step in the window, the
wall time of ``engine.step`` less the runner's readback wait
(``ModelRunner.device_wait_s``)."""


def read(ctx):
    s = ctx.serve
    if not s.step_count:
        return None
    return 1e3 * (s.step_wall_s - s.device_wait_s) / s.step_count
