"""Gateway layer: host microseconds inside ``Gateway.route`` per request
due in the window (the harness's clock around each call)."""


def read(ctx):
    xs = [r.route_s for r in ctx.records if r.in_window and r.req is not None]
    return 1e6 * sum(xs) / len(xs) if xs else None
