"""Scheduler layer: 90th percentile of the time the window's requests
waited between their due time and admission (``Request.queue_time``);
a request never admitted waits until the run stopped."""
from chipbench.window import nearest_rank


def read(ctx):
    xs = []
    for r in ctx.records:
        if not r.in_window:
            continue
        q = r.req
        if q is not None and q.schedule_time:
            xs.append(q.queue_time)
        else:
            xs.append(ctx.stop_e - (r.due + ctx.open_e))
    return 1e3 * nearest_rank(xs, 90) if xs else None
