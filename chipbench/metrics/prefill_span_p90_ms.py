"""Scheduler layer: 90th percentile, over the requests due in the
window, of the time from a request's first admission
(``Request.admit_time``) to its first token: the chunked prefill spread
over steps.  A request with no first token counts until the run
stopped; one never admitted is left out."""
from chipbench.window import nearest_rank


def read(ctx):
    xs = []
    for r in ctx.records:
        admit = getattr(r.req, "admit_time", None)
        if r.in_window and admit is not None:
            xs.append((r.req.first_token_time or ctx.stop_e) - admit)
    return 1e3 * nearest_rank(xs, 90) if xs else None
