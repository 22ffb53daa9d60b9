"""Scheduler layer: share of the prompt tokens admitted in the window
that the prefix cache served (each request's ``cached_prefix_tokens``,
the count behind ``EngineMetrics.prefix_hit_tokens``)."""


def read(ctx):
    hit = tot = 0
    for r in ctx.records:
        q = r.req
        if q is not None and q.schedule_time and \
                ctx.open_e <= q.schedule_time < ctx.close_e:
            hit += q.cached_prefix_tokens
            tot += q.prompt_len
    return 100.0 * hit / tot if tot else None
