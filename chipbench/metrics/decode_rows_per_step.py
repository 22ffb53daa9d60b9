"""Step layer: mean live decode rows per step dispatched in the window
(the harness's log around ``run_mixed``/``run_decode``/``run_prefill``)."""


def read(ctx):
    xs = [len(s.dec_lens) for s in ctx.window_steps]
    return sum(xs) / len(xs) if xs else None
