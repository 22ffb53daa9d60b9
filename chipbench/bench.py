"""The system under test, driven as a user would: the prefix-cache-aware
``Gateway`` routes each request to an ``InferenceEngine`` built by
``repro.launch.serve.build_engines``, and the harness steps the engine
in one thread, submitting arrivals when they come due (open loop).

Set-up builds the engine on seeded weights, loads every step program
the cell's traffic can meet, then replays the cell's own warm-up
traffic.  The window opens on the clock, whatever the engine is doing;
requests due in it are followed to their end, or to the traffic's
drain limit, while the drain's arrivals keep the load on.

Engine clock: seconds since the warm-up began, plus one, so every time
the program sees is positive; the window opens at ``warmup_s + 1``.
Each request's ``arrival_time`` is its due time on that clock.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np

from chipbench import arch
from chipbench import weights as W
from chipbench.traffic import Arrival

CLOCK_OFFSET_S = 1.0


class CompileCounter:
    """Backend compiles (and persistent-cache loads, which JAX times
    under the same event), each with the host time it ended."""

    def __init__(self):
        import jax
        self.ends: List[float] = []
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.ends.append(time.perf_counter())
            self.seconds += duration

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.ends if t0 <= t < t1)


@dataclass
class Record:
    """One arrival and what the system did with it."""
    arrival: Arrival
    in_window: bool
    req: object = None              # repro.engine.Request, once routed
    refused: bool = False
    late_s: float = 0.0             # submitted this long after its due time
    route_s: float = 0.0

    @property
    def due(self) -> float:
        return self.arrival.due

    @property
    def prompt(self):
        return self.arrival.prompt

    @property
    def output(self):
        return [] if self.req is None else self.req.output_tokens

    @property
    def finished(self) -> bool:
        return (self.req is not None and self.req.state.value == "finished"
                and len(self.req.output_tokens) == self.arrival.max_new)


@dataclass
class StepLog:
    t: float                        # host time of the dispatch
    kind: str                       # mixed | decode | prefill
    dec_lens: List[int]             # attended length of each decode row
    chunks: List[tuple]             # (context before, chunk length)


@dataclass
class ServeResult:
    records: List[Record]
    steps: List[StepLog]
    window_open: float              # host perf_counter times
    window_close: float
    to_engine: Callable[[float], float]   # host time -> engine clock
    step_wall_s: float = 0.0        # host seconds inside engine.step in window
    step_count: int = 0
    device_wait_s: float = 0.0
    window_compiles: int = 0
    trace: Optional[dict] = None
    drained_at: float = 0.0
    # engine.metrics() as the window opened and as it closed: a reader
    # of a program counter takes its delta over the window
    counters_open: object = None
    counters_close: object = None


class Bench:
    def __init__(self, cdoc: dict, seed: int, device=None):
        import jax
        import jax.numpy as jnp
        from repro.core.gateway import Gateway
        from repro.launch.serve import build_engines
        from repro.models import model as M
        from chipbench.traffic import seed_words

        self.cdoc = cdoc
        self.device = device or jax.devices()[0]
        module = arch.load(cdoc)
        self.cfg = cfg = module.program_config(cdoc)
        module.check(cdoc, cfg)
        dtype = jnp.dtype(cdoc["program"]["dtype"])
        key = jax.random.PRNGKey(int(np.uint32(
            np.random.SeedSequence(seed_words(seed)).generate_state(1)[0])))
        with jax.default_device(self.device):
            self.weights = W.make(cdoc, key, dtype)
        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            jax.eval_shape(lambda: M.init(cfg, key, dtype)))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.weights)
        if want != got:
            raise ValueError("chipbench weights do not match the program's "
                             "parameter layout")
        kw = dict(cdoc["engine"], dtype=cdoc["program"]["dtype"])
        self._origin = time.perf_counter()
        self.gateway = Gateway(policy="prefix-cache-aware", clock=self.clock)
        engines, _, _ = build_engines(cfg, ["mixed"], self.clock, kw,
                                      gateway=self.gateway,
                                      params=self.weights, seed=0)
        self.engines = engines
        self.engine = engines["engine-0"]
        self.compiles = CompileCounter()
        self.steps: List[StepLog] = []
        self._log_steps()

    def clock(self) -> float:
        return time.perf_counter() - self._origin

    # ------------------------------------------------------------ step log
    def _log_steps(self) -> None:
        """Note each step's live work as it is dispatched: the harness's
        own wrappers around the runner's entry points."""
        r = self.engine.runner
        steps = self.steps

        def wrap(name, describe):
            inner = getattr(r, name, None)
            if inner is None:
                return

            def call(arg):
                kind, dec, chunks = describe(arg)
                steps.append(StepLog(time.perf_counter(), kind, dec, chunks))
                return inner(arg)
            setattr(r, name, call)

        def lens(reqs):
            return [q.prompt_len + len(q.output_tokens) for q in reqs]

        wrap("run_mixed", lambda out: (
            "mixed", lens(out.decode),
            [(w.start, w.chunk_len) for w in out.prefills if w.chunk_len]))
        wrap("run_decode", lambda reqs: ("decode", lens(reqs), []))
        wrap("run_prefill", lambda w: (
            "prefill", [], [(w.start, w.chunk_len)]))

    # ------------------------------------------------------------ warm-up
    def warm_programs(self, max_tokens: int) -> int:
        """Run every step program the traffic can meet once: the fused
        step for each pair of block-table widths up to ``max_tokens`` of
        context (prompt and answer), the decode-only and the lone-prefill
        step for each width, and the sampling of every decode batch
        size.  Writes go to the pool's scratch page."""
        import jax
        from repro.engine import Request
        from repro.engine.scheduler import PrefillWork, ScheduleOutput

        e, r = self.engine.ecfg, self.engine.runner
        ps, scratch = e.page_size, e.num_pages
        pages = min(-(-max_tokens // ps), e.max_pages_per_seq)
        widths = sorted({r._bt_width(p) for p in range(1, pages + 1)})

        def dec_reqs(pages):
            reqs = []
            for _ in range(e.max_batch):
                q = Request(prompt_tokens=[0] * (pages * ps - 1))
                q.output_tokens = [0]
                q.page_ids = [scratch] * pages
                reqs.append(q)
            return reqs

        def work(pages):
            q = Request(prompt_tokens=[0] * (pages * ps))
            q.page_ids = [scratch] * pages
            return PrefillWork(q, pages * ps - e.chunk_size, e.chunk_size,
                               e.chunk_size)

        n0 = len(self.compiles.ends)
        for wd in widths:
            for wp in widths:
                out = ScheduleOutput(
                    mode="mixed", decode=dec_reqs(wd),
                    prefills=[work(wp)] * max(e.max_prefills, 1),
                    pad_len=e.chunk_size)
                dec_logits, pre_logits = r.run_mixed(out)
            jax.block_until_ready(r.run_decode(dec_reqs(wd)))
            jax.block_until_ready(r.run_prefill(work(wd)))
        reqs = dec_reqs(widths[0])
        for n in range(1, e.max_batch + 1):
            r.sample(dec_logits[:n], reqs[:n], positions=[1] * n)
        for i in range(max(e.max_prefills, 1)):
            r.sample(pre_logits[i][None], reqs[:1], positions=[1])
        self.steps.clear()
        jax.block_until_ready(self.engine.pool)
        return len(self.compiles.ends) - n0

    # ------------------------------------------------------------- serving
    def serve(self, arrivals: List[Arrival], warmup_s: float,
              window_s: float, drain_s: float,
              trace_slice: Optional[tuple] = None,
              spans: Optional[Callable[[str], object]] = None
              ) -> ServeResult:
        """Warm-up traffic, the window, and the drain.  ``trace_slice``
        (start, stop, cap) in seconds from the window's opening brackets
        a profiler trace: it stops at ``stop`` where a traced step has
        carried a prefill chunk by then, else after the first step that
        does, or at ``cap``.  The run follows requests until it has
        stopped; ``spans(name)`` opens a host span."""
        import jax
        from repro.engine import Request, SamplingParams

        span = spans or (lambda _name: contextlib.nullcontext())
        eng, gw = self.engine, self.gateway
        open_e = warmup_s + CLOCK_OFFSET_S
        close_e, stop_e = open_e + window_s, open_e + window_s + drain_s
        records = [Record(a, 0.0 <= a.due < window_s) for a in arrivals]
        window = [rec for rec in records if rec.in_window]
        self.steps.clear()
        self._origin = time.perf_counter() - CLOCK_OFFSET_S
        res = ServeResult(records, self.steps,
                          self._origin + open_e, self._origin + close_e,
                          lambda t: t - self._origin)
        trace_on = trace_off = trace_cap = None
        if trace_slice is not None:
            trace_on, trace_off, trace_cap = (open_e + t for t in trace_slice)
        tracer, traced0 = None, 0
        wait0 = step_wall = 0.0
        nsteps, opened, closed, i, n = 0, False, False, 0, len(records)
        while True:
            now = self.clock()
            if not opened and now >= open_e:
                opened = True
                wait0 = eng.runner.device_wait_s
                res.counters_open = eng.metrics()
            if trace_on is not None and tracer is None and now >= trace_on:
                jax.block_until_ready(eng.pool)
                tracer, traced0 = trace_start(), len(self.steps)
            if tracer is not None and trace_off is not None \
                    and now >= trace_off and (now >= trace_cap or any(
                        s.chunks for s in self.steps[traced0:])):
                jax.block_until_ready(eng.pool)
                res.trace = trace_stop(tracer)
                trace_off = None
            with span("submit"):
                while i < n and records[i].due + open_e <= now:
                    rec = records[i]
                    i += 1
                    a = rec.arrival
                    rec.late_s = now - (a.due + open_e)
                    t0 = time.perf_counter()
                    with span("route"):
                        eid = gw.route(a.prompt, user=a.user,
                                       est_output_tokens=a.max_new,
                                       session_id=a.session)
                    rec.route_s = time.perf_counter() - t0
                    if eid is None:
                        rec.refused = True
                        continue
                    rec.req = Request(
                        prompt_tokens=a.prompt,
                        sampling=SamplingParams(max_new_tokens=a.max_new,
                                                temperature=0.0),
                        user=a.user, session_id=a.session,
                        arrival_time=a.due + open_e)
                    self.engines[eid].submit(rec.req)
            if eng.has_work:
                t0 = time.perf_counter()
                with span("step"):
                    eng.step()
                if opened and now < close_e:
                    step_wall += time.perf_counter() - t0
                    nsteps += 1
            else:
                nxt = records[i].due + open_e if i < n else now + 0.001
                with span("wait_arrival"):
                    time.sleep(max(min(nxt - self.clock(), 0.002), 0.0))
            if now >= close_e and not closed:
                closed = True
                res.device_wait_s = eng.runner.device_wait_s - wait0
                res.step_wall_s, res.step_count = step_wall, nsteps
                res.counters_close = eng.metrics()
            if closed and trace_off is None and (
                    now >= stop_e or all(_settled(rec) for rec in window)):
                break
        res.drained_at = self.clock()
        res.window_compiles = self.compiles.between(res.window_open,
                                                    res.window_close)
        return res

    def free(self) -> None:
        """Drop the program's state (engine, pool, gateway); the weights
        stay for the reference."""
        import gc
        self.engines.clear()
        self.engine = self.gateway = None
        gc.collect()


def _settled(rec: Record) -> bool:
    if rec.refused or rec.req is None:
        return rec.refused
    return rec.req.state.value in ("finished", "failed")


def trace_start():
    import tempfile
    import jax
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.perf_counter()
    jax.profiler.start_trace(d, profiler_options=opts)
    return {"dir": d, "t_start": time.perf_counter(), "t_call": t0}


def trace_stop(tracer: dict) -> dict:
    import jax
    tracer["t_stop"] = time.perf_counter()
    jax.profiler.stop_trace()
    return tracer
