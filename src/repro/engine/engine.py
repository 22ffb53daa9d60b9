"""Continuous-batching inference engine (the "vLLM" role in the paper).

One ``InferenceEngine`` = one serving pod's engine process.  Since the
scheduler-core refactor it is a thin composition of two layers behind
the unchanged ``submit/step/metrics/match_prefix_len`` handle contract:

- :class:`repro.engine.scheduler.Scheduler` — the pure-Python unified
  scheduler (admission incl. cache-aware deferral, per-step token
  budget with chunk trimming, preemption, finish/stop bookkeeping, and
  P/D roles).  The SAME class drives the cluster simulator's SimEngine,
  so scheduling semantics cannot drift between the real data plane and
  the simulator.
- :class:`repro.engine.runner.ModelRunner` — the JAX data plane: jitted
  ``mixed_step``/``decode_batch``/``prefill_step`` calls over donated
  ``PagePool`` state, persistent preallocated host input buffers, the
  LoRA bank and the sampling PRNG stream.

Scheduling is a vLLM-style **fused mixed batch** under a per-step token
budget: every ``step()`` packs up to ``max_batch`` decode tokens plus
chunks from up to ``max_prefills`` concurrently-PREFILLING requests
into one jitted forward pass (``paged_model.mixed_step``), so long
prefills no longer stall decoding.  ``mixed_batching=False`` restores
the legacy two-phase scheduler.

P/D disaggregation (paper §3.2.5): ``role="prefill"`` engines prefill,
publish KV pages through the distributed pool and hand each request to
a decode engine via the ``handoff`` callable; ``role="decode"`` engines
pull the prefilled pages from the pool by block hash at admission and
only recompute the tail block.  ``python -m repro.launch.serve --roles
2P2D`` wires a real disaggregated pod group end-to-end.

The engine takes an injectable ``clock`` so it runs identically under
wall-clock (CPU examples/tests) and under the discrete-event cluster
simulator (repro.core.sim).  A ``kv_pool_client`` hook connects it to
the distributed KV cache pool (repro.core.kvcache): local prefix misses
consult the pool by block hash; newly filled pages are published back.
"""
from __future__ import annotations

import queue
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

from repro.core.kvcache.tiers import (CompressedPage, HostPagePool,
                                      SSDPagePool, compress_page,
                                      decompress_page, payload_nbytes,
                                      validate_wire_dtype)
from repro.engine import paged_model as PM
from repro.engine.page_table import PageAllocator, chunk_hashes
from repro.engine.request import Request, RequestState
from repro.engine.runner import ModelRunner
from repro.engine.scheduler import (EngineMetrics, PENDING_TOKEN,  # noqa: F401
                                    ScheduleOutput, Scheduler,
                                    SchedulerConfig, window_throughput)
from repro.engine.tracing import EngineTracer, phased
from repro.models.config import ModelConfig


@dataclass
class EngineConfig:
    page_size: int = 16
    num_pages: int = 512
    max_batch: int = 8              # decode slots
    max_pages_per_seq: int = 32     # block-table width
    chunk_size: int = 64            # chunked-prefill chunk
    chunked_prefill: bool = True
    prefix_caching: bool = True
    impl: str = "pallas"            # pallas | ref
    dtype: str = "float32"
    lora_rank: int = 8
    max_adapters: int = 8
    # -- high-density multi-LoRA serving --
    # auto-register unknown adapters at submit (single-engine/dev
    # ergonomics).  False => residency is the control plane's job
    # (LoRAController.sync): requests queue behind the scheduler's
    # adapter_ready gate until the adapter is loaded, or shed after
    # lora_queue_timeout_s — never silently serving base-model outputs.
    lora_autoload: bool = True
    lora_queue_timeout_s: float = 30.0
    # bounded host-DRAM adapter tier backing the HBM bank's LRU
    # cascade (entries, not bytes — adapters are tiny next to KV);
    # 0 disables (evictions drop to the name-keyed artifact store)
    host_adapter_slots: int = 32
    # -- fused mixed-batch scheduler --
    mixed_batching: bool = True     # False => legacy two-phase scheduler
    max_prefills: int = 2           # concurrent PREFILLING requests
    token_budget: int = 0           # 0 => max_batch + max_prefills*chunk
    # -- P/D disaggregation --
    role: str = "mixed"             # mixed | prefill | decode
    # -- tiered KV cache --
    # host-DRAM tier capacity; 0 disables the tier (no eviction
    # cascade, drop-and-recompute preemption — the pre-tier engine)
    host_cache_gb: float = 0.0
    # SSD third tier below host DRAM; 0 disables.  Host-tier evictions
    # cascade into it via asynchronous write-behind (a daemon thread
    # pickling payloads under ``ssd_dir``), and the admission walk /
    # swap resume consult it after host, before the distributed pool.
    # Payloads are never quantized — SSD resume is byte-identical.
    ssd_cache_gb: float = 0.0
    ssd_dir: Optional[str] = None   # None => a per-engine temp dir
    # wire format for distributed-pool page payloads: "fp" publishes
    # the raw arrays (byte-exact), "int8" quantizes with per-layer
    # scales (≈4x fewer handoff bytes, parity within
    # tiers.INT8_WIRE_MAX_REL_ERR of the per-layer max-abs)
    wire_dtype: str = "fp"
    # pool-handoff transfers stream in groups of this many pages
    # (0 => eager whole-payload, the pre-tier behavior)
    handoff_chunk_pages: int = 4
    swap_preemption: bool = True    # swap to host tier when available
    # -- SLO-aware scheduling (scheduler.DEFAULT_SLO_CLASSES targets) --
    slo_aware: bool = False         # deadline-aware admission/preemption
    slo_classes: Optional[dict] = None      # None => scheduler defaults
    slo_preempt_headroom: float = 0.25
    slo_preempt_cooldown_s: float = 1.0
    # -- crash-recovery checkpoint policy (the recovery log) --
    # publish a running decode's full KV blocks to the pool every
    # this-many new sequence tokens (0 disables), bounded per pass by
    # ckpt_budget_bytes (0 => unbounded)
    ckpt_interval_tokens: int = 0
    ckpt_budget_bytes: int = 0
    # -- speculative n-gram decoding --
    # max prompt-lookup draft tokens verified per decode row in one
    # fused pass (0 disables).  Drafts spend step budget LAST and the
    # per-request acceptance EWMA backs them off on low-acceptance
    # outputs — see scheduler.SchedulerConfig.
    spec_tokens: int = 0
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    spec_probe_interval: int = 50
    # -- async overlapped step loop --
    # dispatch step N+1's host scheduling + input prep while step N
    # still runs on device (decode-only steps overlap; prefill/mixed/
    # speculative steps resolve the in-flight tokens first).  Output
    # tokens stay byte-identical; only readback is deferred one step.
    async_loop: bool = False

    @property
    def step_token_budget(self) -> int:
        return self.scheduler_config().step_token_budget

    def scheduler_config(self) -> SchedulerConfig:
        kw = {}
        if self.slo_classes is not None:
            kw["slo_classes"] = dict(self.slo_classes)
        return SchedulerConfig(
            page_size=self.page_size, max_batch=self.max_batch,
            max_pages_per_seq=self.max_pages_per_seq,
            chunk_size=self.chunk_size,
            chunked_prefill=self.chunked_prefill,
            prefix_caching=self.prefix_caching,
            mixed_batching=self.mixed_batching,
            max_prefills=self.max_prefills,
            token_budget=self.token_budget, role=self.role,
            lora_queue_timeout_s=self.lora_queue_timeout_s,
            handoff_chunk_pages=self.handoff_chunk_pages,
            swap_preemption=self.swap_preemption,
            slo_aware=self.slo_aware,
            slo_preempt_headroom=self.slo_preempt_headroom,
            slo_preempt_cooldown_s=self.slo_preempt_cooldown_s,
            ckpt_interval_tokens=self.ckpt_interval_tokens,
            ckpt_budget_bytes=self.ckpt_budget_bytes,
            spec_tokens=self.spec_tokens,
            spec_ngram_max=self.spec_ngram_max,
            spec_ngram_min=self.spec_ngram_min,
            spec_probe_interval=self.spec_probe_interval, **kw)


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig = None,
                 params=None, clock: Callable[[], float] = time.monotonic,
                 kv_pool_client=None, engine_id: str = "engine-0",
                 seed: int = 0, ssd_pool=None, device=None):
        """``device`` pins this engine's params, KV pool and step inputs
        to one accelerator (None: JAX's default device)."""
        ecfg = ecfg or EngineConfig()
        if not PM.pageable(cfg):
            raise ValueError(
                f"{cfg.name}: paged engine requires a uniform dense/moe "
                "attention pattern; use the slot engine for hybrid/SSM")
        self.cfg, self.ecfg = cfg, ecfg
        self.engine_id = engine_id
        self.clock = clock
        self.kv_pool = kv_pool_client
        validate_wire_dtype(ecfg.wire_dtype)
        # in-program spans, step records and per-phase host counters
        # (repro.engine.tracing); off until tracer.enable()
        self.tracer = EngineTracer()
        self.runner = ModelRunner(cfg, ecfg, params=params, seed=seed,
                                  device=device, tracer=self.tracer)
        # host-DRAM KV tier: device evictions cascade into it and
        # preemption swaps to it instead of recomputing
        self.host_pool = None
        if ecfg.host_cache_gb > 0:
            self.host_pool = HostPagePool(
                capacity_bytes=int(ecfg.host_cache_gb * (1 << 30)))
        # SSD third tier (write-behind, file-backed): host evictions
        # cascade here so idle-session prefixes and parked swap entries
        # survive host pressure and resume byte-identically
        self.ssd_pool = None
        if ssd_pool is not None and self.host_pool is not None:
            # host-shared SSD tier: the launcher passes one
            # SharedSSDPool per host; this engine attaches a per-engine
            # accounting view (same interface as a private pool, plus
            # cross-engine hit classification)
            self.ssd_pool = ssd_pool.view(engine_id) \
                if hasattr(ssd_pool, "view") else ssd_pool
        elif ecfg.ssd_cache_gb > 0 and self.host_pool is not None:
            ssd_dir = ecfg.ssd_dir or tempfile.mkdtemp(
                prefix=f"kv-ssd-{engine_id}-")
            self.ssd_pool = SSDPagePool(
                capacity_bytes=int(ecfg.ssd_cache_gb * (1 << 30)),
                directory=ssd_dir)
        self.sched = Scheduler(
            ecfg.scheduler_config(),
            PageAllocator(ecfg.num_pages, ecfg.page_size),
            kv_pool=kv_pool_client, engine_id=engine_id,
            install_page=self._install_page,
            publish_page=self._publish_page,
            host_pool=self.host_pool,
            page_payload=self.runner.page_payload,
            page_bytes=self.runner.page_bytes,
            adapter_ready=lambda name: name in self.runner.adapter_ids,
            ssd_pool=self.ssd_pool)
        # unloads requested while the adapter still serves an in-flight
        # batch are deferred (applied at the next step() once the last
        # user drains) — the control plane must never disturb a batch
        self._deferred_unloads: set = set()
        # async overlapped loop: the ONE in-flight dispatch record —
        # {reqs, tok_dev (device), idxs (placeholder positions)};
        # resolved when the next step is dispatched (or at drain)
        self._pending: Optional[dict] = None
        # predictive promotion: a daemon thread reads SSD pages off the
        # critical path; landed payloads queue here and are installed
        # into the host pool at step boundaries by the engine thread
        self._promote_req_q: Optional[queue.Queue] = None
        self._promote_q: Optional[queue.Queue] = None
        self._promoter: Optional[threading.Thread] = None

    # ----------------------------------------------------------- views
    @property
    def params(self):
        return self.runner.params

    @property
    def pool(self):
        return self.runner.pool

    @property
    def alloc(self) -> PageAllocator:
        return self.sched.alloc

    @property
    def waiting(self) -> List[Request]:
        return self.sched.waiting

    @property
    def prefills(self) -> List[Request]:
        return self.sched.prefills

    @property
    def running(self) -> List[Request]:
        return self.sched.running

    @property
    def finished(self) -> List[Request]:
        return self.sched.finished

    @property
    def prefilling(self) -> Optional[Request]:
        """Back-compat view of the (first) in-flight prefill."""
        return self.sched.prefills[0] if self.sched.prefills else None

    @property
    def handoff(self) -> Optional[Callable[[Request], None]]:
        return self.sched.handoff

    @handoff.setter
    def handoff(self, fn) -> None:
        self.sched.handoff = fn

    # ------------------------------------------------------------- LoRA
    def _adapters_in_use(self) -> set:
        """Adapters pinned by admitted (in-flight) requests."""
        return {r.lora_adapter
                for r in self.sched.running + self.sched.prefills
                if r.lora_adapter}

    def register_adapter(self, name: str, weights: dict = None) -> int:
        self._deferred_unloads.discard(name)   # re-wanted before unload
        return self.runner.register_adapter(
            name, weights, pinned=self._adapters_in_use())

    def unregister_adapter(self, name: str) -> None:
        if name in self._adapters_in_use():
            self._deferred_unloads.add(name)
            return
        self.runner.unregister_adapter(name)

    def _flush_deferred_unloads(self) -> None:
        if not self._deferred_unloads:
            return
        in_use = self._adapters_in_use()
        for name in list(self._deferred_unloads):
            if name not in in_use:
                self._deferred_unloads.discard(name)
                self.runner.unregister_adapter(name)

    @property
    def adapters(self) -> List[str]:
        return self.runner.adapters

    # ------------------------------------------------------------- submit
    def submit(self, req: Request) -> None:
        if (req.lora_adapter and self.ecfg.lora_autoload
                and req.lora_adapter not in self.runner.adapter_ids):
            try:
                self.register_adapter(req.lora_adapter)
            except RuntimeError:
                pass    # all slots pinned: queue behind adapter_ready
        self.sched.enqueue(req, self.clock())

    @property
    def has_work(self) -> bool:
        return self.sched.has_work

    # ------------------------------------------------------------- pool
    def _install_page(self, pid: int, payload, req: Request,
                      now: float, source: str = "pool",
                      stream: bool = False, nbytes: int = 0) -> None:
        """Payload hook for the Scheduler's page walk (pool OR host
        tier): write the fetched (k_page, v_page) arrays into a local
        device page, dequantizing compressed wire payloads first.  The
        synchronous real data plane installs streamed chunks in place;
        ``stream`` only changes the simulator's cost attribution."""
        if isinstance(payload, CompressedPage):
            payload = decompress_page(payload)
        self.runner.write_remote_page(pid, *payload)

    def _publish_page(self, pid: int, block_hash: str, req: Request,
                      now: float) -> None:
        """Payload hook for the Scheduler's prompt-page registration:
        copy the page off-device and publish it under its block hash —
        quantized to int8 with per-layer scales when the wire format
        asks for it, so a handoff moves ~4x fewer bytes."""
        payload = self.runner.page_payload(pid)
        size = self.runner.page_bytes
        if self.ecfg.wire_dtype == "int8":
            payload = compress_page(*payload)
            size = payload.nbytes
        self.kv_pool.publish(block_hash, payload, self.engine_id, now,
                             size_bytes=size)

    # -------------------------------------------------------- promotion
    def promote_session(self, session_id: str) -> int:
        """Prefetch a session's SSD-resident pages back into host DRAM
        ahead of its predicted next turn.  The SSD reads happen on a
        background daemon thread; payloads land in the host pool at the
        next step boundary, so promotion never stalls the data plane.
        Returns the number of pages queued for promotion."""
        if self.ssd_pool is None or self.host_pool is None:
            return 0
        keys = self.sched.session_promotable(session_id)
        if not keys:
            return 0
        self._ensure_promoter()
        self._promote_req_q.put((session_id, keys))
        return len(keys)

    def _ensure_promoter(self) -> None:
        if self._promoter is not None:
            return
        self._promote_req_q = queue.Queue()
        self._promote_q = queue.Queue()
        self._promoter = threading.Thread(
            target=self._promote_worker, daemon=True,
            name=f"kv-promote-{self.engine_id}")
        self._promoter.start()

    def _promote_worker(self) -> None:
        while True:
            sid, keys = self._promote_req_q.get()
            try:
                for key in keys:
                    payload = self.ssd_pool.get(key, self.clock())
                    if payload is not None:
                        self._promote_q.put((key, payload, sid))
            finally:
                self._promote_req_q.task_done()

    def _land_promotions(self) -> None:
        """Engine-thread drain: install prefetched pages in host DRAM."""
        if self._promote_q is None:
            return
        while True:
            try:
                key, payload, sid = self._promote_q.get_nowait()
            except queue.Empty:
                return
            self.sched.complete_promotion(
                key, payload,
                payload_nbytes(payload, self.runner.page_bytes),
                self.clock(), sid)

    def drain_promotions(self) -> None:
        """Block until all queued promotions have been read off SSD,
        then land them (deterministic tests / shutdown)."""
        if self._promote_req_q is not None:
            self._promote_req_q.join()
        self._land_promotions()

    # ------------------------------------------------------------- step
    def step(self) -> int:
        """One scheduler iteration.  Returns #tokens produced (sampled
        output tokens: one per decode row — several per row when a
        speculative step verified drafts — one per *completed* prefill;
        an unfinished prefill chunk produces none).  With
        ``async_loop`` the count for an overlapped decode step is the
        number DISPATCHED (read back when the next step is issued)."""
        with self.tracer.step():
            self._flush_deferred_unloads()
            self._land_promotions()
            if self.ecfg.async_loop:
                return self._step_async()
            return self._exec(self._schedule())

    def _schedule(self) -> ScheduleOutput:
        with self.tracer.phase("schedule"):
            return self.sched.schedule(self.clock())

    def _exec(self, out: ScheduleOutput) -> int:
        """Execute one declarative schedule synchronously."""
        if out.mode == "idle":
            return 0
        if out.mode == "decode":
            if out.spec:
                return self._step_spec(out)
            self._postprocess_decode(out.decode,
                                     self.runner.run_decode(out.decode))
            return len(out.decode)
        if out.mode == "prefill":      # legacy two-phase chunk
            work = out.prefills[0]
            logits = self.runner.run_prefill(work)
            return 1 if self._advance_prefill(work, logits) else 0
        if out.spec:
            return self._step_spec(out)
        # mixed: one fused decode+prefill pass under the token budget
        dec_logits, pre_logits = self.runner.run_mixed(out)
        produced = 0
        # prefill bookkeeping first (their chunks are already in the pool)
        for i, work in enumerate(out.prefills):
            if work.chunk_len == 0:
                continue            # budget-starved this step
            if self._advance_prefill(work, pre_logits[i][None]):
                produced += 1
        if out.decode:
            self._postprocess_decode(out.decode,
                                     dec_logits[:len(out.decode)])
            produced += len(out.decode)
        return produced

    def _step_spec(self, out: ScheduleOutput) -> int:
        """One speculative verification step: every decode row carries
        its drafts as a short multi-query chunk, prefill chunks (when
        live) ride the same fused pass; acceptance appends the model's
        own samples so the output stream is byte-identical to plain
        decoding."""
        spec_logits, pre_logits = self.runner.run_spec(out)
        produced = 0
        if pre_logits is not None:
            for i, work in enumerate(out.prefills):
                if work.chunk_len == 0:
                    continue
                if self._advance_prefill(work, pre_logits[i][None]):
                    produced += 1
        emitted = self.runner.verify_drafts(spec_logits, out.decode,
                                            out.spec)
        with self.tracer.phase("post"):
            produced += self.sched.on_spec_batch(out.decode, out.spec,
                                                 emitted, self.clock())
        return produced

    # ------------------------------------------------ async overlapped loop
    def _step_async(self) -> int:
        """Overlap host scheduling with device compute: a decode-only
        step is dispatched (input prep + forward + on-device sampling)
        WITHOUT waiting for the previous step's tokens — the scheduler
        plans on PENDING placeholders and the previous dispatch is
        resolved only after the new one is queued.  Any other step
        shape (prefill chunks, speculative drafts, idle) is a sync
        point: resolve first, re-plan on the real history, run the
        normal path."""
        out = self._schedule()
        if self._overlappable(out):
            return self._dispatch_async(out.decode)
        if self._pending is not None:
            self.drain_async()
            # resolution patched real tokens (and may have finished or
            # truncated requests) — the plan must be rebuilt on it
            out = self._schedule()
            if self._overlappable(out):
                return self._dispatch_async(out.decode)
        return self._exec(out)

    @staticmethod
    def _overlappable(out: ScheduleOutput) -> bool:
        return out.mode == "decode" and not out.spec and bool(out.decode)

    def _dispatch_async(self, reqs: List[Request]) -> int:
        reqs = list(reqs)
        tok_dev = self.runner.run_decode_async(reqs, self._pending)
        with self.tracer.phase("post"):
            idxs = self.sched.on_decode_provisional(reqs, self.clock())
        prev, self._pending = self._pending, dict(
            reqs=reqs, tok_dev=tok_dev, idxs=idxs)
        if prev is not None:
            self._resolve_async(prev)
        return len(reqs)

    @phased("post")
    def _resolve_async(self, rec: dict) -> None:
        """Read back one dispatched step's sampled tokens and patch
        them over the PENDING placeholders.  Stop-token finishes are
        retroactive: the stop lands at its true position and anything
        dispatched past it (at most the one in-flight step) is
        truncated — output streams match the sync loop byte for byte.
        A placeholder that vanished meanwhile (preempt reset, stop
        truncation) is skipped by the guard."""
        toks = self.runner.readback(rec["tok_dev"])
        now = self.clock()
        for i, (r, idx) in enumerate(zip(rec["reqs"], rec["idxs"])):
            if (idx >= len(r.output_tokens)
                    or r.output_tokens[idx] != PENDING_TOKEN):
                continue
            tok = int(toks[i])
            r.output_tokens[idx] = tok
            r._pending_toks = max(
                getattr(r, "_pending_toks", 1) - 1, 0)
            sp = r.sampling
            if (self.sched.honor_stop_token and sp.stop_token is not None
                    and tok == sp.stop_token):
                if len(r.output_tokens) > idx + 1:
                    # over-dispatched past the stop: drop the tail
                    # (its placeholders die here; the later record's
                    # patch guard skips the vanished indices)
                    del r.output_tokens[idx + 1:]
                    del r.token_times[idx:]
                    r._pending_toks = 0
                if r.state is RequestState.RUNNING:
                    self.sched.maybe_finish(r, now)

    def drain_async(self) -> None:
        """Resolve the in-flight async dispatch (no-op when none)."""
        rec, self._pending = self._pending, None
        if rec is not None:
            self._resolve_async(rec)

    @phased("post")
    def _advance_prefill(self, work, logits) -> bool:
        """Advance one prefill chunk; True when it produced a token
        (prefill completed and its first token was sampled)."""
        req = work.req
        if not self.sched.note_prefill_progress(req, work.chunk_len):
            return False
        now = self.clock()
        self.sched.register_prompt_pages(req, now)
        if self.sched.wants_handoff:
            # disaggregated: KV is in the pool; hand the request to a
            # decode engine and free this engine for the next prefill.
            # The handoff is a synchronization point: the simulator
            # delays delivery past the pool's metadata lag, and the
            # synchronous real data plane instead flushes exactly the
            # records it just published (other engines' pending records
            # keep their lag) so the decode engine's admission walk
            # sees them rather than recomputing the whole prompt.
            self.sched.handoff_prefill(req, now)
            if self.kv_pool is not None:
                self.kv_pool.flush_hashes(
                    chunk_hashes(req.prompt_tokens, self.ecfg.page_size,
                                 req.lora_adapter or ""),
                    now)
            self.sched.deliver_handoff(req)
            return False
        tok = self.runner.sample(
            logits, [req],
            positions=[req.prompt_len + len(req.output_tokens)])[0]
        self.sched.finish_prefill(req, int(tok), now)
        self.sched.note_tokens(now, req.prompt_len + 1)
        return True

    @phased("post")
    def _postprocess_decode(self, reqs, logits) -> None:
        # per-position sampling keys: the sample for a given (seed,
        # absolute position) is the same whether this row is decoded
        # alone, in any batch order, or as part of a speculative
        # verification pass — the invariant byte-identity rests on
        new = self.runner.sample(
            logits, reqs,
            positions=[r.prompt_len + len(r.output_tokens)
                       for r in reqs])
        self.sched.on_decode_batch(reqs, new, self.clock())

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not self.has_work:
                # async loop: the final dispatch may still be in
                # flight after the last request "finished" on a
                # placeholder — resolve it before declaring idle
                self.drain_async()
                if not self.has_work:
                    return
            self.step()
        raise RuntimeError("engine did not drain")

    # ------------------------------------------------------------- metrics
    def metrics(self) -> EngineMetrics:
        m = self.sched.metrics(self.clock(),
                               loaded_adapters=tuple(self.adapters))
        m.device_wait_s = self.runner.device_wait_s
        m.lora_cold_loads = self.runner.adapter_loads
        m.lora_cold_load_s = self.runner.adapter_load_s
        m.lora_evictions = self.runner.adapter_evictions
        m.lora_host_hits = self.runner.adapter_host_hits
        m.steps = self.tracer.steps
        m.step_host_s = dict(self.tracer.phase_s)
        if self.tracer.step_s > 0:
            m.host_overhead_frac = min(max(
                1.0 - self.runner.device_wait_s / self.tracer.step_s,
                0.0), 1.0)
        return m

    def match_prefix_len(self, tokens) -> int:
        """Prefix-cache coverage for router scoring (non-mutating)."""
        return self.sched.match_prefix_len(tokens)

    @property
    def queue_depth(self) -> int:
        """Cheap routing-load accessor (== metrics() num_running +
        num_waiting) — see SchedulerCore.queue_depth."""
        return self.sched.queue_depth
