"""ModelRunner: executes a ``ScheduleOutput`` on the real JAX model.

The runner is the data plane the unified :class:`repro.engine.scheduler.
Scheduler` drives — it owns everything device-shaped: the model params,
the paged KV ``PagePool`` (donated through every jitted call, so the
pages are updated in place rather than copied), the high-density LoRA
bank, the sampling PRNG stream, and the *persistent preallocated host
input buffers* for step assembly.

The buffer point matters for step overhead: the pre-refactor engine
re-allocated ~6 numpy arrays (tokens / positions / block tables /
active mask / adapter ids, plus the prefill-chunk set) on every
``step()`` before uploading them.  The runner allocates them once at
construction and re-fills the used slice per step; ``benchmarks/
bench_kernels.py --quick`` ("step_inputs" rows) tracks the win.

The runner also owns the page *payload* side of the distributed KV
pool protocol: publishing freshly filled prompt pages (skipping the
device→host copy when the pool already holds the hash) and writing
fetched remote pages into local device pages.

Placement: ``device`` pins one runner to one accelerator.  Params, pool
and LoRA bank are committed there, and every method that builds step
inputs runs under ``jax.default_device(device)``, so one process can
drive several one-chip replicas, each on its own device.
"""
from __future__ import annotations

import functools
import time
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import paged_model as PM
from repro.engine.request import Request
from repro.engine.sampling import row_keys, sample
from repro.engine.scheduler import PrefillWork, ScheduleOutput
from repro.engine.speculative import accept_length
from repro.engine.tracing import EngineTracer, phased
from repro.models import model as M
from repro.models.config import ModelConfig


def _on_device(method):
    """Run a runner method with the runner's device as JAX's default, so
    the step inputs it uploads land next to the params and pool."""
    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        with jax.default_device(self.device):
            return method(self, *args, **kwargs)
    return wrapped


class ModelRunner:
    """Turns declarative schedules into jitted forward passes."""

    def __init__(self, cfg: ModelConfig, ecfg, params=None, seed: int = 0,
                 device: Optional[jax.Device] = None,
                 tracer: Optional[EngineTracer] = None):
        """``tracer`` is the owning engine's; a runner built alone makes
        its own."""
        self.cfg, self.ecfg = cfg, ecfg
        self.device = device
        self.tracer = tracer or EngineTracer()
        dtype = jnp.dtype(ecfg.dtype)
        with jax.default_device(device):
            if params is None:
                params = M.init(cfg, jax.random.PRNGKey(seed), dtype)
            pool = PM.init_pool(cfg, ecfg.num_pages + 1, ecfg.page_size,
                                dtype)  # +1: OOB scratch page for drops
            lora = PM.init_lora(cfg, ecfg.max_adapters, ecfg.lora_rank,
                                dtype)
            self._key = jax.random.PRNGKey(seed + 1)
            # adapter tiering (HBM bank <-> bounded host DRAM tier <->
            # artifact store): weights are a pure function of (engine
            # seed, adapter NAME) — never of the HBM slot — so eviction
            # is always safe (re-load is byte-identical) and slot reuse
            # can't leak.  ``_adapter_base_key`` is separate from the
            # sampling key stream, which ``sample`` mutates.
            self._adapter_base_key = jax.random.PRNGKey(seed + 2)
        if device is not None:
            params, pool, lora = jax.device_put((params, pool, lora), device)
        self.params, self.pool, self.lora = params, pool, lora
        self._adapter_ids: Dict[str, int] = {}
        self._free_adapter_slots = list(range(1, ecfg.max_adapters))
        self._adapter_lru: Dict[str, int] = {}
        self._lru_tick = 0
        self._host_adapters: Dict[str, dict] = {}
        self._host_adapter_slots = int(
            getattr(ecfg, "host_adapter_slots", 32))
        self.adapter_loads = 0          # non-resident registers paid
        self.adapter_load_s = 0.0       # wall seconds stalled on them
        self.adapter_evictions = 0      # LRU HBM-bank evictions
        self.adapter_host_hits = 0      # loads served from the host tier
        # persistent host input buffers (allocated once, refilled per
        # step; block tables are sliced to the bucketed width in use)
        b, kk = ecfg.max_batch, ecfg.max_prefills
        nbmax = ecfg.max_pages_per_seq
        self._dec_toks = np.zeros(b, np.int32)
        self._dec_pos = np.zeros(b, np.int32)
        self._dec_bts = np.full((b, nbmax), ecfg.num_pages, np.int32)
        self._dec_active = np.zeros(b, bool)
        self._dec_aids = np.zeros(b, np.int32)
        # floor of one row: two-phase prefill writes row 0 even when
        # the mixed scheduler is configured with max_prefills=0
        kk1 = max(kk, 1)
        self._pre_toks = np.zeros((kk1, ecfg.chunk_size), np.int32)
        self._pre_ctx = np.zeros(kk1, np.int32)
        self._pre_chunk = np.zeros(kk1, np.int32)
        self._pre_aids = np.zeros(kk1, np.int32)
        self._pre_bts = np.full((kk1, nbmax), ecfg.num_pages, np.int32)
        # speculative verification buffers: every decode row becomes a
        # fixed-width chunk [last_token, draft_1..draft_d] (padding to
        # the full width keeps the jitted spec step at ONE shape)
        sd = 1 + max(getattr(ecfg, "spec_tokens", 0), 0)
        self._spec_toks = np.zeros((b, sd), np.int32)
        self._spec_ctx = np.zeros(b, np.int32)
        self._spec_len = np.zeros(b, np.int32)
        # seconds spent blocked on device readback — the host-overhead
        # signal the async loop shrinks
        self.device_wait_s = 0.0
        # outputs of the most recent jitted call.  jnp.asarray may
        # zero-copy alias a host buffer on some backend/dtype combos
        # (CPU float32 does), so before REFILLING the persistent
        # buffers we block on the previous step's computation — it must
        # not be able to read next-step data through an alias.
        self._inflight = None

    def _sync_inflight(self) -> None:
        if self._inflight is not None:
            with self.tracer.phase("wait") as ph:
                jax.block_until_ready(self._inflight)
            self.device_wait_s += ph.dt
            self._inflight = None

    def readback(self, arr) -> np.ndarray:
        """Block on a device array and charge the wait to the
        device-wait counter — the async loop's one sync point."""
        with self.tracer.phase("readback") as ph:
            jax.block_until_ready(arr)
        self.device_wait_s += ph.dt
        return np.asarray(arr)

    def _upload(self, *host) -> List[jax.Array]:
        """One step's host input buffers, on the device."""
        with self.tracer.phase("upload"):
            return [jnp.asarray(a) for a in host]

    # ------------------------------------------------------------- LoRA
    def _adapter_key(self, name: str):
        """The 'artifact store': adapter weights derive from the NAME,
        so any tier can drop them and re-materialize byte-identically."""
        return jax.random.fold_in(
            self._adapter_base_key, zlib.crc32(name.encode()) & 0x7FFFFFFF)

    def _touch_adapter(self, name: str) -> None:
        self._lru_tick += 1
        self._adapter_lru[name] = self._lru_tick

    @_on_device
    def register_adapter(self, name: str, weights: dict = None,
                         pinned=()) -> int:
        """Dynamic high-density LoRA registration (paper §3.2.1).

        When the HBM bank is full, the least-recently-used resident
        adapter not in ``pinned`` (adapters of in-flight batches) is
        evicted into the bounded host tier.  Weights come from, in
        order: the caller, the host tier, the artifact store
        (:meth:`_adapter_key`).  The wall time of a non-resident load —
        the cold-load stall — accumulates in ``adapter_load_s``."""
        if name in self._adapter_ids:
            self._touch_adapter(name)
            return self._adapter_ids[name]
        t0 = time.perf_counter()
        if not self._free_adapter_slots:
            victim = next(
                (n for n in sorted(self._adapter_ids,
                                   key=lambda a: self._adapter_lru.get(a, 0))
                 if n not in pinned), None)
            if victim is None:
                raise RuntimeError(
                    "adapter slots exhausted and every resident adapter "
                    "is pinned by an in-flight batch")
            self.unregister_adapter(victim)
            self.adapter_evictions += 1
        idx = self._free_adapter_slots.pop(0)
        if weights is None:
            host = self._host_adapters.pop(name, None)
            if host is not None:
                weights = host
                self.adapter_host_hits += 1
            else:
                weights = PM.make_adapter(self.cfg, self.ecfg.lora_rank,
                                          self._adapter_key(name))
        self.lora = {k: self.lora[k].at[idx].set(weights[k])
                     for k in self.lora}
        jax.block_until_ready(self.lora)
        self._adapter_ids[name] = idx
        self._touch_adapter(name)
        self.adapter_loads += 1
        self.adapter_load_s += time.perf_counter() - t0
        return idx

    def unregister_adapter(self, name: str) -> None:
        idx = self._adapter_ids.pop(name, None)
        if idx is None:
            return
        if self._host_adapter_slots > 0:
            # LRU cascade: HBM victims fall into the bounded host tier;
            # host overflow drops to the artifact store (safe — weights
            # are name-keyed, so re-load is byte-identical)
            self._host_adapters[name] = {
                k: np.array(self.lora[k][idx]) for k in self.lora}
            while len(self._host_adapters) > self._host_adapter_slots:
                self._host_adapters.pop(next(iter(self._host_adapters)))
        self._adapter_lru.pop(name, None)
        self.lora = {k: self.lora[k].at[idx].set(0.0) for k in self.lora}
        self._free_adapter_slots.append(idx)

    @property
    def adapters(self) -> List[str]:
        return sorted(self._adapter_ids)

    @property
    def adapter_ids(self) -> Dict[str, int]:
        return self._adapter_ids

    def _aid(self, req: Request) -> int:
        if not req.lora_adapter:
            return 0
        idx = self._adapter_ids.get(req.lora_adapter)
        if idx is None:
            # loud: a non-resident adapter must queue at admission
            # (Scheduler.adapter_ready), never silently serve base
            raise RuntimeError(
                f"request {req.request_id} reached the data plane with "
                f"non-resident adapter {req.lora_adapter!r}")
        self._touch_adapter(req.lora_adapter)
        return idx

    # ---------------------------------------------------------- sampling
    @phased("sample")
    @_on_device
    def sample(self, logits, reqs, positions=None) -> np.ndarray:
        """``positions`` (absolute index of the token being produced,
        per row) switches to per-position keying (seed x position via
        :func:`row_keys`): the sample then doesn't depend on batch
        order or on how many positions one pass verifies — required
        for speculative verification to match step-by-step decoding."""
        b = logits.shape[0]
        temps = np.zeros(b, np.float32)
        tops = np.ones(b, np.float32)
        for i, r in enumerate(reqs[:b]):
            temps[i] = r.sampling.temperature
            tops[i] = r.sampling.top_p
        keys = None
        if positions is not None:
            seeds = np.zeros(b, np.int32)
            pos = np.zeros(b, np.int32)     # pad to the logits batch
            for i, r in enumerate(reqs[:b]):
                seeds[i] = r.sampling.seed
                pos[i] = positions[i]
            keys = row_keys(jnp.asarray(seeds), jnp.asarray(pos))
            sub = self._key     # unused by sample() when keys given
        else:
            self._key, sub = jax.random.split(self._key)
        return self.readback(sample(logits, sub, jnp.asarray(temps),
                                    top_k=0, top_p=jnp.asarray(tops),
                                    keys=keys))

    # ------------------------------------------------------- input prep
    def _pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.ecfg.page_size)

    def _bt_width(self, pages_needed: int) -> int:
        """Bucketed block-table width: bounds the decode kernel's page
        grid by what the batch actually uses (multiples of 4 to limit
        recompiles) instead of the full ``max_pages_per_seq``."""
        cap = -(-max(pages_needed, 1) // 4) * 4
        return min(cap, self.ecfg.max_pages_per_seq)

    @phased("prep")
    def _decode_inputs(self, reqs):
        self._sync_inflight()
        ecfg = self.ecfg
        nb = self._bt_width(max((self._pages_for(
            r.prompt_len + len(r.output_tokens)) for r in reqs),
            default=1))
        toks, pos = self._dec_toks, self._dec_pos
        active, aids = self._dec_active, self._dec_aids
        bts = self._dec_bts[:, :nb]
        toks[:] = 0
        pos[:] = 0
        bts[:] = ecfg.num_pages             # OOB scratch page
        active[:] = False
        aids[:] = 0
        for i, r in enumerate(reqs):
            toks[i] = r.output_tokens[-1]
            pos[i] = r.prompt_len + len(r.output_tokens) - 1
            n = min(len(r.page_ids), nb)
            bts[i, :n] = r.page_ids[:n]
            active[i] = True
            aids[i] = self._aid(r)
        return toks, pos, bts, active, aids

    @phased("prep")
    def _prefill_inputs(self, works: List[PrefillWork], s: int):
        self._sync_inflight()
        ecfg = self.ecfg
        kk = ecfg.max_prefills
        if s == ecfg.chunk_size:
            pre_toks = self._pre_toks
        else:                               # unchunked: dynamic width
            pre_toks = np.zeros((kk, s), np.int32)
        pre_ctx, pre_chunk = self._pre_ctx, self._pre_chunk
        pre_aids = self._pre_aids
        nb_pre = self._bt_width(max((self._pages_for(w.start + w.chunk_len)
                                     for w in works), default=1))
        pre_bts = self._pre_bts[:, :nb_pre]
        pre_toks[:] = 0
        pre_ctx[:] = 0
        pre_chunk[:] = 0
        pre_aids[:] = 0
        pre_bts[:] = ecfg.num_pages
        for i, w in enumerate(works):
            p, c = w.req, w.chunk_len
            pre_toks[i, :c] = p.prompt_tokens[w.start:w.start + c]
            pre_ctx[i] = w.start
            pre_chunk[i] = c
            n = min(len(p.page_ids), nb_pre)
            pre_bts[i, :n] = p.page_ids[:n]
            pre_aids[i] = self._aid(p)
        return pre_toks, pre_ctx, pre_chunk, pre_aids, pre_bts

    # ---------------------------------------------------------- execute
    @_on_device
    def run_mixed(self, out: ScheduleOutput) -> Tuple[jax.Array, jax.Array]:
        """One fused decode+prefill pass; returns (dec_logits, pre_logits)."""
        ecfg = self.ecfg
        pre_toks, pre_ctx, pre_chunk, pre_aids, pre_bts = \
            self._prefill_inputs(out.prefills, out.pad_len)
        toks, pos, bts, active, aids = self._decode_inputs(out.decode)
        (toks, pos, bts, active, pre_toks, pre_bts, pre_ctx, pre_chunk,
         aids, pre_aids) = self._upload(
            toks, pos, bts, active, pre_toks, pre_bts, pre_ctx, pre_chunk,
            aids, pre_aids)
        with self.tracer.launch("mixed_step",
                                (bts.shape[1], pre_bts.shape[1]),
                                out.prefills):
            dec_logits, pre_logits, self.pool = PM.mixed_step(
                self.params, self.pool, toks, pos, bts, active, pre_toks,
                pre_bts, pre_ctx, pre_chunk, self.lora, aids, pre_aids,
                cfg=self.cfg, page_size=ecfg.page_size, impl=ecfg.impl)
        self._inflight = (dec_logits, pre_logits)
        return dec_logits, pre_logits

    @_on_device
    def run_decode(self, reqs: List[Request]) -> jax.Array:
        ecfg = self.ecfg
        toks, pos, bts, active, aids = self._upload(
            *self._decode_inputs(reqs))
        with self.tracer.launch("decode_batch", (bts.shape[1], 0)):
            logits, self.pool = PM.decode_batch(
                self.params, self.pool, toks, pos, bts, active, self.lora,
                aids, cfg=self.cfg, page_size=ecfg.page_size,
                impl=ecfg.impl)
        self._inflight = logits
        return logits

    # -------------------------------------------------- speculative step
    @phased("prep")
    def _spec_inputs(self, reqs: List[Request], spec: List[List[int]]):
        self._sync_inflight()
        ecfg = self.ecfg
        nb = self._bt_width(max((self._pages_for(
            r.prompt_len + len(r.output_tokens) + len(d))
            for r, d in zip(reqs, spec)), default=1))
        toks, ctx, slen = self._spec_toks, self._spec_ctx, self._spec_len
        aids = self._dec_aids
        bts = self._dec_bts[:, :nb]
        toks[:] = 0
        ctx[:] = 0
        slen[:] = 0                         # 0 marks an idle lane
        aids[:] = 0
        bts[:] = ecfg.num_pages             # OOB scratch page
        for i, (r, d) in enumerate(zip(reqs, spec)):
            toks[i, 0] = r.output_tokens[-1]
            toks[i, 1:1 + len(d)] = d
            ctx[i] = r.prompt_len + len(r.output_tokens) - 1
            slen[i] = 1 + len(d)
            n = min(len(r.page_ids), nb)
            bts[i, :n] = r.page_ids[:n]
            aids[i] = self._aid(r)
        return toks, ctx, slen, bts, aids

    @_on_device
    def run_spec(self, out: ScheduleOutput
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """One speculative verification pass.  Decode-only schedules
        take :func:`PM.spec_decode_step` (no idle prefill lanes — the
        fast path the 1.5x target depends on); schedules carrying live
        prefill chunks fuse them via :func:`PM.spec_mixed_step`.
        Returns (spec logits (B, SD, V), prefill logits (K, V) | None).
        """
        ecfg = self.ecfg
        live = [w for w in out.prefills if w.chunk_len > 0]
        if live:
            pre_toks, pre_ctx, pre_chunk, pre_aids, pre_bts = \
                self._prefill_inputs(out.prefills, out.pad_len)
            toks, ctx, slen, bts, aids = self._spec_inputs(
                out.decode, out.spec)
            (toks, ctx, slen, bts, pre_toks, pre_bts, pre_ctx, pre_chunk,
             aids, pre_aids) = self._upload(
                toks, ctx, slen, bts, pre_toks, pre_bts, pre_ctx, pre_chunk,
                aids, pre_aids)
            with self.tracer.launch("spec_mixed_step",
                                    (bts.shape[1], pre_bts.shape[1]),
                                    out.prefills):
                spec_logits, pre_logits, self.pool = PM.spec_mixed_step(
                    self.params, self.pool, toks, ctx, slen, bts, pre_toks,
                    pre_bts, pre_ctx, pre_chunk, self.lora, aids, pre_aids,
                    cfg=self.cfg, page_size=ecfg.page_size, impl=ecfg.impl)
            self._inflight = (spec_logits, pre_logits)
            return spec_logits, pre_logits
        toks, ctx, slen, bts, aids = self._upload(
            *self._spec_inputs(out.decode, out.spec))
        with self.tracer.launch("spec_decode_step", (bts.shape[1], 0)):
            spec_logits, self.pool = PM.spec_decode_step(
                self.params, self.pool, toks, ctx, slen, bts, self.lora,
                aids, cfg=self.cfg, page_size=ecfg.page_size,
                impl=ecfg.impl)
        self._inflight = spec_logits
        return spec_logits, None

    @phased("sample")
    @_on_device
    def verify_drafts(self, spec_logits, reqs: List[Request],
                      spec: List[List[int]]) -> List[List[int]]:
        """Sample EVERY verification row with per-position keys and
        accept each row's longest draft prefix that matches the model's
        own samples.  Returns per-request emitted token lists (accepted
        prefix + the bonus/correction token) — byte-identical to what
        step-by-step decoding would have produced."""
        b, sd, v = spec_logits.shape
        temps = np.zeros(b * sd, np.float32)
        tops = np.ones(b * sd, np.float32)
        seeds = np.zeros(b * sd, np.int32)
        pos = np.zeros(b * sd, np.int32)
        for i, r in enumerate(reqs):
            temps[i * sd:(i + 1) * sd] = r.sampling.temperature
            tops[i * sd:(i + 1) * sd] = r.sampling.top_p
            seeds[i * sd:(i + 1) * sd] = r.sampling.seed
            base = r.prompt_len + len(r.output_tokens)
            pos[i * sd:(i + 1) * sd] = base + np.arange(sd)
        keys = row_keys(jnp.asarray(seeds), jnp.asarray(pos))
        sampled = self.readback(sample(
            spec_logits.reshape(b * sd, v), self._key,
            jnp.asarray(temps), top_k=0, top_p=jnp.asarray(tops),
            keys=keys)).reshape(b, sd)
        emitted = []
        for i, (r, d) in enumerate(zip(reqs, spec)):
            m = accept_length(d, sampled[i, :len(d) + 1])
            emitted.append([int(t) for t in sampled[i, :m + 1]])
        return emitted

    # ------------------------------------------------ async decode step
    @_on_device
    def run_decode_async(self, reqs: List[Request],
                         prev: Optional[dict]) -> jax.Array:
        """Dispatch a decode step WITHOUT blocking on the previous one.

        Fresh input buffers (the persistent ones require a sync before
        refill), a device-side gather for any input token still in
        flight (``prev["tok_dev"]`` holds the previous async step's
        sampled tokens, not yet read back — the host only has PENDING
        placeholders for them), and on-device sampling with per-
        position keys so the step's output is itself a device array the
        NEXT step can consume without a sync.  Returns the sampled
        tokens (device)."""
        ecfg = self.ecfg
        host, rows, srcs = self._async_inputs(reqs, prev)
        toks, pos, bts, active, aids, seeds, pos1, temps, tops = \
            self._upload(*host)
        if rows:
            with self.tracer.phase("upload"):
                toks = toks.at[jnp.asarray(np.asarray(rows))].set(
                    prev["tok_dev"][jnp.asarray(np.asarray(srcs))])
        with self.tracer.launch("decode_batch", (bts.shape[1], 0)):
            logits, self.pool = PM.decode_batch(
                self.params, self.pool, toks, pos, bts, active, self.lora,
                aids, cfg=self.cfg, page_size=ecfg.page_size,
                impl=ecfg.impl)
        with self.tracer.phase("sample"):
            tok_dev = sample(logits, self._key, temps, top_k=0, top_p=tops,
                             keys=row_keys(seeds, pos1))
        self._inflight = tok_dev
        return tok_dev

    @phased("prep")
    def _async_inputs(self, reqs: List[Request], prev: Optional[dict]):
        """The async decode step's host inputs, and the rows whose input
        token is still on the device with their rows in ``prev``."""
        ecfg = self.ecfg
        b = ecfg.max_batch
        nb = self._bt_width(max((self._pages_for(
            r.prompt_len + len(r.output_tokens)) for r in reqs),
            default=1))
        toks = np.zeros(b, np.int32)
        pos = np.zeros(b, np.int32)
        bts = np.full((b, nb), ecfg.num_pages, np.int32)
        active = np.zeros(b, bool)
        aids = np.zeros(b, np.int32)
        temps = np.zeros(b, np.float32)
        tops = np.ones(b, np.float32)
        seeds = np.zeros(b, np.int32)
        rows: List[int] = []
        srcs: List[int] = []
        prev_rows = ({id(r): j for j, r in enumerate(prev["reqs"])}
                     if prev else {})
        for i, r in enumerate(reqs):
            if getattr(r, "_pending_toks", 0) and id(r) in prev_rows:
                rows.append(i)              # token still on device
                srcs.append(prev_rows[id(r)])
            else:
                toks[i] = r.output_tokens[-1]
            pos[i] = r.prompt_len + len(r.output_tokens) - 1
            n = min(len(r.page_ids), nb)
            bts[i, :n] = r.page_ids[:n]
            active[i] = True
            aids[i] = self._aid(r)
            temps[i] = r.sampling.temperature
            tops[i] = r.sampling.top_p
            seeds[i] = r.sampling.seed
        return ((toks, pos, bts, active, aids, seeds, pos + 1, temps, tops),
                rows, srcs)

    @_on_device
    def run_prefill(self, work: PrefillWork) -> jax.Array:
        """One (possibly chunked) prefill for ONE request (two-phase)."""
        ecfg = self.ecfg
        req, s, c = work.req, work.pad_len, work.chunk_len
        with self.tracer.phase("prep"):
            self._sync_inflight()
            if s == ecfg.chunk_size:
                toks = self._pre_toks[:1]
                toks[:] = 0
            else:
                toks = np.zeros((1, s), np.int32)
            toks[0, :c] = req.prompt_tokens[work.start:work.start + c]
            nb = self._bt_width(self._pages_for(work.start + c))
            bt = self._pre_bts[:1, :nb]
            bt[:] = ecfg.num_pages              # OOB scratch page
            n = min(len(req.page_ids), nb)
            bt[0, :n] = req.page_ids[:n]
            aid = np.asarray([self._aid(req)], np.int32)
        toks, bt, start, clen, aid = self._upload(
            toks, bt, np.int32(work.start), np.int32(c), aid)
        with self.tracer.launch("prefill_step", (0, nb), [work]):
            logits, self.pool = PM.prefill_step(
                self.params, self.pool, toks, bt, start, clen, self.lora,
                aid, cfg=self.cfg, page_size=ecfg.page_size,
                impl=ecfg.impl)
        self._inflight = logits
        return logits

    # ------------------------------------------------------- pool payloads
    @property
    def page_bytes(self) -> int:
        """Raw (k + v) payload bytes of one page — what the host tier's
        capacity accounting and the transfer counters charge."""
        k = self.pool.k                     # (L, Hkv, P, page, D)
        return int(2 * k[:, :, 0].size * k.dtype.itemsize)

    def page_payload(self, pid: int):
        """Materialize one page's (k, v) arrays for a pool publish or a
        host-tier offload — the device→host copy the Scheduler's
        contains() gate avoids for blocks the pool already knows.
        ``np.array`` forces a real copy: host-tier entries outlive this
        step, and on CPU backends a zero-copy view could alias a
        donated buffer the next jitted call overwrites in place."""
        return (np.array(self.pool.k[:, :, pid]),
                np.array(self.pool.v[:, :, pid]))

    @_on_device
    def write_remote_page(self, pid: int, k_page, v_page) -> None:
        """Install a page payload fetched from the distributed pool."""
        self.pool = PM.PagePool(
            self.pool.k.at[:, :, pid].set(k_page),
            self.pool.v.at[:, :, pid].set(v_page))
