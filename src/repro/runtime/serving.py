"""Serving on the REAL engine: continuous batching over `SlotBufferEngine`.

This is the runtime counterpart of `simulator.serving.simulate_serving`:
the same `Request` objects, the same `ContinuousBatcher` (including the
working-set admission cap fed by the SHARED `StepSizeController`), and the
same `ServingReport`/`RequestMetrics` output — but every decode iteration is
real JAX execution through the slot-buffer runtime instead of a latency
model. One `launch.serve --backend {sim,engine}` CLI drives either.

Loop shape (paper §4.1, continuous batching enabled), scheduled at PREFILL
CHUNK granularity so prompt ingestion never head-of-line blocks the batch:

    admit      -> each admitted prompt opens a resumable `PrefillCursor`
                  (fixed-shape chunked ingestion; `prefill_chunk=0` falls
                  back to one monolithic prefill at admission)
    prefill    -> ONE chunk of ONE in-flight cursor per iteration
                  (shortest-remaining-first, so a short prompt admitted
                  behind a long one still reaches its first token quickly;
                  cursor aging guarantees any prompt ingests within
                  n_chunks * max(prefill_starve_limit + 1, in-flight
                  cursors) iterations even under a sustained stream of
                  shorter arrivals)
    decode     -> ONE batched `decode_step` advances every FULLY-PREFILLED
                  row; per-layer routing/pre-gate masks are merged across
                  rows so the adaptive horizon's single (S+1, E) sync
                  covers the whole batch
    sample     -> per-request temperature and PRNG stream via
                  `sampler.sample_rows` (mixed greedy/sampled in one step)
    retire     -> finished rows free their slot for the next waiting
                  request; admission re-consults the controller snapshot

Timing is wall-clock: TTFT/TPOT/queue-delay are measured, not modeled, and
TTFT is attributed across queue / prefill / first-step
(`RequestMetrics.prefill_s` / `first_step_s`).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import (RunReport, ServingReport, StepMetrics,
                                request_metrics)
from repro.distributed.fault_tolerance import StragglerPolicy
from repro.runtime.batching import ContinuousBatcher, WorkingSetAdmission
from repro.runtime.engine import SlotBufferEngine
from repro.runtime.instrument import named_jit, span
from repro.runtime.request import Request
from repro.runtime.sampler import sample, sample_rows


@dataclass
class EngineServingConfig:
    max_batch: int = 4
    admission_cap: bool = True
    admission_headroom: float = 1.0
    max_iterations: int = 100_000
    # chunked prefill: fixed prompt-chunk width interleaved with decode
    # (compile count independent of prompt-length diversity). 0 = monolithic
    # whole-prompt prefill at admission (the head-of-line baseline).
    # Architectures without chunk support (recurrent mixers, sliding
    # windows) fall back to monolithic automatically.
    prefill_chunk: int = 32
    # aging bound for the shortest-remaining-first chunk scheduler: a cursor
    # skipped this many consecutive iterations is advanced regardless, so a
    # long prompt's prefill finishes within
    # n_chunks * max(limit + 1, concurrent cursors) iterations (aged
    # cursors rotate when more than limit+1 starve at once) even under a
    # sustained stream of shorter arrivals
    prefill_starve_limit: int = 4
    # arrival handling: requests with arrival_s in the future are gated on
    # wall-clock; the loop naps this long when the queue is empty
    idle_sleep_s: float = 1e-4
    # record per-request decode logits rows (tests / debugging)
    trace_logits: bool = False
    # §3.4 cache-aware routing knobs, applied to the engine at construction
    # (None = leave the engine's own setting untouched). `route_bias` is the
    # perturbation strength delta in router-logit units — router KL vs
    # unperturbed routing is provably <= delta nats; 0 disables (bit-exact).
    # With `route_bias_adaptive`, delta becomes a ceiling the shared
    # StepSizeController ramps within from its stall/overfetch thresholds.
    route_bias: Optional[float] = None
    route_bias_adaptive: Optional[bool] = None
    # graceful degradation / SLO knobs. `deadline_s` is the default
    # per-request deadline (relative to arrival): a request still queued
    # past it is shed at admission instead of served uselessly late
    # (requests carrying their own `deadline_s` keep it; None = never shed).
    deadline_s: Optional[float] = None
    # brownout admission: the single-replica StragglerPolicy drains when
    # the decode-step EWMA blows past threshold x its healthy baseline;
    # while draining (or while the engine is fault-degraded / its watchdog
    # tripped) admissions pause — but the queue head still admits into an
    # EMPTY batch, so nobody starves. None = auto: enabled iff the engine
    # was built with a FaultPlan. The SAME StragglerPolicy drain signal is
    # the multi-replica mitigation path (distributed.fault_tolerance).
    brownout_admission: Optional[bool] = None
    brownout_threshold: float = 4.0
    brownout_recovery: float = 1.5


class ServingEngine:
    """Continuous-batching server over one `SlotBufferEngine`."""

    def __init__(self, engine: SlotBufferEngine,
                 cfg: Optional[EngineServingConfig] = None,
                 key: Optional[jax.Array] = None):
        self.engine = engine
        self.cfg = cfg or EngineServingConfig()
        if self.cfg.route_bias is not None:
            engine.set_route_bias(
                self.cfg.route_bias,
                adaptive=bool(self.cfg.route_bias_adaptive))
        admission = None
        if self.cfg.admission_cap:
            L = max(len(engine.moe_layer_ids), 1)
            admission = WorkingSetAdmission(
                controller=engine.controller,     # the engine's OWN signals
                slots_per_layer=max(1, engine.n_slots // L),
                expert_bytes=engine._expert_nbytes,
                default_ws=float(engine.cfg.moe.top_k),
                headroom=self.cfg.admission_headroom)
        self.straggler = StragglerPolicy(
            1, threshold=self.cfg.brownout_threshold,
            recovery=self.cfg.brownout_recovery)
        brown = self.cfg.brownout_admission
        if brown is None:
            brown = engine.faults is not None
        self.batcher = ContinuousBatcher(
            self.cfg.max_batch, admission=admission,
            brownout=self._browned_out if brown else None)
        self.base_key = key if key is not None else jax.random.PRNGKey(17)
        self.logits_trace: Dict[int, List[np.ndarray]] = {}
        # per-slot decode-time sampling state
        self._row_key = [self.base_key] * self.cfg.max_batch
        self._row_temp = np.zeros(self.cfg.max_batch, np.float32)
        self._row_step = [0] * self.cfg.max_batch
        # in-flight chunked prefills: [(Request, PrefillCursor)]
        self._prefills: List = []
        self._chunked = (self.cfg.prefill_chunk > 0
                         and engine.chunked_prefill_supported)

    def _browned_out(self) -> bool:
        """Admission brownout signal: the straggler policy's drain verdict
        on this (single) replica, OR the engine's own degraded state —
        fault-degraded routing or a tripped step watchdog."""
        eng = self.engine
        return (self.straggler.draining(0) or eng._degraded
                or (eng.watchdog is not None and eng.watchdog.tripped))

    # -- admission-control working-set estimate -----------------------------
    def _ws_bucket(self, n: int) -> int:
        """Pad prompt lengths to the engine's KV-prefix buckets
        (`SlotBufferEngine._kv_bucket`: next power of two, floor 8, clamped
        to max_seq) so the working-set predictor compiles per BUCKET, not
        per distinct prompt length — and stays aligned with the chunked
        prefill's bucket set, keeping total compiles one-per-bucket."""
        return self.engine._kv_bucket(n, self.engine.max_seq)

    def predict_working_set(self, req: Request) -> float:
        """Predict the request's distinct-experts-per-layer working set by
        routing its prompt token embeddings through every MoE router (one
        jitted dispatch over the stacked routers; no FFN compute). A
        topic-anchored prompt concentrates on few experts, a diverse prompt
        spreads — exactly the signal the admission cap needs to keep
        co-batched working sets inside the shared cache. The prompt is
        right-padded to a length bucket (padding masked out of the distinct
        count), so estimates cost one compile per bucket."""
        eng = self.engine
        prompt = np.asarray(req.prompt, np.int32)
        T = int(prompt.size)
        buf = np.zeros((1, self._ws_bucket(T)), np.int32)
        buf[0, :T] = prompt.reshape(-1)
        counts = self._ws_fn()(eng.params, jnp.asarray(buf), T)
        return float(np.mean(np.asarray(counts)))

    def _ws_fn(self):
        eng = self.engine
        if "predict_ws" not in eng._fns:
            model, stack = eng.model, eng._router_stack
            k = eng.cfg.moe.top_k

            def fn(params, tokens, n_valid):
                x = model.embed(params, tokens)[0].astype(jnp.float32)
                logits = jnp.einsum("td,lde->lte", x, stack)
                _, ids = jax.lax.top_k(logits, k)          # (L, T, k)
                E = stack.shape[-1]
                # padding rows scatter out of range and drop from the count
                ids = jnp.where(jnp.arange(ids.shape[1])[None, :, None]
                                < n_valid, ids, E)
                hot = jnp.zeros((ids.shape[0], E), jnp.bool_)
                hot = hot.at[jnp.arange(ids.shape[0])[:, None],
                             ids.reshape(ids.shape[0], -1)].set(
                                 True, mode="drop")
                return hot.sum(axis=1)                      # (L,) distinct
            eng._fns["predict_ws"] = named_jit("predict_ws", fn)
        return eng._fns["predict_ws"]

    # -- lifecycle helpers ---------------------------------------------------
    def _admit_one(self, req: Request, slot: int, state, now_s: float,
                   report: ServingReport, it: int) -> None:
        """Monolithic admission path: whole-prompt prefill, then the first
        token — all inside one serving iteration (the head-of-line
        baseline chunked serving exists to beat)."""
        eng = self.engine
        req.admitted_s = now_s
        logits = eng.prefill_into(state, slot, np.asarray(
            req.prompt, np.int32)[None, :])
        req.prefill_done_s = time.perf_counter() - self._t0
        self._emit_first_token(req, slot, logits, now_s, report, it)

    def _emit_first_token(self, req: Request, slot: int, logits,
                          t_start: float, report: ServingReport,
                          it: int) -> None:
        """Sample the prompt's first output token and stamp TTFT."""
        eng = self.engine
        with span("serve.sample", request_id=req.request_id):
            key = jax.random.fold_in(self.base_key, req.request_id)
            tok = sample(logits, key, req.temperature)
            self._row_key[slot] = key
            self._row_temp[slot] = max(float(req.temperature), 0.0)
            self._row_step[slot] = 0
            req.output.append(int(np.asarray(tok)[0]))
            req.first_token_s = time.perf_counter() - self._t0
            if self.cfg.trace_logits:
                self.logits_trace.setdefault(req.request_id, []).append(
                    np.asarray(logits)[0])
        sm = StepMetrics(step=it, compute_s=req.first_token_s - t_start,
                         step_size=eng.controller.s)
        report.run.add(sm)

    def _advance_prefill(self, state, report: ServingReport, it: int,
                         finish) -> None:
        """One chunk of ONE in-flight prefill cursor per serving iteration.

        Shortest-remaining-first: a short prompt admitted behind a long one
        overtakes it chunk-wise, so its TTFT is a few chunks instead of the
        long prompt's whole ingestion. SRF alone could starve a long cursor
        forever under a sustained stream of shorter arrivals (freed slots
        keep refilling with shorter cursors), so cursors AGE: one skipped
        `prefill_starve_limit` consecutive iterations is advanced
        regardless, bounding any prompt's ingestion to
        n_chunks * max(limit + 1, concurrent cursors) prefill-iterations
        (cursors capped by max_batch; aged ones rotate)."""
        eng = self.engine
        t0 = time.perf_counter() - self._t0
        self._prefills.sort(key=lambda rc: rc[1].remaining)
        pick = max(range(len(self._prefills)),
                   key=lambda i: self._prefills[i][1].skipped)
        if self._prefills[pick][1].skipped < self.cfg.prefill_starve_limit:
            pick = 0                       # nobody starving: pure SRF
        req, cursor = self._prefills[pick]
        for _, other in self._prefills:
            other.skipped += 1
        cursor.skipped = 0
        eng.prefill_chunk(cursor)
        if not cursor.done:
            report.run.add(StepMetrics(
                step=it, compute_s=(time.perf_counter() - self._t0) - t0,
                step_size=eng.controller.s))
            return
        self._prefills.pop(pick)
        with span("serve.admit", request_id=req.request_id):
            logits = eng.finish_prefill_into(state, req.slot, cursor)
        req.prefill_done_s = time.perf_counter() - self._t0
        self._emit_first_token(req, req.slot, logits, t0, report, it)
        if req.done:                 # 1-token request: done at prefill
            with span("serve.retire", request_id=req.request_id):
                finish(req)
                self.batcher.release(req)

    # -- the serving loop ----------------------------------------------------
    def serve(self, requests: List[Request]) -> ServingReport:
        """Serve the request population to completion; returns the same
        `ServingReport` the simulator emits (TTFT/TPOT/queue p50/p95/p99,
        throughput, occupancy) with wall-clock timings."""
        eng = self.engine
        cfg = self.cfg
        report = ServingReport(
            run=RunReport(policy="engine", platform=jax.default_backend(),
                          model=eng.cfg.name),
            policy="engine", platform=jax.default_backend(),
            model=eng.cfg.name)
        state = eng.alloc_decode_state(cfg.max_batch)
        toks = np.zeros(cfg.max_batch, np.int32)
        pending = sorted(requests, key=lambda r: (r.arrival_s, r.request_id))
        for r in pending:
            # decode writes KV for all but the last sampled token
            if r.prompt_len + r.max_new_tokens - 1 > eng.max_seq:
                raise ValueError(
                    f"request {r.request_id}: prompt {r.prompt_len} + "
                    f"max_new {r.max_new_tokens} exceeds engine "
                    f"max_seq {eng.max_seq}; it would fail mid-decode")
        if cfg.deadline_s is not None:
            for r in pending:
                if r.deadline_s is None:
                    r.deadline_s = cfg.deadline_s
        for r in pending:
            if self.batcher.admission is not None and r.predicted_ws is None:
                r.predicted_ws = self.predict_working_set(r)
        # health counters are cumulative on the engine: diff around this run
        failures0 = eng.stats.link_failures
        retries0 = eng.stats.retries
        degraded0 = eng.stats.degraded_steps
        host_hits0 = eng.stats.host_hits
        host_misses0 = eng.stats.host_misses
        disk_stall0 = eng.stats.disk_stall_s
        integ0 = eng.integrity_counters()
        self._t0 = time.perf_counter()
        it = 0

        def now() -> float:
            return time.perf_counter() - self._t0

        def finish(req: Request, slot: Optional[int] = None) -> None:
            # `slot` must be passed wherever the batcher has already retired
            # the request (step() clears req.slot so it can't alias a reused
            # slot); the prefill-path callers finish BEFORE release, while
            # req.slot is still live
            req.finish_s = now()
            eng.retire_slot(state, req.slot if slot is None else slot)
            report.add_request(request_metrics(req))

        while pending or self.batcher.has_work:
            if it >= cfg.max_iterations:
                raise RuntimeError("serving exceeded max_iterations")
            tnow = now()
            while pending and pending[0].arrival_s <= tnow:
                self.batcher.submit(pending.pop(0))
            if not self.batcher.has_work:
                # nothing can happen before the next arrival: sleep through
                # the gap instead of polling it away
                with span("serve.sleep"):
                    time.sleep(max(pending[0].arrival_s - tnow,
                                   cfg.idle_sleep_s))
                continue

            for req in self.batcher.admit(now=tnow):
                with span("serve.admit", request_id=req.request_id):
                    if self._chunked:
                        # chunked: admission only OPENS the cursor;
                        # ingestion is scheduled one chunk per iteration
                        # below
                        req.admitted_s = now()
                        cursor = eng.start_prefill(
                            np.asarray(req.prompt, np.int32),
                            cfg.prefill_chunk)
                        cursor.request_id = req.request_id
                        self._prefills.append((req, cursor))
                        continue
                    self._admit_one(req, req.slot, state, now(), report, it)
                    it += 1
                    if req.done:      # 1-token request: done at prefill
                        # release BEFORE decode so the slot frees immediately
                        finish(req)
                        # release bookkeeping via batcher (slot back to pool)
                        self.batcher.release(req)

            # -- one prefill chunk per iteration, interleaved with decode --
            if self._prefills:
                self._advance_prefill(state, report, it, finish)
                it += 1

            # decode advances only fully-prefilled rows (state.active);
            # rows mid-prefill hold their slot but sit out the batch
            active_slots = [s for s in self.batcher.active_slots()
                            if state.active[s]]
            if not active_slots:
                continue

            # -- one batched decode iteration over all occupied rows --------
            t_step = now()
            sm = StepMetrics(step=it, step_size=eng.controller.s)
            it += 1
            misses0 = eng.stats.demand_misses
            hits0 = eng.stats.prefetch_hits
            pf0 = eng.stats.prefetched
            for slot in active_slots:
                toks[slot] = self.batcher.active[slot].output[-1]
            logits, state = eng.decode_step(jnp.asarray(toks), state)
            with span("serve.sample", rows=len(active_slots)):
                if any(self._row_temp[s] > 0.0 for s in active_slots):
                    # advance every active row's key BEFORE sampling — the
                    # same fold_in(key, step) schedule
                    # `SlotBufferEngine.generate` walks, so a sampled
                    # request's stream matches its single-request run
                    for slot in active_slots:
                        self._row_step[slot] += 1
                        self._row_key[slot] = jax.random.fold_in(
                            self._row_key[slot], self._row_step[slot])
                    keys = jnp.stack([self._row_key[s]
                                      for s in range(cfg.max_batch)])
                    temps = jnp.asarray(self._row_temp)
                    sampled = np.asarray(sample_rows(logits, keys, temps))
                else:
                    # all-greedy iteration: keys are never consumed — skip
                    # the per-row fold/stack and the discarded categorical
                    # draw
                    sampled = np.asarray(
                        jnp.argmax(logits, axis=-1).astype(jnp.int32))
                if cfg.trace_logits:
                    logits_h = np.asarray(logits)
                    for slot in active_slots:
                        rid = self.batcher.active[slot].request_id
                        self.logits_trace.setdefault(rid, []).append(
                            logits_h[slot])
            next_tokens = {slot: int(sampled[slot]) for slot in active_slots}
            with span("serve.retire"):
                slot_of = {self.batcher.active[s].request_id: s
                           for s in active_slots}
                for req in self.batcher.step(next_tokens):
                    finish(req, slot_of[req.request_id])
                sm.compute_s = now() - t_step
                sm.n_misses = eng.stats.demand_misses - misses0
                sm.n_hits = eng.stats.prefetch_hits - hits0
                sm.n_prefetched = eng.stats.prefetched - pf0
                report.run.add(sm)
                # feed the brownout detector with real decode-step wall time
                self.straggler.record(0, sm.compute_s)

        report.makespan_s = now()
        report.mean_occupancy = self.batcher.stats.mean_occupancy
        report.n_link_failures = eng.stats.link_failures - failures0
        report.n_retries = eng.stats.retries - retries0
        report.n_degraded_steps = eng.stats.degraded_steps - degraded0
        report.n_shed = self.batcher.stats.shed
        report.n_host_hits = eng.stats.host_hits - host_hits0
        report.n_host_misses = eng.stats.host_misses - host_misses0
        report.disk_stall_s = eng.stats.disk_stall_s - disk_stall0
        integ = eng.integrity_counters()
        report.n_corrupt_detected = \
            int(integ["n_corrupt_detected"] - integ0["n_corrupt_detected"])
        report.n_requarantined = \
            int(integ["n_requarantined"] - integ0["n_requarantined"])
        report.n_scrubbed = int(integ["n_scrubbed"] - integ0["n_scrubbed"])
        # quarantine is permanent: report the gauge, not a diff
        report.n_quarantined_experts = int(integ["n_quarantined_experts"])
        return report
