"""Request-queue scheduler with continuous batching.

Subsumes the old ``DisaggregatedRuntime.generate_pipelined`` round-robin:
``submit()`` enqueues a request at any time (including between steps —
new work joins the next ``step()``), ``step()`` advances every active
sequence one token in two phases:

  phase 1 — ONE ``decode_wave`` dispatch advances every active
     sequence over the engine's slotted ``KVCachePool`` (tokens [W],
     slots [W], positions [W]; W bucketed to powers of two, attention
     reads cropped to the wave's block-aligned valid prefix ``kv_len``
     — ``pool.stats.blocks_skipped/blocks_total`` record the ragged-
     wave savings, ``decode_compiles`` the graph churn). jax
     dispatch is async, so on a disaggregated deployment the wave's
     retrieval (phase 2) overlaps its decode on the other pool — the
     paper's batched GPU pool (§5) plus the multi-process ChamLM overlap
     (Fig. 12 throughput). (PoolTimes instrumentation blocks per pool
     step for measurement; build the backend with ``measure=False`` for
     maximum overlap. The per-sequence oracle — ``wave=False`` on the
     engine — instead dispatches one decode per sequence.)
  phase 2a — issue every due sequence's retrieval query. With an
     ``AsyncRetriever`` the queries only *enqueue* on the
     ``RetrievalService`` (each returns a ``SearchHandle`` future) while
     the phase-1 decode is still in flight; synchronous retrievers get
     one batched ``search`` over the wave's due rows.
  phase 2b — one ``flush_searches()``: the whole wave's queries
     coalesce into a single batched IVF-scan/PQ-ADC/top-k dispatch.
  phase 2c — resolve + integrate + sample, batched over the wave (one
     ``resolve``/interpolate over all due rows, one argmax over all
     greedy rows); per-request ``rng`` sampling stays per-sequence.

Sequences finish independently (continuous batching): a request that was
submitted later, or that asks for fewer steps, completes without waiting
for the rest of the batch — and frees its KV-pool slots for the next
queued request. Admission consults ``engine.can_admit`` (fixed-capacity
pools defer requests until slots free up) in strict FIFO order.
"""
from __future__ import annotations

import time
from collections import deque
from typing import TYPE_CHECKING, Dict, List, Optional

import numpy as np

from repro.runtime.fault_tolerance import StragglerMonitor
from repro.serve.api import RalmRequest, RalmResponse

if TYPE_CHECKING:  # avoid a circular import; the engine owns its scheduler
    from repro.serve.engine import RalmEngine


class RalmScheduler:
    """FIFO admission + lockstep two-phase stepping over active
    sequences. ``max_active`` bounds sequences in flight (admission
    control); ``None`` admits everything immediately."""

    def __init__(self, engine: "RalmEngine",
                 max_active: Optional[int] = None):
        self.engine = engine
        self.max_active = max_active
        self.queue: deque = deque()
        self.active: list = []
        self._next_id = 0
        self._issued: set = set()
        # wave-duration outlier detection (rolling-median rule from
        # repro.runtime.fault_tolerance, reused verbatim): a wave that
        # takes >2x the recent median usually means a retrieval stall
        # or a KV-pool growth — worth a counter + a trace instant
        self.straggler = StragglerMonitor(threshold=2.0, window=32)
        self.straggler_events = 0
        self._wave_idx = 0

    # ------------------------------------------------------------------
    def submit(self, request: RalmRequest) -> int:
        """Enqueue a request; returns its id. Prefill happens at
        admission (inside ``step``), not here — but a request that can
        never be admitted (more rows than the fixed KV pool holds) is
        rejected now rather than wedging the FIFO queue later."""
        self.engine.check_admissible(request)
        if request.request_id is None:
            request.request_id = self._next_id
        elif request.request_id in self._issued:
            raise ValueError(
                f"request_id {request.request_id} already issued")
        self._issued.add(request.request_id)
        self._next_id = max(self._next_id, request.request_id) + 1
        if request.trace_id is None:
            # the observability flow id linking this request's spans
            # across tracks; request_id is already unique per engine
            request.trace_id = request.request_id
        if request.times.arrival is None:
            request.times.arrival = time.perf_counter()
        self.queue.append(request)
        return request.request_id

    def cancel(self, request_id: int) -> bool:
        """Abort a request: a queued one is dropped immediately (no
        response will be produced for it); an active one is flagged and
        cleaned up — slots released, response emitted with
        ``cancelled=True`` — at the next ``step()``. Returns whether the
        id named a live request. Call from the thread that runs
        ``step()`` (the scheduler is not locked)."""
        for req in self.queue:
            if req.request_id == request_id:
                self.queue.remove(req)
                return True
        for seq in self.active:
            if seq.request.request_id == request_id:
                seq.request.cancelled = True
                return True
        return False

    def _admit(self) -> None:
        while self.queue and (self.max_active is None or
                              len(self.active) < self.max_active):
            if not self.engine.can_admit(self.queue[0]):
                break   # strict FIFO: a deferred head blocks later work
            self.active.append(self.engine.start(self.queue.popleft()))

    @property
    def has_work(self) -> bool:
        return bool(self.queue or self.active)

    @property
    def num_active(self) -> int:
        return len(self.active)

    # -- queue observability (the gateway's backpressure signal) -------------

    @property
    def queued_requests(self) -> int:
        """Requests admitted into the FIFO but not yet started. The old
        surface only ever exposed ``queue[0]`` implicitly through
        ``step()``; backpressure thresholds need the depth itself."""
        return len(self.queue)

    def queue_age_max_s(self, now: Optional[float] = None) -> float:
        """Age of the oldest queued request (0.0 when empty) — the
        head-of-line wait a newly arriving request is signing up behind."""
        if not self.queue:
            return 0.0
        now = time.perf_counter() if now is None else now
        oldest = min((r.times.arrival for r in self.queue
                      if r.times.arrival is not None), default=now)
        return max(0.0, now - oldest)

    def tenant_depths(self) -> Dict[str, int]:
        """Queued-request count per tenant (active sequences excluded:
        they already hold slots)."""
        depths: Dict[str, int] = {}
        for req in self.queue:
            depths[req.tenant] = depths.get(req.tenant, 0) + 1
        return depths

    def queue_stats(self, now: Optional[float] = None) -> Dict[str, object]:
        """One observable snapshot for /statsz and the degrade policy."""
        return dict(
            queued_requests=self.queued_requests,
            active_requests=self.num_active,
            active_rows=sum(seq.cur.shape[0] for seq in self.active),
            queue_age_max_s=self.queue_age_max_s(now),
            tenant_depth=self.tenant_depths(),
        )

    # ------------------------------------------------------------------
    def step(self) -> List[RalmResponse]:
        """Advance every active sequence one token; returns the requests
        that completed on this step."""
        self._admit()
        finished: List[RalmResponse] = []
        # a steps<=0 request is complete at admission: prompt only
        already_done = [s for s in self.active if s.done]
        self.active = [s for s in self.active if not s.done]
        for seq in already_done:
            self.engine.release(seq)
            finished.append(self._response(seq))
        if self.engine.wave:
            return finished + self._step_wave()
        # --- per-sequence oracle path (wave=False) ---
        # phase 1: dispatch decode for every sequence (async)
        pending = [(seq, *self.engine.dispatch_decode(seq))
                   for seq in self.active]
        # phase 2a: issue every sequence's retrieval query (futures)
        searches = [self.engine.dispatch_search(seq, hidden)
                    for seq, _, hidden in pending]
        # phase 2b: one coalesced kernel dispatch for the whole wave
        self.engine.flush_searches()
        # phase 2c: resolve + integrate + sample (overlaps phase-1 work
        # still in flight on the other pool)
        still_active = []
        for (seq, logits, hidden), search in zip(pending, searches):
            self.engine.finish_step(seq, logits, hidden, search=search)
            if seq.done:
                finished.append(self._response(seq))
            else:
                still_active.append(seq)
        self.active = still_active
        return finished

    def _step_wave(self) -> List[RalmResponse]:
        """Wave-batched step body: one dispatch per phase for the whole
        active set (see the module docstring for the phases). The phase
        spans all land on the "wave" track, nested under one sched.step
        span per wave, so a Perfetto timeline shows decode / search /
        finish as adjacent slices of each step."""
        tr = self.engine.tracer
        t_wave = time.perf_counter()
        with tr.span("sched.step", "wave",
                     args={"active": len(self.active)}
                     if tr.active else None):
            decoded = self.engine.dispatch_wave(self.active)
            if self.engine.speculate_k > 0:
                # speculation harvest: verify points whose real search
                # has had its waves to land — AFTER the next decode is
                # dispatched (the overlap that hides the scan) and
                # BEFORE the search phase (so an accepted point's real
                # neighbors seed this wave's speculations)
                self.engine.spec_harvest(self.active, decoded)
            with tr.span("wave.search", "wave"):
                searches = self.engine.dispatch_search_wave(
                    self.active, decoded)
                self.engine.flush_searches()
            with tr.span("wave.finish", "wave"):
                self.engine.finish_wave(self.active, decoded, searches)
        if self.active:
            self._record_wave(time.perf_counter() - t_wave)
        done = [seq for seq in self.active if seq.done]
        if not done:
            return []
        self.active = [seq for seq in self.active if not seq.done]
        finished: List[RalmResponse] = []
        with tr.span("request.finish", "wave",
                     args={"requests": len(done)} if tr.active else None):
            for seq in done:
                if seq.spec_points:
                    # settle outstanding speculation before the response
                    # leaves the system (forced verify; discard when
                    # cancelled) — the parity guarantee is per-response
                    self.engine.spec_finalize(seq)
                self.engine.release(seq)   # slots free for queued work
                finished.append(self._response(seq))
        return finished

    def _record_wave(self, duration_s: float) -> None:
        """Feed one wave's wall time into the straggler monitor; an
        outlier (>threshold x the rolling median — the monitor needs a
        few waves of history first) bumps the counter the metrics
        adapter exports and drops a trace instant."""
        self._wave_idx += 1
        event = self.straggler.record(self._wave_idx, duration_s)
        if event is None:
            return
        self.straggler_events += 1
        tr = self.engine.tracer
        if tr.enabled:
            tr.instant("sched.straggler", "wave",
                       args={"wave": event.step,
                             "duration_ms": event.duration * 1e3,
                             "median_ms": event.median * 1e3,
                             "ratio": event.ratio})

    @staticmethod
    def _response(seq) -> RalmResponse:
        seq.request.times.finish = time.perf_counter()
        return RalmResponse(
            request_id=seq.request.request_id,
            tokens=np.asarray(seq.tokens()),
            steps=seq.step, trace=seq.request.trace,
            tenant=seq.request.tenant,
            cancelled=seq.request.cancelled,
            times=seq.request.times,
            partial_steps=seq.request.partial_steps)

    def run(self) -> List[RalmResponse]:
        """Drain the queue: step until nothing is queued or active."""
        out: List[RalmResponse] = []
        while self.has_work:
            out.extend(self.step())
        return out
