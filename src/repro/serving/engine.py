"""LLMEngine: step-based request-level serving orchestrator.

The engine wires the serving layers together around a single per-iteration
contract (vLLM-style):

* a pluggable :class:`~repro.serving.scheduler.FCFSScheduler` performs
  admission control and emits one
  :class:`~repro.serving.scheduler.SchedulerOutput` per ``step()`` — a token
  budget split across running decode slots and fixed-size chunks of queued
  prompts (``chunk_size`` set), or whole length-bucketed prefill groups
  (``chunk_size=None``, the legacy phase-based mode);
* an :class:`~repro.serving.core.EngineCore` executes it:
  ``core.step(SchedulerOutput) -> StepOutput`` — in chunked mode ONE fused
  jit'd call advances decode slots and consumes prompt chunks in the same
  batch, so a long queued prompt no longer stalls inter-token latency for
  every active slot. With ``packed=True`` that call is the token-packed
  step (only valid tokens reach the model, one dense pow-2-bucketed
  stream) instead of the padded ``(B, W)`` window;
* this module tracks slots, prefill progress, finish reasons (``length`` /
  ``eos`` / ``rejected`` / ``timeout`` / ``shed`` / ``error`` /
  ``preempted``), streaming callbacks, per-phase wall time, and the
  decompress-weight-cache counters.

Fault tolerance (see ``docs/serving.md`` "Failure semantics"):

* **Preemption-and-recompute** (``admission="preempt"``) — when the
  scheduler evicts a running slot for a higher-priority waiter, the engine
  stashes the slot's PRNG key, rewrites the request's prompt to
  ``original + generated_tokens``, and re-enqueues it; chunked prefill
  recomputes the context and the resumed stream is token-identical to the
  unpreempted run (greedy AND sampled — the restored key advances exactly
  where the uninterrupted one would).
* **NaN quarantine** — the fused step's per-slot ``isfinite`` flag demotes
  exactly the poisoned request to ``FINISH_ERROR``; every other slot keeps
  serving.
* **Watchdog recovery** — a step exception (or a step exceeding
  ``step_timeout_s``, measured around the core call so injected stalls are
  seen) requeues every live slot recompute-style, rebuilds
  :class:`EngineCore` (fused step fns are lru-cached per config — no
  recompile), and carries the fault-plan step index forward. No in-flight
  request is lost, only delayed. A step failure that recurs — after
  ``MAX_RECOVERIES_WITHOUT_TOKEN`` failed steps with no token committed in
  between, as with a kernel the compiler refuses or a device OOM — is
  re-raised instead of replayed forever; a gateway then fails the replica
  over.
* **Deadlines + load shedding** — ``Request.deadline_s`` expires queued and
  running requests as ``FINISH_TIMEOUT``; a bounded waiting queue
  (``max_waiting``) sheds the least-urgent request as ``FINISH_SHED``, and
  ``add_request`` returns the queue-fill backpressure signal.

When the model has OVSF layers and no explicit plan is set, the engine asks
the hardware-aware layer mapper (``runtime.mapper``) for a decode-shaped
ExecutionPlan against the engine's ``hw`` target. With ``calibrate=True``
the engine additionally feeds each pure-decode step's measured wall time
into a :class:`~repro.runtime.calibrate.CalibrationTable`; ``replan()``
re-runs the mapper under the accumulated measured-vs-modeled corrections.

Multi-model serving (the gateway's same-architecture batching): construct
with ``variants=M`` (the stacked-alpha variant count of the params pytree)
and a ``model_index`` callable mapping ``Request.model`` names to variant
indices — each slot's tokens then route through its own alpha bank inside
ONE fused step (see ``serving.gateway``). ``model_label`` keys the
decompress-weight-cache counters per model, so a multi-tenant process can
attribute resident dense-W bytes to the engine that generated them.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.hwmodel.perf_model import check_hw_for_device
from repro.runtime.faults import FaultPlan
from repro.serving.api import (FINISH_CANCELLED, FINISH_EOS, FINISH_ERROR,
                               FINISH_LENGTH,
                               FINISH_PREEMPTED, FINISH_REJECTED,
                               FINISH_SHED, FINISH_TIMEOUT, Request,
                               RequestOutput, SamplingParams, resolve_hw)
from repro.serving.core import _BUCKETED_FAMILIES, EngineCore, StepOutput
from repro.serving.scheduler import (FCFSScheduler, SchedulerOutput,
                                     legacy_schedule)
from repro.serving.trace import span

__all__ = ["LLMEngine", "EngineStats", "Request",
           "SamplingParams", "RequestOutput"]

# Step failures recovered in a row with no token committed between them;
# the next one is re-raised. Stall rebuilds follow a step that succeeded
# and do not count.
MAX_RECOVERIES_WITHOUT_TOKEN = 3


@dataclasses.dataclass
class EngineStats:
    steps: int = 0                # fused decode/window calls
    tokens_out: int = 0
    prefills: int = 0             # requests whose prompt completed
    prefill_batches: int = 0      # jit'd prefill calls (groups + fallbacks)
    prefill_compiles: int = 0     # actual prefill traces (<= n_buckets when
                                  # bucketing; per distinct length otherwise)
    step_compiles: int = 0        # distinct fused step shapes traced
                                  # (chunked steady state: <= 2; packed <= 3)
    chunk_tokens: int = 0         # prompt tokens consumed via chunks
    # Padding efficiency: valid tokens executed vs tokens the device batches
    # actually carried. ONE definition shared by the serving bench and the
    # calibration loop (hwmodel.perf_model.padding_efficiency).
    packed_tokens: int = 0        # valid (useful) tokens across all steps
    padded_tokens: int = 0        # batch tokens across all steps (incl. pad)
    completed: int = 0            # finished naturally (eos / length)
    rejected: int = 0
    # fault-tolerance counters (see docs/serving.md "Failure semantics")
    preemptions: int = 0          # slot evictions for recompute (transient)
    recoveries: int = 0           # watchdog core rebuilds (exception/stall)
    stalls: int = 0               # steps exceeding step_timeout_s
    timeouts: int = 0             # requests expired (FINISH_TIMEOUT)
    shed: int = 0                 # load-shed + dropped-preempt (FINISH_SHED
                                  # / FINISH_PREEMPTED)
    errors: int = 0               # quarantined non-finite-logits requests
    cancelled: int = 0            # caller-cancelled (FINISH_CANCELLED)
    prefill_s: float = 0.0        # per-phase wall time (legacy prefill)
    decode_s: float = 0.0         # pure fused decode steps
    mixed_s: float = 0.0          # fused window steps (chunks + decode)
    # decompress-weight-cache effectiveness for THIS run (delta against the
    # engine's model_label bucket of the kernels.ops counters, snapshotted
    # at engine construction — multi-tenant processes see per-model figures)
    weight_cache_hits: int = 0
    weight_cache_misses: int = 0
    weight_cache_entries: int = 0
    weight_cache_bytes: int = 0   # resident dense-W footprint (this label)
    # paged KV cache (paged=True engines; all zero otherwise). Used/bytes
    # are HIGH-WATER marks across the run — a drained engine has released
    # every page, so the instantaneous value at read time is always 0; the
    # peak is the capacity-pressure signal benches and ops care about.
    kv_pages_total: int = 0       # page pool size
    kv_pages_used: int = 0        # peak pages simultaneously granted
    kv_bytes_used: int = 0        # peak device bytes those pages pin

    @property
    def padding_efficiency(self) -> float:
        from repro.hwmodel.perf_model import padding_efficiency
        return padding_efficiency(self.packed_tokens, self.padded_tokens)

    @property
    def kv_utilization(self) -> float:
        """Peak fraction of the page pool holding live KV (0.0 when the
        engine is not paged) — the paged analogue of padding_efficiency."""
        if not self.kv_pages_total:
            return 0.0
        return self.kv_pages_used / self.kv_pages_total


class LLMEngine:
    """Continuous-batching serving engine over a fixed set of decode slots."""

    def __init__(self, params, cfg: ModelConfig, *, batch_slots: int = 4,
                 buffer_len: int = 256, eos_id: Optional[int] = None,
                 use_mapper: bool = True, hw="v5e",
                 bucketed_prefill: bool = True, admission: str = "reject",
                 scheduler=None, chunk_size: Optional[int] = None,
                 max_step_tokens: Optional[int] = None,
                 packed: bool = False, paged: bool = False,
                 page_size: int = 16, kv_pages: Optional[int] = None,
                 calibrate: bool = False,
                 max_waiting: Optional[int] = None,
                 step_timeout_s: Optional[float] = None,
                 faults: Optional[FaultPlan] = None,
                 variants: int = 0, model_index=None,
                 model_label: Optional[str] = None,
                 journal=None):
        self._base_cfg = cfg
        check_hw_for_device(hw, jax.devices()[0])
        self.hw = hw
        self.hw_label = resolve_hw(hw).name
        # Multi-model mode: variants = stacked-alpha variant count of the
        # params pytree (0 = single-model); model_index maps Request.model
        # names to variant rows. The mapper plans per-layer exec paths for a
        # single alpha bank — stacked leaves dispatch the multi spectral
        # path regardless, so skip planning rather than key traces on a
        # plan the step never consults.
        self.variants = int(variants)
        self._model_index = model_index
        if self.variants and chunk_size is None:
            raise ValueError("variants>0 requires chunk_size (multi-model "
                             "steps serve prompts via chunk tasks)")
        use_mapper = use_mapper and not self.variants
        self.cfg = self._plan_cfg(cfg, batch_slots, use_mapper, hw)
        # Keys this engine's decompress-weight-cache bucket (satellite of the
        # multi-model gateway: per-model byte attribution). Defaults to the
        # config name so single-engine stats stay self-describing.
        self.model_label = cfg.name if model_label is None else model_label
        self.params = params
        self.B = batch_slots
        self.T = buffer_len
        self.eos = eos_id
        if packed and chunk_size is None:
            raise ValueError("packed=True requires chunk_size (the packed "
                             "step serves prompts via chunk tasks)")
        if paged and chunk_size is None:
            raise ValueError("paged=True requires chunk_size (the paged "
                             "cache serves prompts via chunk tasks)")
        if chunk_size is not None and cfg.family not in _BUCKETED_FAMILIES:
            warnings.warn(
                f"chunked prefill requires a KV-cache family (got "
                f"{cfg.family!r}: recurrent state would run through window "
                f"padding); falling back to phase-based serving", stacklevel=2)
            chunk_size = None
            packed = False
            paged = False
        self.chunk = chunk_size
        self.packed = packed
        self.paged = paged
        self.page_size = page_size
        self.kv_pages = kv_pages
        if packed and max_step_tokens is None:
            # Default packed token budget == the mixed-step bucket, so the
            # typical chunk-bearing step fills its pow-2 shape exactly
            # (padding efficiency ~1.0 when prompt tokens are plentiful).
            from repro.serving.scheduler import pack_bucket
            max_step_tokens = pack_bucket(0, batch_slots, chunk_size, True)
        self.max_step_tokens = max_step_tokens
        self.faults = faults
        self.step_timeout_s = step_timeout_s
        self.core = EngineCore(params, self.cfg, batch_slots=batch_slots,
                               buffer_len=buffer_len,
                               window=chunk_size or 0, packed=packed,
                               paged=paged, page_size=page_size,
                               kv_pages=kv_pages, faults=faults,
                               variants=self.variants)
        self.bucketed = bucketed_prefill and self.core.supports_bucketing
        self.scheduler = scheduler if scheduler is not None else FCFSScheduler(
            buffer_len, admission=admission, bucketing=self.bucketed,
            chunk_size=chunk_size, max_waiting=max_waiting,
            page_size=page_size if paged else None,
            total_pages=self.core.pager.P if paged else None)
        if (self.packed or self.paged) and not hasattr(self.scheduler,
                                                       "schedule"):
            raise ValueError(
                "packed/paged mode requires a step scheduler (schedule "
                "method): legacy add/next_group schedulers emit whole "
                "prefill groups, which this core cannot execute")
        self.slots: list[Optional[Request]] = [None] * batch_slots
        self.slot_remaining = np.zeros(batch_slots, np.int32)
        # prompt tokens consumed per slot (== prompt_len once decoding)
        self._prefill_done = np.zeros(batch_slots, np.int64)
        self.stats = EngineStats()
        if self.paged:
            self.stats.kv_pages_total = self.core.pager.P
        self._finished: list[RequestOutput] = []
        from repro.kernels import ops as _ops
        self._ops = _ops
        self._wc_base = _ops.weight_cache_stats(self.model_label)
        self.calibrate = calibrate
        from repro.runtime.calibrate import CalibrationTable
        self.calibration = CalibrationTable()
        # Durability (serving.journal): admissions/tokens/finishes append to
        # the write-ahead log; flush() group-commits once per step. None =
        # non-durable (the default). A broken journal degrades silently to
        # None-like behaviour — it never blocks the step loop.
        self.journal = journal
        self._failures_since_token = 0

    # The fused decode+sample callable; kept assignable for instrumentation.
    @property
    def _step_fn(self):
        return self.core._step_fn

    @_step_fn.setter
    def _step_fn(self, fn):
        self.core._step_fn = fn

    @staticmethod
    def _plan_cfg(cfg: ModelConfig, batch_slots: int, use_mapper: bool,
                  hw) -> ModelConfig:
        if not use_mapper or not cfg.ovsf.enable or cfg.exec_plan is not None:
            return cfg
        from repro.runtime import mapper
        shape = ShapeConfig("serve_decode", 1, batch_slots, "decode")
        # weight_reuse=1: the decode step is jit'd, so the eager decompress
        # cache cannot amortise generation across steps inside the compiled
        # program — don't let the model assume it.
        return mapper.apply_plan(
            cfg, mapper.plan_model(cfg, shape, hw=hw, weight_reuse=1))

    # -- request intake ----------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Admit a request (False + a ``rejected``/``shed`` RequestOutput if
        it would overflow the cache buffer under the scheduler's admission
        policy, or was load-shed from a full bounded queue)."""
        req.t_submit = time.perf_counter()
        if self.journal is not None:
            # WAL rule: the admission record precedes any effect of the
            # request (idempotent by rid — failover/recovery re-admission
            # never double-journals). A rejected request still gets its
            # terminal `fin` record via _finalize below.
            self.journal.admit_request(req)
        admitted = self.scheduler.add(req)
        if not admitted:
            self._finalize(req)
        self._drain_shed()      # the bounded queue may have evicted a waiter
        return admitted

    def add_request(self, req: Request) -> tuple:
        """``submit`` plus the backpressure signal: returns ``(admitted,
        backpressure)`` where backpressure is the waiting-queue fill
        fraction in [0, 1] (0.0 when the queue is unbounded). Callers use
        it to slow their offered load before shedding starts."""
        admitted = self.submit(req)
        return admitted, self.backpressure

    @property
    def backpressure(self) -> float:
        return float(getattr(self.scheduler, "backpressure", 0.0))

    def outputs(self) -> list[RequestOutput]:
        """Finished (completed + rejected) requests, in finish order."""
        return list(self._finished)

    # -- scheduling --------------------------------------------------------

    def _free_slots(self) -> list[int]:
        return [i for i in range(self.B) if self.slots[i] is None]

    def _running_view(self) -> list:
        return [(i, self.slots[i], int(self._prefill_done[i]))
                for i in range(self.B) if self.slots[i] is not None]

    def _schedule(self) -> SchedulerOutput:
        running, free = self._running_view(), self._free_slots()
        if hasattr(self.scheduler, "schedule"):
            return self.scheduler.schedule(
                running, free, token_budget=self.max_step_tokens,
                exact_prefill=not self.bucketed)
        # Legacy three-method scheduler (add/next_group/__len__): adapt its
        # whole-group surface onto the step contract.
        return legacy_schedule(self.scheduler, running, free,
                               not self.bucketed)

    # -- token commit ------------------------------------------------------

    def _commit_first_token(self, i: int, req: Request, tok: int) -> None:
        req.emit(tok)
        self.slots[i] = req
        self._prefill_done[i] = req.prompt_len
        # out_tokens already includes this emission; for a recomputed
        # request it also includes everything generated pre-preemption, so
        # the remaining budget resumes exactly where the eviction cut it
        self.slot_remaining[i] = req.max_new_tokens - len(req.out_tokens)
        self.stats.prefills += 1
        self.stats.tokens_out += 1
        # eos outranks length (same priority as the decode path): a request
        # whose last allowed token is eos stopped naturally, not truncated
        if self.eos is not None and tok == self.eos:
            self._finish(i, FINISH_EOS)
        elif self.slot_remaining[i] <= 0:
            self._finish(i, FINISH_LENGTH)

    def _finish(self, i: int, reason: str) -> None:
        req = self.slots[i]
        req.finish_reason = reason
        self.slots[i] = None
        if self.core.pager is not None:
            self.core.pager.release(i)
        # re-arm the freed slot as greedy so one finished sampling request
        # doesn't pin every later fused step on the slow mixed-sampling
        # branch (the all-greedy fast path tests ALL B rows)
        self.core.clear_sampling(i)
        self._finalize(req)

    def _finalize(self, req: Request) -> None:
        """Book a terminal request: output record, per-reason counter, and
        the exactly-once ``on_finish`` notification."""
        out = req.output()
        self._finished.append(out)
        r = req.finish_reason
        st = self.stats
        if r in (FINISH_EOS, FINISH_LENGTH):
            st.completed += 1
        elif r == FINISH_REJECTED:
            st.rejected += 1
        elif r == FINISH_TIMEOUT:
            st.timeouts += 1
        elif r in (FINISH_SHED, FINISH_PREEMPTED):
            st.shed += 1
        elif r == FINISH_ERROR:
            st.errors += 1
        elif r == FINISH_CANCELLED:
            st.cancelled += 1
        if self.journal is not None:
            # The terminal record is fsync'd BEFORE on_finish surfaces the
            # result: anything a client may have observed is durable, so a
            # crash can never re-execute an already-answered request.
            self.journal.finish(req.rid, r)
        if req.on_finish is not None and not req._notified:
            req._notified = True
            req.on_finish(out)

    def _drain_shed(self) -> None:
        """Finalize load-shed victims the scheduler evicted from its
        bounded queue (they were already marked SHED/PREEMPTED)."""
        shed = getattr(self.scheduler, "shed", None)
        if shed:
            for req in shed:
                self._finalize(req)
            shed.clear()

    # -- the step loop -----------------------------------------------------

    def step(self) -> int:
        """One scheduler iteration: emit a SchedulerOutput, execute it as
        one ``EngineCore.step``, commit the results. Returns the remaining
        work — occupied slots after the step plus queued waiting requests —
        so ``while eng.step(): ...`` drains fully even when every occupied
        slot finishes in the same iteration (0 = engine fully idle).

        Failure is a first-class outcome here: expired deadlines finish
        FINISH_TIMEOUT before scheduling; scheduler-decided preemptions are
        executed (evict + recompute-requeue) before the device call; a step
        exception triggers watchdog recovery instead of propagating."""
        with span("engine.step"):
            return self._step()

    def _step(self) -> int:
        with span("engine.schedule"):
            self._expire_deadlines()
            self._drain_shed()
            so = self._schedule()
            for i in so.preempt_slots:  # evict + recompute-requeue
                self._requeue_slot(i, preempt=True)
            self._drain_shed()          # requeue into a full queue sheds
        if self.paged:
            with span("engine.page_gate"):
                so = self._page_gate(so)    # grant KV pages / preempt on OOM
                self._drain_shed()
        if so.empty:
            return self._remaining()
        last = np.zeros(self.B, np.int32)
        for i in so.decode_slots:
            last[i] = self.slots[i].out_tokens[-1]
        for c in so.chunks:             # bind newly admitted requests
            if c.start == 0:
                self._bind(c.slot, c.req)
                if self.variants:       # route the slot to its alpha variant
                    self.core.model_ids[c.slot] = (
                        self._model_index(c.req.model)
                        if self._model_index is not None
                        and c.req.model is not None else 0)
        for pg in so.prefill_groups:    # legacy whole-prompt prefill
            for i, req in pg.slot_reqs:
                self._bind(i, req)
        t0 = time.perf_counter()
        try:
            # Scope the decompress weight cache to this engine's model label
            # so a multi-tenant process attributes hits/bytes per model.
            with self._ops.weight_cache_scope(self.model_label):
                out = self.core.step(so, last)
        except Exception:               # watchdog: step crashed — recover
            if self._failures_since_token >= MAX_RECOVERIES_WITHOUT_TOKEN:
                raise                   # it recurs: surface, do not replay
            self._failures_since_token += 1
            self._recover()
            return self._remaining()
        # Stall watchdog: measure around the core call (injected/organic
        # stalls may fall outside the core's phase timers). The step's
        # output is valid — commit it first, then rebuild so the next step
        # runs on a fresh core; recompute keeps streams identical.
        stalled = (self.step_timeout_s is not None
                   and time.perf_counter() - t0 > self.step_timeout_s)
        with span("engine.commit"):
            self._commit(so, out)
            if self.journal is not None:
                self.journal.flush()    # group-commit this step's records
        if stalled:
            self.stats.stalls += 1
            self._recover()
        return self._remaining()

    def _bind(self, i: int, req: Request) -> None:
        """Put a request's first chunk (or whole prompt) in slot ``i``. The
        first binding admits it: it stamps ``t_admit``, which a preempted
        request keeps, and marks the profile with an ``engine.admit`` span
        that carries the queue wait."""
        self.slots[i] = req
        self._prefill_done[i] = 0
        if req.t_admit is None:
            req.t_admit = time.perf_counter()
            with span("engine.admit", rid=req.rid,
                      queue_wait_s=req.t_admit - req.t_submit):
                pass

    def _page_gate(self, so: SchedulerOutput) -> SchedulerOutput:
        """Grant KV pages for everything the scheduler just emitted, treating
        page exhaustion exactly like cache-overflow admission pressure.

        Must-run work — decodes and chunks continuing an already-started
        prompt — cannot be deferred (the slot's context is live), so a pool
        shortfall preempts the lowest-priority / youngest scheduled slot
        (the scheduler's own victim order) for recompute until the rest
        fits. New prompts (``start == 0``) are best-effort: an ungrantable
        one goes back to the waiting queue with its original arrival order
        and retries next step once decodes finish and release pages.
        """
        pager = self.core.pager
        pos = self.core._host_pos
        decodes = list(so.decode_slots)
        run_chunks = [c for c in so.chunks if c.start > 0]
        new_chunks = [c for c in so.chunks if c.start == 0]

        def shortfall() -> int:
            need = (sum(pager.pages_needed(i, int(pos[i]) + 1)
                        for i in decodes)
                    + sum(pager.pages_needed(c.slot, c.start + c.length)
                          for c in run_chunks))
            return need - pager.free_pages

        while shortfall() > 0:
            cands = ([(i, self.slots[i]) for i in decodes]
                     + [(c.slot, self.slots[c.slot]) for c in run_chunks])
            if len(cands) <= 1:
                break   # one slot always fits: admission caps it at buffer
            victim = min(cands, key=lambda t: (t[1].priority,
                                               -(t[1]._sched_seq or 0)))[0]
            decodes = [i for i in decodes if i != victim]
            run_chunks = [c for c in run_chunks if c.slot != victim]
            self._requeue_slot(victim, preempt=True)    # releases its pages
        for i in decodes:
            pager.grant(i, int(pos[i]) + 1)
        for c in run_chunks:
            pager.grant(c.slot, c.start + c.length)
        kept_new = []
        for c in new_chunks:
            if pager.grant(c.slot, c.start + c.length):
                kept_new.append(c)
            elif hasattr(self.scheduler, "requeue"):
                self.scheduler.requeue(c.req)
            else:
                self.scheduler.add(c.req)
        keep = {id(c) for c in run_chunks} | {id(c) for c in kept_new}
        chunks = tuple(c for c in so.chunks if id(c) in keep)
        st = self.stats
        st.kv_pages_used = max(st.kv_pages_used, pager.used_pages)
        st.kv_bytes_used = max(st.kv_bytes_used, pager.used_bytes)
        return dataclasses.replace(
            so, decode_slots=tuple(decodes), chunks=chunks,
            n_scheduled_tokens=len(decodes) + sum(c.length for c in chunks))

    def _expire_deadlines(self) -> None:
        """Finish expired requests as FINISH_TIMEOUT — queued requests via
        the scheduler, running ones straight out of their slot."""
        now = time.perf_counter()
        if hasattr(self.scheduler, "pop_expired"):
            for req in self.scheduler.pop_expired(now):
                self._finalize(req)
        for i in range(self.B):
            req = self.slots[i]
            if req is not None and req.expired:
                self._finish(i, FINISH_TIMEOUT)

    def _stash_slot(self, i: int) -> Request:
        """Evict slot ``i`` recompute-style and return its request: stash
        the PRNG key (sampled streams resume exactly), rewrite the prompt to
        original + generated tokens (chunked prefill rebuilds the context),
        reset prefill progress, release KV pages. The caller decides where
        the request goes next — this scheduler (requeue), another replica
        (failover ``adopt``), or nowhere."""
        req = self.slots[i]
        self.slots[i] = None
        if self.core.seeded[i]:     # else the step that binds it failed
            req.resume_key = np.array(self.core.keys[i])
        self.core.clear_sampling(i)
        if self.core.pager is not None:
            self.core.pager.release(i)  # victim pages free immediately
        self._prefill_done[i] = 0
        self.slot_remaining[i] = 0
        if req.prompt_len_orig is None:
            req.prompt_len_orig = req.prompt_len
        # tokens generated since the LAST rewrite (the prompt already holds
        # everything generated before an earlier preemption)
        new_tail = req.out_tokens[req.prompt_len - req.prompt_len_orig:]
        if new_tail:
            req.prompt = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(new_tail, np.int32)])
        return req

    def _requeue_slot(self, i: int, *, preempt: bool) -> None:
        """``_stash_slot`` + re-enqueue on this engine's own scheduler.
        ``preempt=True`` books it as a preemption; recovery requeues are
        not preemptions."""
        req = self._stash_slot(i)
        if preempt:
            req.preemptions += 1
            self.stats.preemptions += 1
        if hasattr(self.scheduler, "requeue"):
            self.scheduler.requeue(req)
        else:                           # legacy scheduler: re-admit FCFS
            self.scheduler.add(req)

    # -- fleet-level hooks (gateway failover / drain / cancellation) --------

    def adopt(self, req: Request) -> None:
        """Accept a request migrated from another replica (failover) or
        displaced by a group rebuild. Bypasses admission — the request was
        already admitted by an identically-configured engine and its total
        cache need (original prompt + max_new) is unchanged under the
        recompute prompt rewrite."""
        if hasattr(self.scheduler, "requeue"):
            self.scheduler.requeue(req)
        else:
            self.scheduler.add(req)
        self._drain_shed()

    def recover_from_journal(self, *, wire=None) -> list:
        """Crash recovery: re-admit every non-terminal journaled request
        through the preempt-and-recompute path and return them (adoption
        order == original admission order, so recovered streams are
        token-identical to the fault-free run — greedy AND sampled, the
        resume key is re-derived from the seed).

        A request whose deadline expired while the process was down is
        finished as ``FINISH_TIMEOUT`` immediately — never silently
        resumed — with its exactly-once ``on_finish`` firing here.

        ``wire(req)``, when given, attaches callbacks (``stream`` /
        ``on_finish``) to each rebuilt request before it is adopted or
        finalized. The journal is compacted afterwards, so the replayed
        segments collapse to one snapshot record per entry."""
        if self.journal is None:
            return []
        recovered = []
        for entry in self.journal.live_entries():
            req = entry.to_request()
            if wire is not None:
                wire(req)
            if req.expired:
                req.finish_reason = FINISH_TIMEOUT
                self._finalize(req)
                continue
            self.adopt(req)
            recovered.append(req)
        self.journal.compact()
        return recovered

    def drain_requests(self) -> list:
        """Strip every live request off this engine — running slots are
        evicted recompute-style (token-identical resume elsewhere), then the
        waiting queue is appended in priority-FCFS order. Used by the
        gateway to fail over a DEAD replica or rebuild a group after an
        alpha-bank repair; the drained engine is left empty but usable."""
        out = [self._stash_slot(i) for i in range(self.B)
               if self.slots[i] is not None]
        if hasattr(self.scheduler, "pop_all"):
            out.extend(self.scheduler.pop_all())
        else:                           # legacy scheduler: pop FCFS groups
            while len(self.scheduler):
                pg = self.scheduler.next_group(self.B)
                if pg is None:
                    break
                out.extend(pg.requests)
        return out

    def cancel(self, req: Request) -> bool:
        """Cancel one in-flight request (e.g. the SSE client disconnected):
        a running request is finished as FINISH_CANCELLED — releasing its
        slot and KV pages immediately — and a queued one is withdrawn.
        Returns False when the request is not live here (already finished
        or routed elsewhere)."""
        if req.done:
            return False
        for i in range(self.B):
            if self.slots[i] is req:
                self._finish(i, FINISH_CANCELLED)
                return True
        if hasattr(self.scheduler, "remove") and self.scheduler.remove(req):
            req.finish_reason = FINISH_CANCELLED
            self._finalize(req)
            return True
        return False

    def _recover(self) -> None:
        """Watchdog recovery: requeue every live slot recompute-style, then
        rebuild the core. Compile state carries over — the fused step fns
        are lru-cached per config, so the rebuilt core re-uses their traces;
        the fault-plan step index carries forward so a step-pinned fault
        fires once per run, not once per core."""
        with span("engine.recover"):
            for i in range(self.B):
                if self.slots[i] is not None:
                    self._requeue_slot(i, preempt=False)
            self._drain_shed()
            old = self.core
            self.core = EngineCore(self.params, self.cfg, batch_slots=self.B,
                                   buffer_len=self.T, window=self.chunk or 0,
                                   packed=self.packed, paged=self.paged,
                                   page_size=self.page_size,
                                   kv_pages=self.kv_pages, faults=self.faults,
                                   variants=self.variants)
            self.core.step_idx = old.step_idx
            self.core.prefill_compiles = old.prefill_compiles
            self.core.step_shapes = old.step_shapes
            self.stats.recoveries += 1

    def _remaining(self) -> int:
        return (sum(s is not None for s in self.slots)
                + len(self.scheduler))

    def _commit(self, so: SchedulerOutput, out: StepOutput) -> None:
        if out.first_tokens or out.decode_tokens:
            self._failures_since_token = 0
        for c in so.chunks:
            self._prefill_done[c.slot] += c.length
        self.stats.chunk_tokens += sum(c.length for c in so.chunks)
        # NaN quarantine: a slot whose emitted logits went non-finite got no
        # token this step; its request is terminal, the engine keeps serving
        for i in out.bad_slots:
            self._finish(i, FINISH_ERROR)
        for i, tok in out.first_tokens.items():
            # journal the token before any finish it may trigger, so the
            # `tok` record always precedes its request's `fin` record
            if self.journal is not None:
                self.journal.tokens(self.slots[i].rid, (tok,))
            self._commit_first_token(i, self.slots[i], tok)
        for i, tok in out.decode_tokens.items():
            req = self.slots[i]
            if self.journal is not None:
                self.journal.tokens(req.rid, (tok,))
            req.emit(tok)
            self.stats.tokens_out += 1
            self.slot_remaining[i] -= 1
            if self.eos is not None and tok == self.eos:
                self._finish(i, FINISH_EOS)
            elif self.slot_remaining[i] <= 0:
                self._finish(i, FINISH_LENGTH)
        st = self.stats
        st.prefill_s += out.prefill_s
        st.decode_s += out.decode_s
        st.mixed_s += out.mixed_s
        st.packed_tokens += out.n_valid_tokens
        st.padded_tokens += out.n_batch_tokens
        if so.decode_slots or so.chunks:
            st.steps += 1
        st.prefill_batches += sum(
            len(pg.slot_reqs) if pg.exact else 1 for pg in so.prefill_groups)
        st.prefill_compiles = self.core.prefill_compiles
        st.step_compiles = len(self.core.step_shapes)
        wc = self._ops.weight_cache_stats(self.model_label)
        st.weight_cache_hits = wc["hits"] - self._wc_base["hits"]
        st.weight_cache_misses = wc["misses"] - self._wc_base["misses"]
        st.weight_cache_entries = wc["entries"]
        st.weight_cache_bytes = wc["bytes"]
        if (self.calibrate and out.decode_s > 0.0 and not so.chunks
                and not so.prefill_groups and self.cfg.exec_plan is not None):
            from repro.runtime.calibrate import update_from_step
            update_from_step(self.calibration, self.cfg.exec_plan,
                             out.decode_s, self.hw_label)

    def run_until_drained(self, max_steps: int = 10_000) -> EngineStats:
        for _ in range(max_steps):
            if self.step() == 0:
                break
        return self.stats

    # -- measured-vs-modeled calibration -----------------------------------

    def replan(self):
        """Re-run the mapper under the accumulated calibration table.

        Returns the corrected decode-shaped ExecutionPlan; compare against
        ``self.cfg.exec_plan`` to see which layers the measured-vs-modeled
        loop re-mapped. (The engine does not hot-swap the plan — a new plan
        keys new jit traces, so callers rebuild the engine to adopt it.)
        """
        from repro.runtime import mapper
        shape = ShapeConfig("serve_decode", 1, self.B, "decode")
        return mapper.plan_model(self._base_cfg, shape, hw=self.hw,
                                 weight_reuse=1, calibration=self.calibration)


