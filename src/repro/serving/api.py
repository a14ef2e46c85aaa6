"""Request-level serving API: the public surface of ``repro.serving``.

Three layers sit behind this module (vLLM-style split, sized for this repo):

  ``api``        SamplingParams / Request / RequestOutput, HW targets
  ``scheduler``  pluggable admission + length-bucketed batching (FCFS default)
  ``core``       EngineCore: stacked cache, jit'd bucketed prefill, ONE fused
                 decode+sample call per token
  ``engine``     LLMEngine orchestrator

Requests carry their own :class:`SamplingParams` (greedy / temperature /
top-k with a per-request seed) and an optional streaming token callback;
finished requests surface as :class:`RequestOutput` with a finish reason.

HW targets: every mapper/perf-model entry point takes ``hw`` as either an
``hwmodel.perf_model.HW`` instance or a registered name. The presets
(``v5e``/``v5p``/``v6e``/``cpu``) live in ``hwmodel.perf_model``; this module
re-exports the registry so serving callers never import hwmodel directly.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np

from repro.hwmodel.perf_model import (HW, hw_by_name, hw_names, register_hw,
                                      resolve_hw)

# An HW target *is* a perf-model HW instance; the name is the registry key.
HWTarget = HW

__all__ = [
    "SamplingParams", "Request", "RequestOutput",
    "FINISH_LENGTH", "FINISH_EOS", "FINISH_REJECTED",
    "FINISH_TIMEOUT", "FINISH_SHED", "FINISH_ERROR", "FINISH_PREEMPTED",
    "FINISH_EVICTED", "FINISH_CANCELLED",
    "HWTarget", "HW", "hw_by_name", "hw_names", "register_hw", "resolve_hw",
]

FINISH_LENGTH = "length"        # hit max_new_tokens
FINISH_EOS = "eos"              # sampled the eos token
FINISH_REJECTED = "rejected"    # failed admission (would overflow the cache)
FINISH_TIMEOUT = "timeout"      # deadline_s expired (queued or mid-flight)
FINISH_SHED = "shed"            # load-shed from a full bounded waiting queue
FINISH_ERROR = "error"          # quarantined: non-finite emitted logits
FINISH_PREEMPTED = "preempted"  # preempted AND could not be re-admitted
                                # (bounded queue full of higher-priority
                                # work); otherwise preemption is transient —
                                # the request is recomputed, never finished
FINISH_EVICTED = "evicted"      # gateway: the target model's weights are
                                # evicted and could not be made resident
                                # within the byte budget — a distinct
                                # backpressure signal, never a silent queue
                                # against a cold model
FINISH_CANCELLED = "cancelled"  # caller abandoned the request (e.g. SSE
                                # client disconnect): the slot and its KV
                                # pages are released immediately


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding parameters.

    ``temperature <= 0`` means greedy argmax (top_k/seed are then unused).
    ``top_k == 0`` means no top-k filtering. ``seed`` fully determines the
    sampled token stream for a given model/prompt: sampling state is kept
    per slot and advances once per generated token, so results do not
    depend on batch composition or slot assignment.
    """
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


GREEDY = SamplingParams()


@dataclasses.dataclass
class Request:
    """One generation request. Mutable fields track in-flight progress.

    ``priority`` orders the waiting queue (higher first, FCFS within a
    level) and arms preemption under ``admission="preempt"``: a waiting
    request with strictly higher priority may evict the lowest-priority
    running slot (the victim is recomputed, never lost). ``deadline_s`` is
    a wall-clock budget relative to submission; an expired request —
    queued or mid-flight — finishes as ``FINISH_TIMEOUT`` with whatever
    tokens it has. ``on_finish`` fires exactly once with the final
    :class:`RequestOutput`, for every terminal reason including
    ``rejected``/``shed``/``timeout``/``error``.
    """
    rid: int
    prompt: np.ndarray                  # (S,) int32 token ids
    max_new_tokens: int = 16
    sampling: SamplingParams = GREEDY
    # gateway routing target (registry model name); None = single-model
    # engines, which ignore it
    model: Optional[str] = None
    # called as stream(rid, token) the moment each token is committed
    stream: Optional[Callable[[int, int], None]] = None
    priority: int = 0                   # higher = more urgent
    deadline_s: Optional[float] = None  # seconds after t_submit
    # exactly-once client semantics: a client-chosen retry-dedup key. The
    # journal persists it with the admission record and the gateway maps it
    # to the request's durable result, so retrying the same key — across
    # any number of process crashes — attaches to or replays the ONE
    # execution instead of starting another (see serving.journal).
    idempotency_key: Optional[str] = None
    # called exactly once with the final RequestOutput (any finish reason)
    on_finish: Optional[Callable[["RequestOutput"], None]] = None
    out_tokens: list = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    # latency bookkeeping: the engine stamps submission and the first
    # binding to a slot (kept across preemptions: queue wait is
    # t_admit - t_submit); emit stamps tokens
    t_submit: float = 0.0
    t_admit: Optional[float] = None
    token_times: list = dataclasses.field(default_factory=list)
    # -- preemption/recompute state (engine-managed) ------------------------
    preemptions: int = 0                # times this request lost its slot
    # PRNG key stashed at preemption so a recomputed sampled stream resumes
    # exactly where the unpreempted run would be (None = seed fresh)
    resume_key: Optional[np.ndarray] = None
    # original prompt length; ``prompt`` is rewritten to prompt + generated
    # tokens on preemption so chunked prefill recomputes the context
    prompt_len_orig: Optional[int] = None
    _notified: bool = False             # on_finish fired (exactly-once guard)
    # scheduler-managed FCFS sequence number; survives requeue so a
    # preempted request resumes ahead of younger same-priority waiters
    _sched_seq: Optional[int] = None

    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    @property
    def prompt_len(self) -> int:
        return int(len(self.prompt))

    @property
    def expired(self) -> bool:
        """Deadline elapsed (False when no deadline or not yet submitted)."""
        return (self.deadline_s is not None and self.t_submit > 0.0
                and time.perf_counter() - self.t_submit > self.deadline_s)

    def emit(self, tok: int) -> None:
        self.token_times.append(time.perf_counter())
        self.out_tokens.append(tok)
        if self.stream is not None:
            self.stream(self.rid, tok)

    def output(self) -> "RequestOutput":
        ttft = (self.token_times[0] - self.t_submit
                if self.token_times and self.t_submit else None)
        itls = tuple(b - a for a, b in zip(self.token_times,
                                           self.token_times[1:]))
        plen = (self.prompt_len_orig if self.prompt_len_orig is not None
                else self.prompt_len)
        return RequestOutput(rid=self.rid, prompt_len=plen,
                             tokens=tuple(self.out_tokens),
                             finish_reason=self.finish_reason,
                             ttft_s=ttft, itls_s=itls,
                             preemptions=self.preemptions)


@dataclasses.dataclass(frozen=True)
class RequestOutput:
    """Immutable result of a finished (or rejected) request."""
    rid: int
    prompt_len: int
    tokens: tuple
    finish_reason: Optional[str]
    # time-to-first-token (submission -> first committed token; None when no
    # token was emitted) and the inter-token latency samples between
    # consecutive committed tokens — the raw material for the serving
    # bench's p50/p95 percentiles.
    ttft_s: Optional[float] = None
    itls_s: tuple = ()
    preemptions: int = 0    # times the request was preempted + recomputed

    @property
    def n_tokens(self) -> int:
        return len(self.tokens)
