"""Fault-tolerant serving: preemption-and-recompute equivalence, NaN
quarantine, watchdog recovery, deadlines, load shedding, chaos injectors.

The acceptance bar (ISSUE 6): a preempted+recomputed request's token stream
is identical to the unpreempted run (greedy AND sampled); with injected NaN
logits and an injected step exception the engine finishes every healthy
request, quarantines exactly the poisoned one, records a recovery, and the
post-recovery streams match the fault-free run.
"""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import registry as R
from repro.runtime.faults import Fault, FaultPlan, InjectedFault, parse_fault
from repro.serving import (FCFSScheduler, FINISH_EOS, FINISH_ERROR,
                           FINISH_LENGTH, FINISH_PREEMPTED, FINISH_SHED,
                           FINISH_TIMEOUT, LLMEngine, Request, SamplingParams)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("tinyllama_1_1b")
    params = R.model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _req(rid, plen, max_new=6, vocab=512, **kw):
    rng = np.random.default_rng(rid)
    return Request(rid, rng.integers(0, vocab, plen, dtype=np.int32),
                   max_new_tokens=max_new, **kw)


def _outs(eng):
    return {o.rid: o for o in eng.outputs()}


# ---------------------------------------------------------------------------
# FaultPlan: parsing, determinism, injector semantics (no model needed)
# ---------------------------------------------------------------------------

def test_parse_fault_specs():
    f = parse_fault("nan:step=3,slot=1")
    assert f.kind == "nan" and f.step == 3 and f.slot == 1
    f = parse_fault("fail:step=7,every=50")
    assert f.kind == "fail" and f.every == 50
    f = parse_fault("delay:p=0.1,s=0.002")
    assert f.kind == "delay" and f.p == 0.1 and f.delay_s == 0.002
    for bad in ("boom:step=1", "nan:", "nan:step=1,p=0.5", "delay:step=1",
                "nan:bogus=1"):
        with pytest.raises(ValueError):
            parse_fault(bad)


def test_fault_firing_is_deterministic():
    plan = FaultPlan.parse(["nan:p=0.3", "fail:step=5,every=10"], seed=7)
    fired_a = [tuple(f.kind for f in plan.at(s)) for s in range(40)]
    fired_b = [tuple(f.kind for f in plan.at(s)) for s in range(40)]
    assert fired_a == fired_b                       # pure function of step
    fails = [s for s in range(40) if any(f.kind == "fail"
                                         for f in plan.at(s))]
    assert fails == [5, 15, 25, 35]                 # step + every recurrence
    # a different seed reshuffles the probabilistic firings
    plan2 = FaultPlan.parse(["nan:p=0.3", "fail:step=5,every=10"], seed=8)
    nans = lambda p: [s for s in range(40)          # noqa: E731
                      if any(f.kind == "nan" for f in p.at(s))]
    assert nans(plan) and nans(plan) != nans(plan2)


def test_poison_row_targets_exact_slot():
    plan = FaultPlan.parse(["nan:step=2,slot=1"])
    assert plan.poison_row(0, 4) is None            # nothing fires
    row = plan.poison_row(2, 4)
    assert np.isnan(row[1]) and np.isfinite(row[[0, 2, 3]]).all()


def test_raise_or_delay_raises_injected_fault():
    plan = FaultPlan.parse(["fail:step=1"])
    plan.raise_or_delay(0)                          # no-op off-step
    with pytest.raises(InjectedFault):
        plan.raise_or_delay(1)


# ---------------------------------------------------------------------------
# Scheduler: priority queue, bounded queue + shedding, deadlines, preemption
# ---------------------------------------------------------------------------

def test_waiting_queue_orders_by_priority_then_fcfs():
    s = FCFSScheduler(128, chunk_size=8)
    for rid, prio in [(0, 0), (1, 2), (2, 0), (3, 2)]:
        assert s.add(_req(rid, 10, priority=prio))
    so = s.schedule([], [0, 1, 2, 3], token_budget=64)
    # priority 2 first (FCFS within: 1 before 3), then priority 0 (0, 2)
    assert [c.req.rid for c in so.chunks] == [1, 3, 0, 2]


def test_bounded_queue_sheds_least_urgent():
    s = FCFSScheduler(128, chunk_size=8, max_waiting=2)
    assert s.add(_req(0, 10, priority=1))
    assert s.add(_req(1, 10, priority=0))
    # full queue + lower-priority newcomer: the newcomer is shed
    loser = _req(2, 10, priority=0)
    assert not s.add(loser)
    assert loser.finish_reason == FINISH_SHED
    # full queue + higher-priority newcomer: the least-urgent waiter is shed
    winner = _req(3, 10, priority=5)
    assert s.add(winner)
    assert len(s.shed) == 1 and s.shed[0].rid == 1
    assert s.shed[0].finish_reason == FINISH_SHED
    assert sorted(r.rid for r in s.waiting) == [0, 3]


def test_backpressure_signal():
    s = FCFSScheduler(128, chunk_size=8, max_waiting=4)
    assert s.backpressure == 0.0
    for rid in range(2):
        s.add(_req(rid, 10))
    assert s.backpressure == 0.5
    assert FCFSScheduler(128).backpressure == 0.0   # unbounded: always 0


def test_requeue_into_full_queue_of_equals_drops_preempted():
    s = FCFSScheduler(128, chunk_size=8, max_waiting=1)
    assert s.add(_req(0, 10, priority=3))
    victim = _req(1, 10, priority=3)
    victim._sched_seq = 99                          # younger than the waiter
    assert not s.requeue(victim)
    assert victim.finish_reason == FINISH_PREEMPTED
    assert victim in s.shed


def test_pop_expired_marks_timeout():
    s = FCFSScheduler(128, chunk_size=8)
    fresh = _req(0, 10)
    stale = _req(1, 10, deadline_s=0.01)
    now = time.perf_counter()
    fresh.t_submit = stale.t_submit = now - 1.0     # submitted 1s ago
    s.add(fresh)
    s.add(stale)
    expired = s.pop_expired(now)
    assert [r.rid for r in expired] == [1]
    assert stale.finish_reason == FINISH_TIMEOUT
    assert len(s) == 1


def test_preempt_admission_requires_chunking():
    with pytest.raises(ValueError):
        FCFSScheduler(128, admission="preempt")


def test_scheduler_emits_preempt_for_higher_priority_waiter():
    s = FCFSScheduler(128, admission="preempt", chunk_size=8)
    lo = [_req(i, 10, priority=0) for i in range(2)]
    for r in lo:
        s.add(r)
    so = s.schedule([], [0, 1], token_budget=64)     # both admitted
    running = [(c.slot, c.req, 10) for c in so.chunks]
    assert s.add(_req(9, 10, priority=5))
    so = s.schedule(running, [], token_budget=64)
    assert len(so.preempt_slots) == 1               # one eviction per step
    # victim is the youngest lowest-priority slot; it is NOT scheduled work
    assert so.preempt_slots[0] not in [c.slot for c in so.chunks]
    # equal-priority waiters never preempt
    s2 = FCFSScheduler(128, admission="preempt", chunk_size=8)
    s2.add(_req(0, 10, priority=5))
    so2 = s2.schedule(running, [], token_budget=64)
    assert so2.preempt_slots == () if all(
        r.priority >= 5 for _s, r, _d in running) else True


# ---------------------------------------------------------------------------
# Preemption-and-recompute equivalence (the tentpole acceptance bar)
# ---------------------------------------------------------------------------

def _drain_tokens(eng):
    eng.run_until_drained()
    return {o.rid: o.tokens for o in eng.outputs()}


def _preempt_run(cfg, params, sampling, *, packed=False, paged=False):
    """Fill both slots, let them decode a few tokens, then submit a
    higher-priority request so one slot is preempted and recomputed."""
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=8,
                    admission="preempt", packed=packed, paged=paged,
                    page_size=8 if paged else 16)
    for rid in range(2):
        eng.submit(_req(rid, 10, max_new=6, vocab=cfg.vocab,
                        sampling=sampling))
    for _ in range(4):                              # both slots mid-decode
        eng.step()
    eng.submit(_req(9, 10, max_new=4, vocab=cfg.vocab, priority=5,
                    sampling=sampling))
    eng.run_until_drained()
    return eng


def test_preemption_recompute_is_token_identical_greedy(tiny):
    cfg, params = tiny
    base = LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=8)
    for rid in range(2):
        base.submit(_req(rid, 10, max_new=6, vocab=cfg.vocab))
    toks0 = _drain_tokens(base)

    eng = _preempt_run(cfg, params, SamplingParams())
    assert eng.stats.preemptions >= 1
    outs = _outs(eng)
    assert all(outs[r].finish_reason in (FINISH_EOS, FINISH_LENGTH)
               for r in outs)
    for rid in range(2):                            # identical streams
        assert outs[rid].tokens == toks0[rid]
    preempted = [o for o in outs.values() if o.preemptions > 0]
    assert preempted and all(o.rid in (0, 1) for o in preempted)
    # original prompt length is reported, not the rewritten one
    assert all(outs[r].prompt_len == 10 for r in (0, 1))


def test_preemption_recompute_is_token_identical_sampled(tiny):
    cfg, params = tiny
    sp = SamplingParams(temperature=0.8, top_k=20, seed=42)
    base = LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=8)
    for rid in range(2):
        base.submit(_req(rid, 10, max_new=6, vocab=cfg.vocab, sampling=sp))
    toks0 = _drain_tokens(base)

    eng = _preempt_run(cfg, params, sp)
    assert eng.stats.preemptions >= 1
    outs = _outs(eng)
    for rid in range(2):
        assert outs[rid].tokens == toks0[rid]       # resume_key did its job


def test_preemption_equivalence_packed_mode(tiny):
    cfg, params = tiny
    base = LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=8,
                     packed=True)
    for rid in range(2):
        base.submit(_req(rid, 10, max_new=6, vocab=cfg.vocab))
    toks0 = _drain_tokens(base)
    eng = _preempt_run(cfg, params, SamplingParams(), packed=True)
    assert eng.stats.preemptions >= 1
    outs = _outs(eng)
    for rid in range(2):
        assert outs[rid].tokens == toks0[rid]


@pytest.mark.parametrize("packed", [False, True])
def test_preemption_equivalence_paged_mode(tiny, packed):
    """Preemption releases the victim's pages immediately and the resumed
    stream is token-identical — window AND packed paged paths, sampled
    (the resume_key must land in a freshly regranted page layout)."""
    cfg, params = tiny
    sp = SamplingParams(temperature=0.8, top_k=20, seed=42)
    base = LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=8,
                     packed=packed, paged=True, page_size=8)
    for rid in range(2):
        base.submit(_req(rid, 10, max_new=6, vocab=cfg.vocab, sampling=sp))
    toks0 = _drain_tokens(base)
    eng = _preempt_run(cfg, params, sp, packed=packed, paged=True)
    assert eng.stats.preemptions >= 1
    outs = _outs(eng)
    for rid in range(2):
        assert outs[rid].tokens == toks0[rid]
    assert eng.core.pager.used_pages == 0           # everything released


# ---------------------------------------------------------------------------
# NaN quarantine + watchdog recovery (the chaos acceptance bar)
# ---------------------------------------------------------------------------

def _chaos_run(cfg, params, faults=None, **kw):
    eng = LLMEngine(params, cfg, batch_slots=4, buffer_len=64, chunk_size=8,
                    faults=faults, **kw)
    for rid in range(4):
        eng.submit(_req(rid, 10, max_new=6, vocab=cfg.vocab))
    eng.run_until_drained()
    return eng


def test_nan_quarantine_isolates_exactly_the_poisoned_request(tiny):
    cfg, params = tiny
    toks0 = {o.rid: o.tokens for o in _chaos_run(cfg, params).outputs()}
    eng = _chaos_run(cfg, params,
                     faults=FaultPlan.parse(["nan:step=3,slot=0"]))
    outs = _outs(eng)
    errored = [r for r in outs if outs[r].finish_reason == FINISH_ERROR]
    assert len(errored) == 1                        # exactly the poisoned one
    assert eng.stats.errors == 1
    healthy = [r for r in outs if r not in errored]
    assert all(outs[r].finish_reason in (FINISH_EOS, FINISH_LENGTH)
               for r in healthy)
    assert all(outs[r].tokens == toks0[r] for r in healthy)
    # the quarantined stream emitted no token sampled from poisoned logits
    assert len(outs[errored[0]].tokens) < len(toks0[errored[0]])


def test_injected_step_failure_recovers_with_identical_streams(tiny):
    cfg, params = tiny
    toks0 = {o.rid: o.tokens for o in _chaos_run(cfg, params).outputs()}
    eng = _chaos_run(cfg, params, faults=FaultPlan.parse(["fail:step=5"]))
    assert eng.stats.recoveries >= 1
    outs = _outs(eng)
    assert len(outs) == 4 and eng.stats.completed == 4   # nobody lost
    for rid in outs:                                # post-recovery == clean
        assert outs[rid].tokens == toks0[rid]


def test_failure_in_admitting_step_keeps_sampled_streams(tiny):
    # A step that fails while binding new requests has not seeded their
    # keys: recovery must resume them from their own seed.
    cfg, params = tiny
    sp = SamplingParams(temperature=0.8, top_k=20, seed=7)

    def run(faults=None):
        eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=64,
                        chunk_size=8, faults=faults)
        for rid in range(2):
            eng.submit(_req(rid, 10, max_new=6, vocab=cfg.vocab, sampling=sp))
        return eng, _drain_tokens(eng)

    _, toks0 = run()
    eng, toks = run(FaultPlan.parse(["fail:step=0"]))
    assert eng.stats.recoveries == 1
    assert toks == toks0


def test_recurring_step_failure_surfaces_after_recovery_bound(tiny):
    # A failure on every step (a kernel the compiler refuses, a device OOM)
    # must surface, not rebuild the core and replay forever.
    from repro.serving.engine import MAX_RECOVERIES_WITHOUT_TOKEN
    cfg, params = tiny
    eng = LLMEngine(params, cfg, batch_slots=4, buffer_len=64, chunk_size=8,
                    faults=FaultPlan.parse(["fail:step=0,every=1"]))
    eng.submit(_req(0, 10, max_new=6, vocab=cfg.vocab))
    with pytest.raises(InjectedFault):
        eng.run_until_drained()
    assert eng.stats.recoveries == MAX_RECOVERIES_WITHOUT_TOKEN


def test_single_failure_after_stalls_still_recovers(tiny):
    # Stall rebuilds follow steps that succeeded: they must not use up the
    # bound that surfaces recurring step failures. Prompts of five chunks
    # keep steps 1-3 in prefill, so no token is committed before step 4.
    from repro.serving.engine import MAX_RECOVERIES_WITHOUT_TOKEN

    def run(faults=None, **kw):
        eng = LLMEngine(params, cfg, batch_slots=4, buffer_len=64,
                        chunk_size=8, faults=faults, **kw)
        for rid in range(4):
            eng.submit(_req(rid, 40, max_new=6, vocab=cfg.vocab))
        eng.run_until_drained()
        return eng

    cfg, params = tiny
    toks0 = {o.rid: o.tokens for o in run().outputs()}
    eng = run(FaultPlan.parse(["delay:step=1,s=0.3", "delay:step=2,s=0.3",
                               "delay:step=3,s=0.3", "fail:step=4"]),
              step_timeout_s=0.2)
    assert eng.stats.stalls >= MAX_RECOVERIES_WITHOUT_TOKEN
    assert eng.stats.recoveries > MAX_RECOVERIES_WITHOUT_TOKEN
    outs = _outs(eng)
    assert len(outs) == 4 and eng.stats.completed == 4
    for rid in outs:
        assert outs[rid].tokens == toks0[rid]


def test_combined_nan_and_failure_chaos(tiny):
    # The full acceptance scenario: NaN at step 3 AND a crash at step 7.
    cfg, params = tiny
    toks0 = {o.rid: o.tokens for o in _chaos_run(cfg, params).outputs()}
    eng = _chaos_run(cfg, params, faults=FaultPlan.parse(
        ["nan:step=3,slot=0", "fail:step=5"]))
    outs = _outs(eng)
    assert eng.stats.recoveries >= 1
    errored = [r for r in outs if outs[r].finish_reason == FINISH_ERROR]
    assert len(errored) == 1
    healthy = [r for r in outs if r not in errored]
    assert all(outs[r].finish_reason in (FINISH_EOS, FINISH_LENGTH)
               for r in healthy)
    assert all(outs[r].tokens == toks0[r] for r in healthy)


def test_paged_chaos_recovery_rebuilds_page_tables(tiny):
    """A step crash in paged mode rebuilds the core (fresh empty pool);
    recompute replay regrants pages and the streams match the fault-free
    paged run — page tables are reconstructable state, never truth."""
    cfg, params = tiny
    toks0 = {o.rid: o.tokens
             for o in _chaos_run(cfg, params, paged=True,
                                 page_size=8).outputs()}
    eng = _chaos_run(cfg, params, faults=FaultPlan.parse(
        ["nan:step=3,slot=0", "fail:step=5"]), paged=True, page_size=8)
    assert eng.stats.recoveries >= 1
    outs = _outs(eng)
    errored = [r for r in outs if outs[r].finish_reason == FINISH_ERROR]
    assert len(errored) == 1
    healthy = [r for r in outs if r not in errored]
    assert all(outs[r].tokens == toks0[r] for r in healthy)
    assert eng.core.pager.used_pages == 0
    assert eng.stats.kv_pages_total == eng.core.pager.P


def test_stall_watchdog_counts_and_recovers(tiny):
    cfg, params = tiny
    eng = _chaos_run(cfg, params,
                     faults=FaultPlan.parse(["delay:step=4,s=0.05"]),
                     step_timeout_s=0.04)
    # compile steps also exceed 40ms — what matters is that the injected
    # stall was seen, every request still finished, and the engine recovered
    assert eng.stats.stalls >= 1 and eng.stats.recoveries >= 1
    assert eng.stats.completed == 4


def test_deadline_expires_running_request(tiny):
    cfg, params = tiny
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=8)
    notified = []
    req = _req(0, 10, max_new=6, vocab=cfg.vocab, deadline_s=1e-6,
               on_finish=lambda o: notified.append(o))
    eng.submit(req)
    eng.run_until_drained()
    out = _outs(eng)[0]
    assert out.finish_reason == FINISH_TIMEOUT
    assert eng.stats.timeouts == 1
    assert len(notified) == 1                       # exactly-once callback
    assert notified[0].finish_reason == FINISH_TIMEOUT


def test_engine_load_shedding_and_backpressure(tiny):
    cfg, params = tiny
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=8,
                    max_waiting=2)
    results = [eng.add_request(_req(rid, 10, max_new=2, vocab=cfg.vocab))
               for rid in range(4)]
    admitted = [ok for ok, _bp in results]
    assert admitted == [True, True, False, False]   # bounded queue sheds
    assert results[1][1] == 1.0                     # backpressure saturated
    assert eng.stats.shed == 2
    shed_outs = [o for o in eng.outputs() if o.finish_reason == FINISH_SHED]
    assert len(shed_outs) == 2
    eng.run_until_drained()
    assert eng.stats.completed == 2                 # the admitted pair


# ---------------------------------------------------------------------------
# FaultPlan shared with the training supervisor
# ---------------------------------------------------------------------------

def test_supervisor_accepts_fault_plan(tmp_path):
    import jax.numpy as jnp
    from repro.runtime import supervisor

    @jax.jit
    def step(state, batch):
        w = state["w"] - 0.1 * (state["w"] - batch["x"])
        return {"w": w}, {"total_loss": jnp.sum((w - batch["x"]) ** 2)}

    def batch_at(s):
        return {"x": jnp.full((4,), float(s % 3))}

    cfg = supervisor.SupervisorConfig(ckpt_dir=str(tmp_path), save_every=4,
                                      log_every=100)
    plan = FaultPlan.parse(["fail:step=9"])
    state, rep = supervisor.run(step, {"w": jnp.zeros((4,))}, batch_at, 15,
                                cfg, faults=plan, log=lambda *_: None)
    # the injector fires once per (fault, step): the node dies at step 9,
    # the supervisor restores the step-8 checkpoint, and the REPLAY of
    # step 9 succeeds (a pure step-keyed raise would livelock the loop)
    assert rep.failures == 1 and rep.restores >= 1
    assert rep.steps_run >= 15 - 8                  # run completed


def test_supervisor_fault_plan_delay_feeds_straggler_watchdog(tmp_path):
    import jax.numpy as jnp
    from repro.runtime import supervisor

    @jax.jit
    def step(state, batch):
        return {"w": state["w"] + batch["x"]}, {"total_loss": jnp.sum(
            state["w"])}

    def batch_at(s):
        return {"x": jnp.ones((2,))}

    cfg = supervisor.SupervisorConfig(ckpt_dir=str(tmp_path), save_every=50,
                                      straggler_factor=3.0, log_every=100)
    plan = FaultPlan.parse(["delay:step=10,s=0.25"])
    _state, rep = supervisor.run(step, {"w": jnp.zeros((2,))}, batch_at, 14,
                                 cfg, faults=plan, log=lambda *_: None)
    assert rep.stragglers >= 1                      # the delay tripped it


# ---------------------------------------------------------------------------
# Satellite regressions: mapper + checkpoint error messages
# ---------------------------------------------------------------------------

def test_mapper_no_viable_path_raises_named_error():
    from repro.runtime import mapper
    with pytest.raises(RuntimeError, match="mlp_up"):
        mapper.classify_gemm(8, 64, 64, 0.25, name="mlp_up", paths=())


def test_ckpt_shape_mismatch_raises_named_value_error(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint import ckpt
    ckpt.save({"w": jnp.zeros((4, 4))}, str(tmp_path), 1)
    template = {"w": jax.ShapeDtypeStruct((2, 8), jnp.float32)}
    with pytest.raises(ValueError) as ei:
        ckpt.restore(str(tmp_path), template=template)
    msg = str(ei.value)
    assert "w" in msg and "(4, 4)" in msg and "(2, 8)" in msg
