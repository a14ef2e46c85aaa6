"""Step-based serving: chunked prefill + decode interleaving.

Covers the unified ``schedule() -> SchedulerOutput -> EngineCore.step()``
contract: scheduler chunk/budget math (pure, no model), chunk-boundary edge
cases (prompt shorter than a chunk, exact-multiple prompts, EOS mid-run,
determinism vs the unchunked path under the same seed), and the 2-shape
compile bound of the fused window step.
"""
import numpy as np
import jax
import pytest

from repro.configs import get_smoke_config
from repro.models import registry as R
from repro.serving import (ChunkTask, FCFSScheduler, FINISH_EOS,
                           FINISH_LENGTH, LLMEngine, Request, SamplingParams,
                           SchedulerOutput)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("tinyllama_1_1b")
    params = R.model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _req(rid, plen, max_new=4, vocab=512, **kw):
    rng = np.random.default_rng(rid)
    return Request(rid, rng.integers(0, vocab, plen, dtype=np.int32),
                   max_new_tokens=max_new, **kw)


def _run(params, cfg, reqs, **kw):
    eng = LLMEngine(params, cfg, batch_slots=kw.pop("batch_slots", 2),
                    buffer_len=kw.pop("buffer_len", 64), **kw)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng


# ---------------------------------------------------------------------------
# Scheduler: chunk splitting + token budget (pure, no model)
# ---------------------------------------------------------------------------

def test_schedule_splits_prompt_into_chunks():
    s = FCFSScheduler(128, chunk_size=8)
    req = _req(0, 20)
    assert s.add(req)
    so = s.schedule([], [0, 1])
    assert isinstance(so, SchedulerOutput) and len(so.chunks) == 1
    c = so.chunks[0]
    assert (c.slot, c.start, c.length, c.last) == (0, 0, 8, False)
    # continuing chunks come from the running view, FCFS
    so2 = s.schedule([(0, req, 8)], [1])
    c2 = so2.chunks[0]
    assert (c2.start, c2.length, c2.last) == (8, 8, False)
    so3 = s.schedule([(0, req, 16)], [1])
    c3 = so3.chunks[0]
    assert (c3.start, c3.length, c3.last) == (16, 4, True)   # partial tail


def test_schedule_decodes_never_preempted_by_budget():
    dec_req = _req(0, 4)
    s = FCFSScheduler(128, chunk_size=8)
    assert s.add(_req(1, 30))
    # budget 5: the decode slot always advances; the chunk gets the rest
    so = s.schedule([(0, dec_req, 4)], [1], token_budget=5)
    assert so.decode_slots == (0,)
    assert len(so.chunks) == 1 and so.chunks[0].length == 4
    assert so.n_scheduled_tokens == 5
    # budget 1: decode only, the waiting prompt stays queued
    s2 = FCFSScheduler(128, chunk_size=8)
    assert s2.add(_req(1, 30))
    so2 = s2.schedule([(0, dec_req, 4)], [1], token_budget=1)
    assert so2.decode_slots == (0,) and not so2.chunks
    assert len(s2) == 1


def test_schedule_partial_prefills_before_new_admissions():
    s = FCFSScheduler(128, chunk_size=8)
    old = _req(0, 24)
    new = _req(1, 24)
    assert s.add(new)
    so = s.schedule([(0, old, 8)], [1], token_budget=10)
    # continuing request gets a full chunk; the new one gets the remainder
    assert [c.slot for c in so.chunks] == [0, 1]
    assert [c.length for c in so.chunks] == [8, 2]


def test_schedule_legacy_mode_emits_bucketed_groups():
    s = FCFSScheduler(64)              # chunk_size=None -> legacy
    for rid, plen in enumerate([10, 12, 40]):
        assert s.add(_req(rid, plen, max_new=2))
    so = s.schedule([(3, _req(9, 4), 4)], [0, 1, 2])
    assert so.decode_slots == (3,)
    assert len(so.prefill_groups) == 2          # bucket 16 pair + bucket 64
    g0 = so.prefill_groups[0]
    assert g0.bucket == 16 and [s for s, _ in g0.slot_reqs] == [0, 1]
    assert so.prefill_groups[1].bucket == 64


# ---------------------------------------------------------------------------
# Chunk-boundary edge cases through the engine
# ---------------------------------------------------------------------------

def _greedy_tokens(params, cfg, reqs_fn, **kw):
    eng = _run(params, cfg, reqs_fn(), **kw)
    return {o.rid: o.tokens for o in eng.outputs()}, eng


def test_prompt_shorter_than_one_chunk_matches_unchunked(tiny):
    cfg, params = tiny
    mk = lambda: [_req(0, 5, max_new=4, vocab=cfg.vocab)]
    ref, _ = _greedy_tokens(params, cfg, mk)
    got, eng = _greedy_tokens(params, cfg, mk, chunk_size=16)
    assert got == ref
    assert eng.stats.chunk_tokens == 5
    assert eng.stats.prefill_compiles == 0      # no phase-based prefill ran


def test_prompt_exact_multiple_of_chunk_matches_unchunked(tiny):
    cfg, params = tiny
    mk = lambda: [_req(0, 24, max_new=4, vocab=cfg.vocab)]
    ref, _ = _greedy_tokens(params, cfg, mk)
    got, eng = _greedy_tokens(params, cfg, mk, chunk_size=8)
    assert got == ref
    assert eng.stats.chunk_tokens == 24         # 3 full chunks, no stragglers


def test_mixed_lengths_deterministic_vs_unchunked_greedy(tiny):
    cfg, params = tiny
    mk = lambda: [_req(rid, L, max_new=4, vocab=cfg.vocab)
                  for rid, L in enumerate([3, 8, 17, 30, 9, 26])]
    ref, _ = _greedy_tokens(params, cfg, mk)
    got, _ = _greedy_tokens(params, cfg, mk, chunk_size=8)
    assert got == ref


def test_sampled_stream_deterministic_vs_unchunked(tiny):
    # A mid-prompt chunk must consume no randomness: the sampled stream under
    # a fixed per-request seed is identical with and without chunking.
    cfg, params = tiny
    mk = lambda: [_req(rid, L, max_new=5, vocab=cfg.vocab,
                       sampling=SamplingParams(temperature=0.9, top_k=16,
                                               seed=rid + 3))
                  for rid, L in enumerate([4, 19, 27])]
    ref, _ = _greedy_tokens(params, cfg, mk)
    got, _ = _greedy_tokens(params, cfg, mk, chunk_size=8)
    assert got == ref


def test_eos_finish_mid_run_frees_slot_for_chunked_prefill(tiny):
    cfg, params = tiny
    # learn the greedy first token of a probe prompt, then use it as eos
    probe, _ = _greedy_tokens(params, cfg,
                              lambda: [_req(0, 5, max_new=1, vocab=cfg.vocab)])
    eos = probe[0][0]
    eng = LLMEngine(params, cfg, batch_slots=1, buffer_len=64,
                    chunk_size=8, eos_id=eos)
    eng.submit(_req(0, 5, max_new=8, vocab=cfg.vocab))   # finishes at eos
    eng.submit(_req(1, 20, max_new=3, vocab=cfg.vocab))  # chunked after free
    eng.run_until_drained()
    outs = {o.rid: o for o in eng.outputs()}
    assert outs[0].finish_reason == FINISH_EOS
    assert outs[1].finish_reason in (FINISH_LENGTH, FINISH_EOS)
    assert outs[1].n_tokens >= 1
    assert eng.stats.completed == 2


def test_decode_interleaves_with_chunked_prefill(tiny):
    # While one slot decodes, a long prompt is consumed in chunks — both
    # inside the same fused window steps (mixed_s accrues, prefill_s doesn't).
    cfg, params = tiny
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=4)
    eng.submit(_req(0, 3, max_new=12, vocab=cfg.vocab))
    eng.submit(_req(1, 24, max_new=2, vocab=cfg.vocab))
    eng.run_until_drained()
    assert eng.stats.completed == 2
    assert eng.stats.mixed_s > 0.0
    assert eng.stats.prefill_s == 0.0
    # outputs identical to the phase-based path
    ref, _ = _greedy_tokens(
        params, cfg,
        lambda: [_req(0, 3, max_new=12, vocab=cfg.vocab),
                 _req(1, 24, max_new=2, vocab=cfg.vocab)])
    assert {o.rid: o.tokens for o in eng.outputs()} == ref


def test_chunked_step_compiles_bounded_regardless_of_length_mix(tiny):
    cfg, params = tiny
    lens = [3, 5, 9, 13, 17, 25, 33, 47]        # 8 distinct lengths
    eng = _run(params, cfg,
               [_req(rid, L, max_new=2, vocab=cfg.vocab)
                for rid, L in enumerate(lens)],
               batch_slots=4, chunk_size=16)
    assert eng.stats.completed == len(lens)
    # ONE window shape + ONE pure-decode shape, vs one prefill trace per
    # bucket (or per distinct length) in the phase-based modes
    assert eng.stats.step_compiles <= 2
    assert eng.stats.prefill_compiles == 0


def test_tight_token_budget_never_corrupts_partial_prefill(tiny):
    # Regression: with an exhausted token budget the scheduler used to emit
    # decode-only steps while a slot sat mid-prefill — and the fused decode
    # advances ALL B slot caches, so the partial prefill's pos drifted past
    # its consumed tokens. A mid-prefill slot now always gets >= 1 chunk
    # token (budget is a soft target), keeping outputs exact.
    cfg, params = tiny
    mk = lambda: [_req(0, 4, max_new=10, vocab=cfg.vocab),
                  _req(1, 26, max_new=3, vocab=cfg.vocab)]
    ref, _ = _greedy_tokens(params, cfg, mk)
    got, eng = _greedy_tokens(params, cfg, mk, chunk_size=8,
                              max_step_tokens=2)
    assert got == ref
    assert eng.stats.completed == 2


def test_schedule_tight_budget_floors_partial_prefill_progress():
    s = FCFSScheduler(128, chunk_size=8)
    dec = _req(0, 4)
    partial = _req(1, 30)
    so = s.schedule([(0, dec, 4), (1, partial, 8)], [], token_budget=1)
    assert so.decode_slots == (0,)
    assert len(so.chunks) == 1
    assert (so.chunks[0].slot, so.chunks[0].length) == (1, 1)


def test_while_step_driver_drains_queued_requests(tiny):
    # Regression: step() must report queued work, not just occupied slots —
    # when every occupied slot finishes in the same iteration, an external
    # `while eng.step()` driver (the seed-era pattern) must still serve the
    # waiting queue. Both modes.
    cfg, params = tiny
    for kw in ({}, {"chunk_size": 8}):
        eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=32, **kw)
        for rid in range(3):                 # same length + same max_new:
            eng.submit(_req(rid, 5, max_new=4, vocab=cfg.vocab))
        while eng.step():
            pass
        assert eng.stats.completed == 3
        assert len(eng.scheduler) == 0


def test_near_capacity_request_is_exact_under_chunking(tiny):
    # The window over-allocation means admission math is unchanged and a
    # prompt_len + max_new == buffer_len request still decodes correctly
    # (the W-wide ragged write near the buffer edge must not clamp onto
    # valid history).
    cfg, params = tiny
    mk = lambda: [_req(0, 24, max_new=8, vocab=cfg.vocab)]   # 24 + 8 == 32
    ref, _ = _greedy_tokens(params, cfg, mk, buffer_len=32)
    got, eng = _greedy_tokens(params, cfg, mk, buffer_len=32, chunk_size=16)
    assert got == ref
    assert eng.outputs()[0].finish_reason == FINISH_LENGTH


def test_recurrent_family_falls_back_to_phase_based():
    cfg = get_smoke_config("falcon_mamba_7b")
    params = R.model_init(jax.random.PRNGKey(0), cfg)
    with pytest.warns(UserWarning, match="chunked prefill requires"):
        eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=32,
                        chunk_size=8)
    assert eng.chunk is None
    eng.submit(_req(0, 6, max_new=3, vocab=cfg.vocab))
    stats = eng.run_until_drained()
    assert stats.completed == 1 and stats.tokens_out == 3


# ---------------------------------------------------------------------------
# Latency accounting (TTFT / ITL percentile raw material)
# ---------------------------------------------------------------------------

def test_request_outputs_carry_ttft_and_itl_samples(tiny):
    cfg, params = tiny
    eng = _run(params, cfg, [_req(0, 9, max_new=4, vocab=cfg.vocab)],
               chunk_size=4)
    out = eng.outputs()[0]
    assert out.ttft_s is not None and out.ttft_s > 0.0
    assert len(out.itls_s) == out.n_tokens - 1
    assert all(d >= 0.0 for d in out.itls_s)
