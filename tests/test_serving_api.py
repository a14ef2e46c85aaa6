"""Request-level serving API: scheduler, sampling, compile counts, HW targets.

Covers the Scheduler/EngineCore split: bucket assignment and FCFS fairness
(pure scheduler, no model), admission rejection/truncation, the bucketed
batched prefill's compile bound (<= n_buckets traces for mixed-length
workloads), per-request sampling determinism under fixed seeds, and the
first-class HW target registry threaded through the mapper.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.hwmodel import perf_model as pm
from repro.models import registry as R
from repro.runtime import mapper
from repro.serving import (FCFSScheduler, FINISH_EOS, FINISH_LENGTH,
                           FINISH_REJECTED, LLMEngine, Request,
                           SamplingParams, bucket_for, bucket_lengths,
                           hw_by_name, hw_names)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("tinyllama_1_1b")
    params = R.model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _req(rid, plen, max_new=4, vocab=512, **kw):
    rng = np.random.default_rng(rid)
    return Request(rid, rng.integers(0, vocab, plen, dtype=np.int32),
                   max_new_tokens=max_new, **kw)


# ---------------------------------------------------------------------------
# Scheduler: buckets, FCFS fairness, admission
# ---------------------------------------------------------------------------

def test_bucket_lengths_pow2_capped_at_buffer():
    assert bucket_lengths(128) == (8, 16, 32, 64, 128)
    assert bucket_lengths(96) == (8, 16, 32, 64, 96)   # last clamps to buffer
    assert bucket_lengths(8) == (8,)


def test_bucket_for_smallest_fit():
    buckets = bucket_lengths(128)
    assert bucket_for(3, buckets) == 8
    assert bucket_for(8, buckets) == 8
    assert bucket_for(9, buckets) == 16
    assert bucket_for(100, buckets) == 128
    with pytest.raises(ValueError):
        bucket_for(200, buckets)


def test_fcfs_same_bucket_requests_keep_submission_order():
    s = FCFSScheduler(128)
    for rid, plen in enumerate([10, 12, 11, 13]):     # all bucket 16
        assert s.add(_req(rid, plen))
    g = s.next_group(3)
    assert g.bucket == 16
    assert [r.rid for r in g.requests] == [0, 1, 2]   # order kept, size capped
    assert [r.rid for r in s.next_group(3).requests] == [3]


def test_fcfs_head_of_line_always_in_next_group():
    # Younger same-bucket requests may ride along, but the oldest waiting
    # request is always served first — bucketing never starves it.
    s = FCFSScheduler(128)
    s.add(_req(0, 10))     # bucket 16
    s.add(_req(1, 100))    # bucket 128
    s.add(_req(2, 12))     # bucket 16 — rides with rid 0
    g1 = s.next_group(4)
    assert [r.rid for r in g1.requests] == [0, 2] and g1.bucket == 16
    g2 = s.next_group(4)
    assert [r.rid for r in g2.requests] == [1] and g2.bucket == 128
    assert len(s) == 0


def test_admission_rejects_cache_overflow():
    # Regression: prompt_len + max_new_tokens > buffer_len used to decode
    # past T and silently wrap/clobber the stacked cache.
    s = FCFSScheduler(32)
    ok = _req(0, 10, max_new=22)                      # 10 + 22 == 32: fits
    bad = _req(1, 10, max_new=23)                     # 33 > 32: overflow
    long = _req(2, 40, max_new=1)                     # prompt alone too long
    assert s.add(ok)
    assert not s.add(bad)
    assert bad.finish_reason == FINISH_REJECTED
    assert not s.add(long)
    assert long.finish_reason == FINISH_REJECTED
    assert len(s) == 1


def test_admission_truncate_clamps_max_new():
    s = FCFSScheduler(32, admission="truncate")
    r = _req(0, 10, max_new=100)
    assert s.add(r)
    assert r.max_new_tokens == 22
    long = _req(1, 40)                                # prompts never truncate
    assert not s.add(long)
    assert long.finish_reason == FINISH_REJECTED


def test_engine_rejected_request_surfaces_as_output(tiny):
    cfg, params = tiny
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=32)
    assert not eng.submit(_req(7, 30, max_new=10, vocab=cfg.vocab))
    assert eng.stats.rejected == 1
    out = eng.outputs()[0]
    assert out.rid == 7 and out.finish_reason == FINISH_REJECTED
    assert out.n_tokens == 0


def test_admission_truncate_exact_fit_is_untouched():
    # Edge: plen + max_new == buffer_len fills the cache exactly — truncate
    # must admit it without clamping (clamping would silently shorten a
    # request that was never oversubscribed).
    s = FCFSScheduler(32, admission="truncate")
    r = _req(0, 10, max_new=22)                       # 10 + 22 == 32 exactly
    assert s.add(r)
    assert r.max_new_tokens == 22                     # untouched
    over = _req(1, 10, max_new=23)                    # one past the edge
    assert s.add(over)
    assert over.max_new_tokens == 22                  # clamped to the fit


def test_rejected_request_fires_on_finish_exactly_once(tiny):
    cfg, params = tiny
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=32)
    calls = []
    req = _req(3, 30, max_new=10, vocab=cfg.vocab,
               on_finish=lambda out: calls.append(out))
    assert not eng.submit(req)
    assert len(calls) == 1                            # exactly once
    assert calls[0].finish_reason == FINISH_REJECTED
    assert calls[0].rid == 3 and calls[0].n_tokens == 0
    # draining the engine must not re-notify the dead request
    eng.run_until_drained()
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# Bucketed batched prefill: compile bound + exactness already covered in
# test_data_serving; here the trace-count contract.
# ---------------------------------------------------------------------------

def test_bucketed_prefill_traces_at_most_n_buckets(tiny):
    cfg, params = tiny
    eng = LLMEngine(params, cfg, batch_slots=4, buffer_len=64)
    lens = [3, 5, 9, 13, 17, 25, 33, 47]              # 8 distinct lengths
    for rid, L in enumerate(lens):
        assert eng.submit(_req(rid, L, max_new=2, vocab=cfg.vocab))
    eng.run_until_drained()
    assert eng.stats.completed == len(lens)
    n_buckets = len(bucket_lengths(64))               # (8, 16, 32, 64)
    assert eng.stats.prefill_compiles <= n_buckets
    assert eng.stats.prefill_compiles < len(set(lens))
    # 4 buckets actually hit: {8, 16, 32, 64}
    assert eng.stats.prefill_compiles == 4
    # and per-phase wall time is attributed
    assert eng.stats.prefill_s > 0 and eng.stats.decode_s > 0


def test_unbucketed_prefill_traces_per_distinct_length(tiny):
    cfg, params = tiny
    eng = LLMEngine(params, cfg, batch_slots=4, buffer_len=64,
                    bucketed_prefill=False)
    lens = [3, 5, 9, 13]
    for rid, L in enumerate(lens):
        eng.submit(_req(rid, L, max_new=2, vocab=cfg.vocab))
    eng.run_until_drained()
    assert eng.stats.prefill_compiles == len(set(lens))


# ---------------------------------------------------------------------------
# Per-request sampling
# ---------------------------------------------------------------------------

def test_sampling_deterministic_under_fixed_seed(tiny):
    cfg, params = tiny

    def gen(seed):
        eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=32)
        eng.submit(_req(0, 5, max_new=6, vocab=cfg.vocab,
                        sampling=SamplingParams(temperature=1.0, top_k=8,
                                                seed=seed)))
        eng.run_until_drained()
        return eng.outputs()[0].tokens

    assert gen(7) == gen(7)
    assert gen(7) != gen(8)        # astronomically unlikely to collide


def test_sampling_independent_of_batch_composition(tiny):
    # A request's sampled stream depends only on (params, prompt, seed) —
    # not on which other requests share the batch or which slot it lands in.
    cfg, params = tiny
    sp = SamplingParams(temperature=0.9, top_k=16, seed=3)

    eng1 = LLMEngine(params, cfg, batch_slots=4, buffer_len=32)
    eng1.submit(_req(0, 5, max_new=5, vocab=cfg.vocab, sampling=sp))
    eng1.run_until_drained()
    alone = eng1.outputs()[0].tokens

    eng2 = LLMEngine(params, cfg, batch_slots=4, buffer_len=32)
    for rid in (10, 11):           # same-bucket companions admitted first
        eng2.submit(_req(rid, 6, max_new=5, vocab=cfg.vocab,
                         sampling=SamplingParams(temperature=1.5, seed=99)))
    eng2.submit(_req(0, 5, max_new=5, vocab=cfg.vocab, sampling=sp))
    eng2.run_until_drained()
    crowded = next(o for o in eng2.outputs() if o.rid == 0).tokens
    assert alone == crowded


def test_greedy_top_k_zero_matches_argmax_semantics():
    from repro.serving.core import _sample_token
    logits = jnp.asarray(np.random.default_rng(0).normal(size=64), jnp.float32)
    key = jax.random.PRNGKey(0)
    tok, _ = _sample_token(logits, jnp.float32(0.0), jnp.int32(0),
                           jnp.asarray(True), key)
    assert int(tok) == int(jnp.argmax(logits))
    # top-k=1 sampling collapses to argmax regardless of temperature
    tok1, _ = _sample_token(logits, jnp.float32(5.0), jnp.int32(1),
                            jnp.asarray(False), key)
    assert int(tok1) == int(jnp.argmax(logits))


def test_streaming_and_finish_reasons(tiny):
    cfg, params = tiny
    got = []
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=32)
    eng.submit(_req(0, 5, max_new=4, vocab=cfg.vocab,
                    stream=lambda rid, tok: got.append((rid, tok))))
    eng.run_until_drained()
    out = eng.outputs()[0]
    assert out.finish_reason == FINISH_LENGTH
    assert [t for _, t in got] == list(out.tokens)    # streamed in order

    # eos finish: run greedy once to learn the first token, then use it as eos
    eos = out.tokens[0]
    eng2 = LLMEngine(params, cfg, batch_slots=2, buffer_len=32, eos_id=eos)
    eng2.submit(_req(0, 5, max_new=8, vocab=cfg.vocab))
    eng2.run_until_drained()
    out2 = eng2.outputs()[0]
    assert out2.finish_reason == FINISH_EOS
    assert out2.tokens[-1] == eos


def test_recurrent_family_falls_back_to_exact_prefill():
    cfg = get_smoke_config("falcon_mamba_7b")
    params = R.model_init(jax.random.PRNGKey(0), cfg)
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=32)
    assert not eng.bucketed                     # SSM state vetoes padding
    for rid, L in enumerate([4, 6, 9]):
        eng.submit(_req(rid, L, max_new=3, vocab=cfg.vocab))
    stats = eng.run_until_drained()
    assert stats.completed == 3
    assert stats.tokens_out == 9


# ---------------------------------------------------------------------------
# HW targets
# ---------------------------------------------------------------------------

def test_hw_registry_presets():
    assert {"v5e", "v5p", "v6e", "cpu"} <= set(hw_names())
    assert hw_by_name("v5p").peak_flops > hw_by_name("v5e").peak_flops
    assert hw_by_name("v6e").hbm_bw > hw_by_name("v5e").hbm_bw
    assert hw_by_name("cpu").hbm_bw < hw_by_name("v5e").hbm_bw
    with pytest.raises(KeyError):
        hw_by_name("h100")
    assert pm.resolve_hw("v5e") is pm.V5E
    assert pm.resolve_hw(pm.V5P) is pm.V5P


def test_mapper_path_decision_differs_cpu_vs_v5e():
    # Same GEMM, different machine balance, different regime: v5e's HBM wall
    # favours the fused generator; on the flat CPU hierarchy regeneration is
    # the bottleneck and materialize wins.
    pc = mapper.classify_gemm(128, 2048, 2048, 0.5, seg=16, hw="cpu")
    pv = mapper.classify_gemm(128, 2048, 2048, 0.5, seg=16, hw="v5e")
    assert pc.path == "materialize" and pv.path == "fused"


def test_plan_model_accepts_registered_targets(tiny):
    cfg, _ = tiny
    shape = ShapeConfig("d", 1, 4, "decode")
    plans = {name: mapper.plan_model(cfg, shape, hw=name)
             for name in ("cpu", "v5e", "v5p")}
    for name, ep in plans.items():
        assert ep.hw_label == name
        assert ep.entries
    assert any(a != b for (_, a), (_, b)
               in zip(plans["cpu"].entries, plans["v5e"].entries))


def test_engine_threads_hw_into_plan(tiny):
    cfg, params = tiny
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=32, hw="v5p")
    assert eng.cfg.exec_plan is not None
    assert eng.cfg.exec_plan.hw_label == "v5p"
