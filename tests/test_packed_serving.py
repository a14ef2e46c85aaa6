"""Token-packed serving step: layout round-trip, equivalence, compile bound.

The packed step replaces the padded (B, W) window with one dense (T,) token
stream (``scheduler.pack_step`` -> ``transformer.serve_step_packed``). These
tests cover: the pure pack/unpack layout (including the hypothesis property
test over arbitrary slot/chunk mixes), token-identity of the packed engine
against the padded window path on the chunk-boundary edge cases (greedy AND
sampled), the all-decode tri-path regression (packed == windowed W=1 ==
legacy bucketed at the same seed), the <= 3 step-shape compile bound, the
padding-efficiency counters, and the perf model's wasted-token term.
"""
import numpy as np
import jax
import pytest

from repro.configs import get_smoke_config
from repro.hwmodel import perf_model as pm
from repro.models import registry as R
from repro.serving import (ChunkTask, FINISH_EOS, FINISH_LENGTH, LLMEngine,
                           Request, SamplingParams, SchedulerOutput,
                           pack_bucket, pack_step, unpack_step)


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


def _tokens(params, cfg, reqs_fn, **kw):
    eng = _run(params, cfg, reqs_fn(), **kw)
    return {o.rid: o.tokens for o in eng.outputs()}, eng


# ---------------------------------------------------------------------------
# Pack/unpack layout (pure, no model)
# ---------------------------------------------------------------------------

def _mk_so(decode_slots, chunk_specs, vocab=512):
    """chunk_specs: [(slot, plen, start, length)] against fresh requests."""
    chunks = []
    for slot, plen, start, length in chunk_specs:
        req = _req(slot, plen, vocab=vocab)
        chunks.append(ChunkTask(slot, req, start, length,
                                start + length >= plen))
    n = len(decode_slots) + sum(c.length for c in chunks)
    return SchedulerOutput(decode_slots=tuple(decode_slots),
                           chunks=tuple(chunks), n_scheduled_tokens=n)


def test_pack_step_layout_basics():
    B, chunk = 4, 8
    so = _mk_so([1, 3], [(0, 20, 8, 8), (2, 5, 0, 5)])
    last = np.array([0, 11, 0, 13], np.int32)
    slot_pos = np.array([8, 9, 0, 7], np.int64)
    ps = pack_step(so, last, slot_pos, B, chunk)
    assert ps.n_valid == 2 + 8 + 5
    assert ps.n_batch == pack_bucket(ps.n_valid, B, chunk, True)
    # decode segments first: their tokens/positions come from last/slot_pos
    assert ps.tokens[0] == 11 and ps.positions[0] == 9
    assert ps.tokens[1] == 13 and ps.positions[1] == 7
    # chunk positions are start..start+len
    assert list(ps.positions[2:10]) == list(range(8, 16))
    assert list(ps.positions[10:15]) == list(range(0, 5))
    # padding rows scatter out of bounds (slot B) so the model drops them
    assert (ps.slot_ids[ps.n_valid:] == B).all()
    # fill levels advance per slot; idle slots keep theirs
    assert list(ps.new_pos) == [16, 10, 5, 8]
    # emitting slots: both decodes + the finishing chunk (slot 2)
    assert sorted(ps.emit_slots) == [1, 2, 3]
    assert ps.emit_idx[2] == 14      # last token of slot 2's chunk
    # segment boundaries are cu_seqlens-style
    assert list(ps.cu_seqlens) == [0, 1, 2, 10, 15]


def test_pack_bucket_bounded_shapes():
    B, chunk = 4, 16
    # pure decode -> one fixed shape regardless of how many slots run
    assert len({pack_bucket(d, B, chunk, False) for d in range(1, B + 1)}) == 1
    # mixed steps under the engine's default budget -> one fixed shape
    budget = pack_bucket(0, B, chunk, True)
    mixed = {pack_bucket(n, B, chunk, True) for n in range(1, budget + 1)}
    assert mixed == {budget}
    # floor overflow grows pow-2 (at most one extra shape in practice)
    assert pack_bucket(budget + 3, B, chunk, True) == 2 * budget


def test_unpack_round_trips_explicit_mix():
    B, chunk = 4, 8
    so = _mk_so([0, 2], [(1, 30, 16, 8), (3, 3, 0, 3)])
    last = np.zeros(B, np.int32)
    slot_pos = np.array([5, 16, 9, 0], np.int64)
    dec, chunks = unpack_step(pack_step(so, last, slot_pos, B, chunk))
    assert dec == (0, 2)
    assert chunks == ((1, 16, 8), (3, 0, 3))


# (The hypothesis property test over arbitrary slot/chunk mixes lives in
# tests/test_packed_layout_properties.py, behind the repo's importorskip
# guard — a module-level skip there must not take these tests with it.)


# ---------------------------------------------------------------------------
# Packed engine == padded window path (chunk-boundary edge cases)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lens,chunk", [
    ([5], 16),                        # prompt shorter than one chunk
    ([24], 8),                        # exact multiple of the chunk
    ([3, 8, 17, 30, 9, 26], 8),       # mixed lengths through slot reuse
])
def test_packed_matches_window_greedy(tiny, lens, chunk):
    cfg, params = tiny
    mk = lambda: [_req(rid, L, max_new=4, vocab=cfg.vocab)
                  for rid, L in enumerate(lens)]
    ref, _ = _tokens(params, cfg, mk, chunk_size=chunk)
    got, eng = _tokens(params, cfg, mk, chunk_size=chunk, packed=True)
    assert got == ref
    assert eng.stats.prefill_compiles == 0


def test_packed_matches_window_sampled(tiny):
    # The packed step must consume randomness exactly like the window path:
    # keys commit only on emit, so sampled streams are identical.
    cfg, params = tiny
    mk = lambda: [_req(rid, L, max_new=5, vocab=cfg.vocab,
                       sampling=SamplingParams(temperature=0.9, top_k=16,
                                               seed=rid + 3))
                  for rid, L in enumerate([4, 19, 27])]
    ref, _ = _tokens(params, cfg, mk, chunk_size=8)
    got, _ = _tokens(params, cfg, mk, chunk_size=8, packed=True)
    assert got == ref


def test_packed_matches_unchunked_single_slot(tiny):
    # Against the ground-truth unchunked path (no slot-reuse divergence at
    # B=1): packed == legacy == windowed for a fresh slot.
    cfg, params = tiny
    for plen in (5, 17, 24):
        mk = lambda: [_req(2, plen, max_new=4, vocab=cfg.vocab)]
        ref, _ = _tokens(params, cfg, mk, batch_slots=1)
        got, _ = _tokens(params, cfg, mk, batch_slots=1, chunk_size=8,
                         packed=True)
        assert got == ref


def test_packed_near_capacity_request_is_exact(tiny):
    # The packed scatter writes exact (slot, pos) coordinates — a
    # prompt_len + max_new == buffer_len request needs no window slack.
    cfg, params = tiny
    mk = lambda: [_req(0, 24, max_new=8, vocab=cfg.vocab)]     # 24 + 8 == 32
    ref, _ = _tokens(params, cfg, mk, buffer_len=32, chunk_size=16)
    got, eng = _tokens(params, cfg, mk, buffer_len=32, chunk_size=16,
                       packed=True)
    assert got == ref
    assert eng.outputs()[0].finish_reason == FINISH_LENGTH
    assert eng.core.T_alloc == 32        # no over-allocation in packed mode


def test_packed_eos_mid_run_frees_slot(tiny):
    cfg, params = tiny
    probe, _ = _tokens(params, cfg,
                       lambda: [_req(0, 5, max_new=1, vocab=cfg.vocab)])
    eos = probe[0][0]
    eng = LLMEngine(params, cfg, batch_slots=1, buffer_len=64,
                    chunk_size=8, packed=True, eos_id=eos)
    eng.submit(_req(0, 5, max_new=8, vocab=cfg.vocab))
    eng.submit(_req(1, 20, max_new=3, vocab=cfg.vocab))
    eng.run_until_drained()
    outs = {o.rid: o for o in eng.outputs()}
    assert outs[0].finish_reason == FINISH_EOS
    assert outs[1].n_tokens >= 1
    assert eng.stats.completed == 2


def test_packed_tight_token_budget_stays_exact(tiny):
    cfg, params = tiny
    mk = lambda: [_req(0, 4, max_new=10, vocab=cfg.vocab),
                  _req(1, 26, max_new=3, vocab=cfg.vocab)]
    ref, _ = _tokens(params, cfg, mk, chunk_size=8)
    got, eng = _tokens(params, cfg, mk, chunk_size=8, packed=True,
                       max_step_tokens=2)
    assert got == ref
    assert eng.stats.completed == 2


# ---------------------------------------------------------------------------
# All-decode fast path: packed == windowed (W=1) == legacy bucketed decode
# ---------------------------------------------------------------------------

def test_all_decode_tri_path_identical(tiny):
    # All slots fill in the first iteration (no mid-run admissions), so
    # every later step is chunk-free: the packed decode bucket, the W=1
    # window, and the legacy fused (B, 1) decode must produce bit-identical
    # streams at the same seed — greedy and sampled slots mixed.
    cfg, params = tiny
    mk = lambda: [
        _req(0, 6, max_new=6, vocab=cfg.vocab),
        _req(1, 6, max_new=6, vocab=cfg.vocab,
             sampling=SamplingParams(temperature=0.8, top_k=12, seed=7)),
        _req(2, 6, max_new=6, vocab=cfg.vocab,
             sampling=SamplingParams(temperature=1.3, seed=11)),
    ]
    kw = {"batch_slots": 3, "buffer_len": 32}
    legacy, _ = _tokens(params, cfg, mk, **kw)
    windowed, eng_w = _tokens(params, cfg, mk, chunk_size=1, **kw)
    packed, eng_p = _tokens(params, cfg, mk, chunk_size=1, packed=True, **kw)
    assert packed == windowed == legacy
    # steady state really was decode-shaped on both step-based engines
    assert ("window", 1) in eng_w.core.step_shapes
    assert any(k == "packed" for k, _t in eng_p.core.step_shapes)


# ---------------------------------------------------------------------------
# Compile bound + stats counters
# ---------------------------------------------------------------------------

def test_packed_step_compiles_bounded_regardless_of_length_mix(tiny):
    cfg, params = tiny
    lens = [3, 5, 9, 13, 17, 25, 33, 47]        # 8 distinct lengths
    eng = _run(params, cfg,
               [_req(rid, L, max_new=2, vocab=cfg.vocab)
                for rid, L in enumerate(lens)],
               batch_slots=4, chunk_size=16, packed=True)
    assert eng.stats.completed == len(lens)
    assert eng.stats.step_compiles <= 3
    assert eng.stats.prefill_compiles == 0


def test_padding_efficiency_counters(tiny):
    # B=4 / chunk 16: the window's mixed step carries B*W = 64 batch tokens,
    # the packed bucket 32 — decode+chunk coexistence shows the gap.
    cfg, params = tiny
    mk = lambda: [_req(rid, L, max_new=6, vocab=cfg.vocab)
                  for rid, L in enumerate([5, 40, 17, 30, 9])]
    _, eng_w = _tokens(params, cfg, mk, batch_slots=4, chunk_size=16)
    _, eng_p = _tokens(params, cfg, mk, batch_slots=4, chunk_size=16,
                       packed=True)
    for eng in (eng_w, eng_p):
        st = eng.stats
        assert 0 < st.packed_tokens <= st.padded_tokens
        assert 0.0 < st.padding_efficiency <= 1.0
    # both modes did the same USEFUL work (same valid-token count)...
    assert eng_p.stats.packed_tokens == eng_w.stats.packed_tokens
    # ...but the packed batches carry strictly less padding
    assert eng_p.stats.padded_tokens < eng_w.stats.padded_tokens
    assert eng_p.stats.padding_efficiency > eng_w.stats.padding_efficiency


def test_packed_requires_chunk_size(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="chunk_size"):
        LLMEngine(params, cfg, batch_slots=2, buffer_len=32, packed=True)


def test_packed_recurrent_family_falls_back():
    cfg = get_smoke_config("falcon_mamba_7b")
    params = R.model_init(jax.random.PRNGKey(0), cfg)
    with pytest.warns(UserWarning, match="chunked prefill requires"):
        eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=32,
                        chunk_size=8, packed=True)
    assert eng.chunk is None and not eng.packed
    eng.submit(_req(0, 6, max_new=3, vocab=cfg.vocab))
    stats = eng.run_until_drained()
    assert stats.completed == 1 and stats.tokens_out == 3


# ---------------------------------------------------------------------------
# Perf model: wasted-vs-valid-token term
# ---------------------------------------------------------------------------

def test_perf_model_padding_efficiency_definition():
    assert pm.padding_efficiency(19, 64) == pytest.approx(19 / 64)
    assert pm.padding_efficiency(0, 0) == 1.0
    assert pm.padding_efficiency(32, 32) == 1.0


def test_perf_model_wasted_token_term(tiny):
    cfg, _ = tiny
    # 3 decode slots + one 16-token chunk inside a (B=4, W=16) window: 19
    # valid of 64 batch tokens (the ISSUE's ~70%-padding motivating case)
    padded = pm.serve_step_timing(cfg, valid_tokens=19, batch_tokens=64,
                                  hw=pm.CPU)
    packed = pm.serve_step_timing(cfg, valid_tokens=19, batch_tokens=32,
                                  hw=pm.CPU)
    assert padded.wasted_s > packed.wasted_s
    assert padded.total_s > packed.total_s
    assert packed.step_efficiency > padded.step_efficiency
    # per-layer waste is exactly the II this layer would shed at valid M
    layer = pm.GemmLayer("l", M=64, d_in=256, d_out=256, m_valid=19)
    t = pm.layer_timing(layer, pm.CPU)
    ideal = pm.layer_timing(pm.GemmLayer("l", M=19, d_in=256, d_out=256),
                            pm.CPU)
    assert t.t_wasted == pytest.approx(t.ii - ideal.ii)
    assert 0.0 < t.t_wasted <= t.ii
    # fully valid batches carry no waste
    dense = pm.GemmLayer("l", M=64, d_in=256, d_out=256)
    assert pm.layer_timing(dense, pm.CPU).t_wasted == 0.0
    # efficiency stays a fraction even at extreme padding (waste is bounded
    # by each layer's own II)
    extreme = pm.serve_step_timing(cfg, valid_tokens=1, batch_tokens=64,
                                   hw=pm.CPU)
    assert 0.0 < extreme.step_efficiency <= 1.0
    # m_valid shards over dp alongside M: a half-padded global batch stays
    # half-padded per device instead of clamping to "no waste"
    sharded = pm.serve_step_timing(cfg, valid_tokens=256, batch_tokens=512,
                                   hw=pm.V5E, n_devices=8, tp=1)
    assert sharded.wasted_s > 0.0


def test_packed_chunk_free_steps_book_decode_s(tiny):
    # Chunk-free packed steps book decode_s (not mixed_s): the engine's and
    # serve's phase split counts them as decode.
    cfg, params = tiny
    assert cfg.ovsf.enable
    eng = _run(params, cfg,
               [_req(rid, L, max_new=6, vocab=cfg.vocab)
                for rid, L in enumerate([5, 11, 20])],
               batch_slots=4, chunk_size=8, packed=True, hw="v5e")
    assert eng.stats.decode_s > 0.0


def test_packed_rejects_legacy_scheduler(tiny):
    cfg, params = tiny

    class Legacy:
        def add(self, req):
            return True

        def next_group(self, n):
            return None

        def __len__(self):
            return 0

    with pytest.raises(ValueError, match="legacy"):
        LLMEngine(params, cfg, batch_slots=2, buffer_len=32,
                  chunk_size=8, packed=True, scheduler=Legacy())
