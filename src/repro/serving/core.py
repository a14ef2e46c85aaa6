"""EngineCore: stacked slot cache, unified step execution, fused sampling.

The core owns everything that touches the device, behind one contract:
``step(SchedulerOutput) -> StepOutput``.

* **One stacked cache** — every per-slot cache leaf carries a leading ``B``
  slot axis; ``pos`` is per-slot, so slots sit at different sequence depths
  inside one pytree. In chunked mode the buffer is over-allocated by the
  window width so ragged window writes never clamp at the buffer edge
  (``dynamic_update_slice`` clamps its start index — without the slack a
  near-capacity slot's padded columns would silently overwrite history).
* **Fused window step (chunked mode)** — ONE jit'd vmapped call advances
  decode slots (1 valid token) and consumes prompt chunks (up to
  ``chunk_size`` valid tokens) in the same ``(B, W)`` batch via the ragged
  ``serve_step_window`` entry point. Steady state compiles exactly two step
  shapes — ``W = chunk_size`` (any chunk scheduled) and ``W = 1`` (pure
  decode) — regardless of the prompt-length mix.
* **Token-packed step (``packed=True``)** — the scheduler's valid tokens are
  flattened into ONE dense ``(T,)`` stream (``scheduler.pack_step``; T = a
  pow-2 bucket) with per-token slot/position vectors, executed by
  ``serve_step_packed`` against a natural-layout cache (B rows per leaf,
  per-slot ``pos`` vector; writes are exact scatters, so no window slack is
  allocated). A decode slot costs 1 token instead of a W-wide padded row —
  the ``(B, W)`` window's dead decode columns never reach the model.
  ``StepOutput.n_valid_tokens``/``n_batch_tokens`` record the padding
  efficiency of every path for the benches and calibration.
* **Paged KV cache (``paged=True``)** — K/V live in shared per-layer page
  pools (``serving.kvcache``) instead of per-slot worst-case buffers; the
  core owns a :class:`~repro.serving.kvcache.PagedKVCache` whose host page
  table rides into every fused step call (constant shape — page churn never
  retraces). Both the packed and window step styles run against the paged
  packed trunk with exact scatters into granted pages, so neither needs
  window slack and both stay bit-identical to the contiguous cache. The
  ENGINE grants pages before calling ``step`` (see ``LLMEngine._page_gate``).
* **Bucketed batched prefill (legacy mode)** — prompts right-padded to the
  scheduler's bucket length prefill as ONE jit'd ``serve_prefill_ragged``
  call over all ``B`` slot rows. The call retraces once per bucket length,
  never per prompt length; ``prefill_compiles`` counts actual traces.
* **Fused decode+sample** — the model step AND per-slot sampling (greedy /
  temperature / top-k, each slot's own PRNG key) run in the same jit'd call,
  so sampling adds zero extra dispatches.

Per-request sampling state lives in (B,)-shaped host arrays scattered at
admission; a slot's PRNG key is seeded from its request's
``SamplingParams.seed`` and advances exactly once per *emitted* token (a
mid-prompt chunk commits no key), so sampled streams are independent of
batch composition, slot placement, and chunking.

Exactness: right-padded prefill/windows are exact for KV-cache families
(causal mask; per-slot ``pos`` re-based to the true length; decode
overwrites each padded cache position before attending to it). SSM/hybrid
state would run through the padding, so those families use the exact
per-request prefill path (``supports_bucketing`` is False and the engine
falls back automatically).

Health + chaos: every fused step fn takes a ``(B,)`` additive ``poison``
vector (zeros normally — constant shape, so fault injection never retraces)
and returns a per-slot ``ok = all(isfinite(logits))`` flag computed INSIDE
the jit'd call, so the NaN quarantine costs no extra dispatch. A
:class:`~repro.runtime.faults.FaultPlan` wired at construction drives the
poison vector plus injected step failures/delays off ``step_idx`` — chaos
flows through the SAME detection path organic NaNs would take.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import registry as R
from repro.runtime.faults import FaultPlan
from repro.serving.api import Request, SamplingParams
from repro.serving.kvcache import PagedKVCache
from repro.serving.scheduler import SchedulerOutput
from repro.serving.trace import call_step, span

_BUCKETED_FAMILIES = ("dense", "moe", "vlm", "encdec")


def _sample_token(logits: jnp.ndarray, temp: jnp.ndarray, top_k: jnp.ndarray,
                  greedy: jnp.ndarray, key: jnp.ndarray):
    """Sample one token from (V,) logits under per-slot params.

    Returns (token, advanced key). Dynamic top-k: k==0 disables filtering;
    otherwise logits below the k-th largest are masked before the
    temperature-scaled categorical draw.
    """
    V = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    tok_greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    nkey, skey = jax.random.split(key)
    k = jnp.where(top_k > 0, top_k, V)
    thresh = jnp.sort(lg)[::-1][jnp.clip(k - 1, 0, V - 1)]
    filt = jnp.where(lg >= thresh, lg, -jnp.inf)
    scaled = filt / jnp.maximum(temp, 1e-6)
    tok_sampled = jax.random.categorical(skey, scaled).astype(jnp.int32)
    return jnp.where(greedy, tok_greedy, tok_sampled), nkey


# Shared across cores; retraces per (B, V) shape only.
_SAMPLE = jax.jit(jax.vmap(_sample_token))


def _fused_sample(logits, temps, topks, greedy, keys):
    """Trace-time tail shared by every fused step fn: all-greedy batches
    (the default) skip the per-slot full-vocab sort + categorical entirely
    at runtime; greedy slots never consume their keys, so leaving them
    unadvanced preserves the per-request determinism contract (one sampling
    slot forces the mixed branch)."""

    def _all_greedy(_):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys

    def _mixed(_):
        return jax.vmap(_sample_token)(logits, temps, topks, greedy, keys)

    return jax.lax.cond(jnp.all(greedy), _all_greedy, _mixed, None)


@jax.named_scope("sample")
def _health_and_sample(logits, poison, temps, topks, greedy, keys):
    """Shared fused tail: apply the (B,) additive poison (zeros when no
    fault fires — same shape either way, so chaos never retraces), check
    emitted-logits finiteness per slot INSIDE the jit'd call, sample."""
    logits = logits + poison[:, None].astype(logits.dtype)
    ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
    toks, nkeys = _fused_sample(logits, temps, topks, greedy, keys)
    return toks, nkeys, ok


@functools.lru_cache(maxsize=16)
def _decode_step_fn(cfg: ModelConfig):
    """Compiled fused decode+sample step, shared across engine instances
    with the same (hashable) config — engine restarts don't recompile."""

    def _batched_step(p, caches, tokens, poison, temps, topks, greedy, keys):
        """(stacked caches, (B,) last tokens, (B,) poison, (B,) sampling
        state) -> ((B,) next tokens, caches, (B,2) advanced keys, (B,) ok)."""

        def one_slot(cache, tok):
            logits, new_cache = R.serve_step(p, cfg, cache, tok[None, None])
            return logits[0], new_cache

        logits, new_caches = jax.vmap(one_slot)(caches, tokens)
        toks, nkeys, ok = _health_and_sample(logits, poison, temps, topks,
                                             greedy, keys)
        return toks, new_caches, nkeys, ok

    return jax.jit(_batched_step)


@functools.lru_cache(maxsize=64)
def _packed_step_fn(cfg: ModelConfig, Tb: int):
    """Compiled fused packed step + sampling, shared across engine instances
    with the same (config, token-bucket) pair. One trace per pow-2 bucket."""

    def _packed(p, caches, tokens, slot_ids, positions, new_pos, emit_idx,
                poison, temps, topks, greedy, keys):
        """((Tb,) packed tokens/slot_ids/positions, (B,) new fill levels,
        (B,) emit indices, (B,) poison, (B,) sampling state) ->
        ((B,) sampled tokens, caches, (B, 2) keys, (B,) ok)."""
        logits, new_caches = R.serve_step_packed(
            p, cfg, caches, tokens, slot_ids, positions, new_pos, emit_idx)
        toks, nkeys, ok = _health_and_sample(logits, poison, temps, topks,
                                             greedy, keys)
        return toks, new_caches, nkeys, ok

    return jax.jit(_packed)


@functools.lru_cache(maxsize=64)
def _paged_step_fn(cfg: ModelConfig, Tb: int):
    """Compiled fused *paged* packed step + sampling: identical contract to
    ``_packed_step_fn`` plus the (n_slots + 1, max_pages) page table. The
    table rides as a traced argument (constant shape), so page churn —
    grants, preemptions, recovery rebuilds — never retraces."""

    def _paged(p, caches, page_table, tokens, slot_ids, positions, new_pos,
               emit_idx, poison, temps, topks, greedy, keys):
        logits, new_caches = R.serve_step_paged(
            p, cfg, caches, page_table, tokens, slot_ids, positions,
            new_pos, emit_idx)
        toks, nkeys, ok = _health_and_sample(logits, poison, temps, topks,
                                             greedy, keys)
        return toks, new_caches, nkeys, ok

    return jax.jit(_paged)


@functools.lru_cache(maxsize=32)
def _paged_window_step_fn(cfg: ModelConfig, W: int):
    """Compiled fused *paged* window step: the (B, W) ragged window is
    flattened onto the paged packed trunk inside the jit (see
    ``models.transformer.serve_step_window_paged``) — no per-slot vmap, and
    the same two steady-state shapes (W = chunk_size, W = 1) as the
    contiguous window path."""

    def _pw(p, caches, page_table, tokens, n_tok, poison, temps, topks,
            greedy, keys):
        logits, new_caches = R.serve_step_window_paged(
            p, cfg, caches, page_table, tokens, n_tok)
        toks, nkeys, ok = _health_and_sample(logits, poison, temps, topks,
                                             greedy, keys)
        return toks, new_caches, nkeys, ok

    return jax.jit(_pw)


@functools.lru_cache(maxsize=64)
def _mm_packed_step_fn(cfg: ModelConfig, Tb: int):
    """Compiled fused *multi-model* packed step: identical contract to
    ``_packed_step_fn`` plus a (B,) ``model_ids`` vector routing each slot's
    tokens to its stacked alpha variant (``serve_step_packed_multi``). The
    vector rides as a traced argument (constant shape), so re-routing a slot
    to a different resident model never retraces."""

    def _mm(p, caches, tokens, slot_ids, positions, new_pos, emit_idx,
            model_ids, poison, temps, topks, greedy, keys):
        logits, new_caches = R.serve_step_packed_multi(
            p, cfg, caches, tokens, slot_ids, positions, new_pos, emit_idx,
            model_ids)
        toks, nkeys, ok = _health_and_sample(logits, poison, temps, topks,
                                             greedy, keys)
        return toks, new_caches, nkeys, ok

    return jax.jit(_mm)


@functools.lru_cache(maxsize=32)
def _mm_window_step_fn(cfg: ModelConfig, W: int):
    """Compiled fused *multi-model* window step: the (B, W) ragged window is
    flattened onto the packed multi trunk inside the jit (see
    ``models.transformer.serve_step_window_multi``) — exact scatters, no
    window slack, and the same two steady-state shapes (W = chunk_size,
    W = 1) as the single-model window path."""

    def _mm(p, caches, tokens, n_tok, model_ids, poison, temps, topks,
            greedy, keys):
        logits, new_caches = R.serve_step_window_multi(
            p, cfg, caches, tokens, n_tok, model_ids)
        toks, nkeys, ok = _health_and_sample(logits, poison, temps, topks,
                                             greedy, keys)
        return toks, new_caches, nkeys, ok

    return jax.jit(_mm)


@functools.lru_cache(maxsize=32)
def _window_step_fn(cfg: ModelConfig, W: int):
    """Compiled fused window step: per-slot ragged (W-wide) model advance +
    sampling, shared across engine instances with the same (config, width)."""

    def _batched_window(p, caches, tokens, n_tok, poison, temps, topks,
                        greedy, keys):
        """(stacked caches, (B, W) token windows, (B,) valid counts,
        (B,) poison, (B,) sampling state) -> ((B,) sampled tokens, caches,
        (B,2) keys, (B,) ok).

        Row semantics: n_tok == 1 with the last generated token in column 0
        is a decode slot; 1 < n_tok <= W is a prompt chunk; n_tok == 0 is an
        idle slot (cache pos unchanged, sampled token meaningless)."""

        def one_slot(cache, toks, n):
            logits, new_cache = R.serve_step_window(p, cfg, cache,
                                                    toks[None], n)
            return logits[0], new_cache

        logits, new_caches = jax.vmap(one_slot)(caches, tokens, n_tok)
        toks, nkeys, ok = _health_and_sample(logits, poison, temps, topks,
                                             greedy, keys)
        return toks, new_caches, nkeys, ok

    return jax.jit(_batched_window)


@dataclasses.dataclass
class StepOutput:
    """Result of one ``EngineCore.step``: sampled tokens + timing samples.

    ``first_tokens`` maps slot -> the first sampled token of a request whose
    prompt completed this step (legacy prefill or final chunk);
    ``decode_tokens`` maps slot -> the next generated token of a decoding
    slot. Wall times are split by phase so the measured-vs-modeled
    calibration loop (``runtime.calibrate``) can consume clean decode-shaped
    samples (``decode_s``) separately from prefill/mixed work.
    """
    first_tokens: dict = dataclasses.field(default_factory=dict)
    decode_tokens: dict = dataclasses.field(default_factory=dict)
    # slots whose EMITTED logits were non-finite this step: their sampled
    # token is withheld (never appears in the dicts above) and the engine
    # quarantines the request as FINISH_ERROR
    bad_slots: tuple = ()
    prefill_s: float = 0.0      # legacy bucketed/exact prefill wall time
    decode_s: float = 0.0       # pure fused decode wall time
    mixed_s: float = 0.0        # fused window/packed (chunks + decode) wall
    n_prompt_tokens: int = 0    # prompt tokens consumed (chunks + prefills)
    n_decode_tokens: int = 0    # decode slots advanced
    # padding-efficiency raw material (one definition for benches AND
    # calibration: hwmodel.perf_model.padding_efficiency(valid, batch))
    n_valid_tokens: int = 0     # tokens that were real work this step
    n_batch_tokens: int = 0     # tokens the device batch actually carried

    @property
    def wall_s(self) -> float:
        return self.prefill_s + self.decode_s + self.mixed_s


def _leaf_batch_axes(cfg: ModelConfig, buffer_len: int):
    """Per-leaf batch-axis index of the serving cache (-1 = no batch axis,
    e.g. the shared scalar ``pos``), found by diffing B=2 vs B=1 specs."""

    def axis_of(s2, s1):
        for ax, (a, b) in enumerate(zip(s2.shape, s1.shape)):
            if a != b:
                return ax
        return -1

    return jax.tree_util.tree_map(axis_of, R.cache_spec(cfg, 2, buffer_len),
                                  R.cache_spec(cfg, 1, buffer_len))


class EngineCore:
    """Device-side half of the engine: caches, prefill, decode, sampling."""

    def __init__(self, params, cfg: ModelConfig, *, batch_slots: int = 4,
                 buffer_len: int = 256, window: int = 0,
                 packed: bool = False, paged: bool = False,
                 page_size: int = 16, kv_pages: Optional[int] = None,
                 faults: Optional[FaultPlan] = None, variants: int = 0):
        self.params = params
        self.cfg = cfg
        self.B = batch_slots
        self.T = buffer_len
        self.window = window
        self.packed = packed
        self.paged = paged
        self.page_size = page_size
        self.faults = faults
        # Multi-model mode (variants = number of stacked alpha variants the
        # params pytree carries; 0 = single-model). Every slot routes through
        # its entry in the host ``model_ids`` vector — the gateway's
        # same-architecture cross-config batching.
        self.variants = variants
        if variants:
            if paged:
                raise NotImplementedError(
                    "multi-model variants over the paged KV cache are not "
                    "supported yet (page-table routing per variant)")
            if window <= 0:
                raise ValueError(
                    "multi-model serving consumes prompts via chunks; pass "
                    "a chunked window (chunk_size)")
        # monotone fused-step counter driving the fault plan; the engine
        # carries it across a watchdog core rebuild so a step-pinned fault
        # fires exactly once per run, not once per core instance
        self.step_idx = 0
        self._zero_poison = np.zeros(batch_slots, np.float32)
        # Logical capacity is buffer_len (admission math unchanged); the
        # allocation carries `window` slack columns so a W-wide ragged write
        # at pos <= buffer_len - 1 never clamps (see module docstring). The
        # packed, paged, and multi-model paths scatter at exact (slot, pos)
        # coordinates — no clamping is possible, so they need (and get) no
        # slack.
        self.T_alloc = (buffer_len if (packed or paged or variants)
                        else buffer_len + window)
        self.prefill_compiles = 0
        self.step_shapes: set = set()   # distinct fused step shapes traced
        self.pager: Optional[PagedKVCache] = None
        if paged:
            # K/V in shared page pools (serving/kvcache.py): device memory
            # is n_pages x page_size tokens regardless of batch_slots, and
            # both packed and window step styles run on the paged packed
            # trunk (exact scatters through the page table).
            if window <= 0:
                raise ValueError("paged serving consumes prompts via chunks;"
                                 " pass a chunked window (chunk_size)")
            if buffer_len % page_size:
                raise ValueError(f"buffer_len={buffer_len} must be a "
                                 f"multiple of page_size={page_size} (pages "
                                 f"tile the virtual slot buffer exactly)")
            max_pages = buffer_len // page_size
            n_pages = (int(kv_pages) if kv_pages is not None
                       else batch_slots * max_pages)
            kv_dtype = jnp.dtype(cfg.kv_cache_dtype or cfg.dtype)
            page_bytes = (2 * cfg.n_layers * page_size * cfg.n_kv_heads
                          * cfg.hd * kv_dtype.itemsize)
            self.pager = PagedKVCache(batch_slots, page_size, n_pages,
                                      max_pages, page_bytes)
            self.caches = R.init_paged_cache(cfg, batch_slots, page_size,
                                             n_pages)
            self.caches["pos"] = jnp.zeros((batch_slots,), jnp.int32)
            self._host_pos = np.zeros(batch_slots, np.int64)
        elif packed or variants:
            # Natural (family) cache layout with B rows per leaf and a
            # per-slot pos vector: the packed model call scans layers over
            # it directly — no per-slot vmap, no leading-slot transpose.
            # (Multi-model window mode also lives here: its (B, W) window is
            # flattened onto the packed multi trunk inside the jit.)
            self.caches = R.init_cache(cfg, batch_slots, self.T_alloc)
            self.caches["pos"] = jnp.zeros((batch_slots,), jnp.int32)
            # host mirror of the per-slot fill levels (decode positions)
            self._host_pos = np.zeros(batch_slots, np.int64)
        else:
            # ONE stacked cache: every per-slot leaf gains a leading B axis.
            one = R.init_cache(cfg, 1, self.T_alloc)
            self.caches = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None],
                                           (batch_slots,) + a.shape), one)
            self._axes = _leaf_batch_axes(cfg, self.T_alloc)
        self._step_fn = _decode_step_fn(cfg)
        # Per-slot variant routing (host-side; the ENGINE scatters each
        # slot's model index at admission, exactly like sampling state).
        # Single-model engines leave it all-zero and never pass it down.
        self.model_ids = np.zeros(batch_slots, np.int32)
        # Per-slot sampling state (host-side, scattered at admission).
        self.temps = np.zeros(batch_slots, np.float32)
        self.topks = np.zeros(batch_slots, np.int32)
        self.greedy = np.ones(batch_slots, bool)
        self.keys = np.array(
            np.broadcast_to(np.asarray(jax.random.PRNGKey(0)),
                            (batch_slots, 2)))
        self.seeded = np.zeros(batch_slots, bool)   # keys[i] is its request's

        alloc_len = self.T_alloc

        def _raw_prefill(p, tokens, lengths):
            # trace-time side effect: counts actual (re)compilations
            self.prefill_compiles += 1
            return R.serve_prefill_ragged(p, cfg, {"tokens": tokens},
                                          alloc_len, lengths)

        def _raw_prefill_exact(p, tokens):
            self.prefill_compiles += 1
            return R.serve_prefill(p, cfg, {"tokens": tokens}, alloc_len)

        self._prefill = jax.jit(_raw_prefill)          # retraces per bucket
        self._prefill_exact = jax.jit(_raw_prefill_exact)  # per prompt length

    @property
    def supports_bucketing(self) -> bool:
        """Padded batched prefill is exact only for KV-cache families."""
        return self.cfg.family in _BUCKETED_FAMILIES

    # -- sampling state ----------------------------------------------------

    def _set_sampling(self, i: int, sp: SamplingParams,
                      resume_key: Optional[np.ndarray] = None) -> None:
        self.temps[i] = max(sp.temperature, 0.0)
        self.topks[i] = sp.top_k
        self.greedy[i] = sp.greedy
        # a recomputed (preempted/recovered) request resumes from its
        # stashed key, not a fresh seed: the key advanced once per emitted
        # token before eviction, so the resumed sampled stream continues
        # exactly where the unpreempted run would be
        self.keys[i] = (np.asarray(resume_key) if resume_key is not None
                        else np.asarray(jax.random.PRNGKey(sp.seed)))
        self.seeded[i] = True

    def clear_sampling(self, i: int) -> None:
        """Reset a freed slot to greedy defaults (the next request re-seeds
        at admission; an idle sampling slot would otherwise force the mixed
        branch of every fused step)."""
        self.temps[i] = 0.0
        self.topks[i] = 0
        self.greedy[i] = True
        self.seeded[i] = False

    def _sample(self, logits: jnp.ndarray) -> np.ndarray:
        """Sample (B,) tokens from (B, V) logits; advances NO keys itself —
        callers commit ``self.keys`` rows for the slots they own."""
        toks, nkeys = _SAMPLE(logits, jnp.asarray(self.temps),
                              jnp.asarray(self.topks),
                              jnp.asarray(self.greedy),
                              jnp.asarray(self.keys))
        return np.asarray(toks), np.asarray(nkeys)

    # -- prefill -----------------------------------------------------------

    def prefill_group(self, slot_reqs: list, bucket: int):
        """Prefill same-bucket requests in ONE jit'd batched call.

        ``slot_reqs`` is [(slot, Request)]; request rows ride at their slot
        index inside a full (B, bucket) token batch (idle rows are dummies),
        so one compile per bucket serves every slot subset. Returns ((B,)
        first sampled tokens, (B,) per-slot finite-logits flags); rows
        outside ``slot_reqs`` are meaningless.
        """
        Lb = min(bucket, self.T)
        tokens = np.zeros((self.B, Lb), np.int32)
        lengths = np.ones(self.B, np.int32)
        for i, req in slot_reqs:
            plen = req.prompt_len
            tokens[i, :plen] = req.prompt
            lengths[i] = plen
            self._set_sampling(i, req.sampling, req.resume_key)
        logits, group_cache = self._prefill(self.params, jnp.asarray(tokens),
                                            jnp.asarray(lengths))
        for i, req in slot_reqs:
            self._adopt_row(i, group_cache, int(lengths[i]))
        # legacy-path health check rides host-side (the prefill call is not
        # one of the fused step fns); fault injection targets fused steps
        ok = np.asarray(jnp.all(jnp.isfinite(logits.astype(jnp.float32)),
                                axis=-1))
        toks, nkeys = self._sample(logits)
        for i, _req in slot_reqs:
            self.keys[i] = nkeys[i]
        return toks, ok

    def prefill_one(self, slot: int, req: Request) -> tuple:
        """Exact per-request prefill at native prompt length (fallback for
        recurrent-state families and the unbucketed baseline). Returns
        (first token, logits-finite flag)."""
        self._set_sampling(slot, req.sampling, req.resume_key)
        prompt = jnp.asarray(req.prompt[None, :], jnp.int32)
        logits, cache = self._prefill_exact(self.params, prompt)
        self.caches = jax.tree_util.tree_map(
            lambda big, small: big.at[slot].set(small), self.caches, cache)
        ok = bool(np.all(np.isfinite(np.asarray(logits, np.float32))))
        toks, nkeys = self._sample(
            jnp.broadcast_to(logits, (self.B,) + logits.shape[1:]))
        self.keys[slot] = nkeys[slot]
        return int(toks[slot]), ok

    def _adopt_row(self, i: int, group_cache, plen: int) -> None:
        """Scatter row i of a B-row prefill cache into slot i, re-basing the
        slot's ``pos`` to the true prompt length (padded K/V past it are
        masked until decode overwrites them)."""

        def put(big, grp, ax):
            if ax < 0:
                return big                          # shared leaf (pos)
            return big.at[i].set(
                jnp.take(grp, jnp.asarray([i]), axis=ax))

        self.caches = jax.tree_util.tree_map(put, self.caches, group_cache,
                                             self._axes)
        self.caches["pos"] = self.caches["pos"].at[i].set(plen)

    # -- decode ------------------------------------------------------------

    def decode(self, last_tokens: np.ndarray,
               poison: Optional[np.ndarray] = None) -> tuple:
        """Advance ALL slots one token with ONE fused decode+sample call.
        Returns ((B,) next tokens, (B,) finite-logits flags)."""
        self.step_shapes.add(("decode", 1))
        with span("engine.launch"):
            next_toks, self.caches, nkeys, ok = self._step_fn(
                self.params, self.caches, jnp.asarray(last_tokens),
                jnp.asarray(poison if poison is not None
                            else self._zero_poison),
                jnp.asarray(self.temps), jnp.asarray(self.topks),
                jnp.asarray(self.greedy), jnp.asarray(self.keys))
        with span("engine.wait"):
            self.keys = np.array(nkeys)              # writable host copy
            return np.asarray(next_toks), np.asarray(ok)   # one host sync

    # -- unified step ------------------------------------------------------

    def step(self, so: SchedulerOutput,
             last_tokens: Optional[np.ndarray] = None) -> StepOutput:
        """Execute one scheduler iteration against the device.

        Chunked mode (``so.chunks`` non-empty, or decode-only): ONE fused
        jit'd call advances decode slots and consumes prompt chunks in the
        same ``(B, W)`` batch. Legacy mode (``so.prefill_groups``): bucketed
        (or exact) prefill calls per group, then the fused ``(B, 1)`` decode
        for the running slots. ``last_tokens`` carries each decode slot's
        previously generated token at its slot index.

        A wired :class:`FaultPlan` fires here, keyed on ``step_idx``:
        ``fail``/``delay`` faults raise/sleep at the top of the step (the
        engine watchdog's territory); ``nan`` faults poison the fused call's
        logits so quarantine exercises the real detection path. ``step_idx``
        advances BEFORE the fault applies — after a watchdog core rebuild a
        step-pinned fault does not re-fire forever.
        """
        out = StepOutput()
        idx = self.step_idx
        self.step_idx += 1
        poison = None
        if self.faults:
            self.faults.raise_or_delay(idx)
            poison = self.faults.poison_row(idx, self.B)
        if self.packed or self.paged or self.variants:
            if so.prefill_groups:
                raise ValueError("packed/paged/multi-model mode serves "
                                 "prompts via chunks only; a legacy "
                                 "scheduler emitted prefill_groups")
            if so.chunks or so.decode_slots:
                t0 = time.perf_counter()
                if self.packed:
                    self._packed_step(so, last_tokens, out, poison)
                elif self.paged:
                    self._paged_window_step(so, last_tokens, out, poison)
                else:
                    self._mm_window_step(so, last_tokens, out, poison)
                dt = time.perf_counter() - t0
                # A chunk-free packed step IS decode-shaped: book it as
                # decode_s so the measured-vs-modeled calibration loop
                # (which consumes pure-decode samples) keeps working.
                if so.chunks:
                    out.mixed_s += dt
                else:
                    out.decode_s += dt
                out.n_prompt_tokens += sum(c.length for c in so.chunks)
            out.n_decode_tokens = len(out.decode_tokens)
            return out
        bad: list = []
        for pg in so.prefill_groups:
            t0 = time.perf_counter()
            if pg.exact:
                for i, req in pg.slot_reqs:
                    tok, fin = self.prefill_one(i, req)
                    if fin:
                        out.first_tokens[i] = tok
                    else:
                        bad.append(i)
                out.n_batch_tokens += sum(r.prompt_len
                                          for _i, r in pg.slot_reqs)
            else:
                toks, fin = self.prefill_group(list(pg.slot_reqs), pg.bucket)
                for i, req in pg.slot_reqs:
                    if fin[i]:
                        out.first_tokens[i] = int(toks[i])
                    else:
                        bad.append(i)
                out.n_batch_tokens += self.B * min(pg.bucket, self.T)
            out.prefill_s += time.perf_counter() - t0
            out.n_prompt_tokens += sum(r.prompt_len for _i, r in pg.slot_reqs)
            out.n_valid_tokens += sum(r.prompt_len for _i, r in pg.slot_reqs)
        if so.chunks:
            t0 = time.perf_counter()
            self._window_step(so, last_tokens, out, poison)
            out.mixed_s += time.perf_counter() - t0
            out.n_prompt_tokens += sum(c.length for c in so.chunks)
        elif so.decode_slots:
            last = np.zeros(self.B, np.int32)
            for i in so.decode_slots:
                last[i] = last_tokens[i]
            t0 = time.perf_counter()
            nxt, ok = self.decode(last, poison)
            out.decode_s += time.perf_counter() - t0
            for i in so.decode_slots:
                if ok[i]:
                    out.decode_tokens[i] = int(nxt[i])
                else:
                    bad.append(i)
            out.n_valid_tokens += len(so.decode_slots)
            out.n_batch_tokens += self.B
        out.bad_slots = out.bad_slots + tuple(bad)
        out.n_decode_tokens = len(out.decode_tokens)
        return out

    def _window_arrays(self, so: SchedulerOutput,
                       last_tokens: Optional[np.ndarray], W: int) -> tuple:
        """The (B, W) ragged window's host arrays — decode slots at width 1,
        chunk slots at their slice length, idle slots at 0 — with newly
        bound slots' sampling state seeded and their device fill level
        re-based to 0. Returns (tokens, n_tok, the newly bound slots)."""
        tokens = np.zeros((self.B, W), np.int32)
        n_tok = np.zeros(self.B, np.int32)
        for i in so.decode_slots:
            tokens[i, 0] = last_tokens[i]
            n_tok[i] = 1
        fresh = []
        for c in so.chunks:
            tokens[c.slot, :c.length] = c.req.prompt[c.start:c.start + c.length]
            n_tok[c.slot] = c.length
            if c.start == 0:            # new request: re-base pos, seed keys
                self._set_sampling(c.slot, c.req.sampling, c.req.resume_key)
                fresh.append(c.slot)
        if fresh:
            self.caches["pos"] = self.caches["pos"].at[
                jnp.asarray(fresh)].set(0)
        return tokens, n_tok, fresh

    def _launch(self, fn, host_args: tuple,
                poison: Optional[np.ndarray]) -> tuple:
        """Upload a fused step's host arrays, then its sampling state, call
        ``fn(params, caches, *arrays)`` and wait for its outputs. Returns
        the host (tokens, keys, ok)."""
        with span("engine.launch"):
            args = host_args + (
                poison if poison is not None else self._zero_poison,
                self.temps, self.topks, self.greedy, self.keys)
            toks, self.caches, nkeys, ok = call_step(
                fn, self.params, self.caches,
                *(jnp.asarray(a) for a in args))
        with span("engine.wait"):
            return np.asarray(toks), np.asarray(nkeys), np.asarray(ok)

    def _emit(self, so: SchedulerOutput, toks: np.ndarray,
              nkeys: np.ndarray, ok: np.ndarray, out: StepOutput) -> None:
        """Book a fused step's sampled tokens. Keys commit ONLY for emitting
        slots: a mid-prompt chunk consumes no randomness, keeping sampled
        streams identical to the unchunked path. A slot whose emitted logits
        went non-finite commits nothing — its token is garbage and its
        request is quarantined by the engine."""
        bad: list = []
        for i in so.decode_slots:
            if not ok[i]:
                bad.append(i)
                continue
            out.decode_tokens[i] = int(toks[i])
            self.keys[i] = nkeys[i]
        for c in so.chunks:
            if c.last:
                if not ok[c.slot]:
                    bad.append(c.slot)
                    continue
                out.first_tokens[c.slot] = int(toks[c.slot])
                self.keys[c.slot] = nkeys[c.slot]
        out.bad_slots = out.bad_slots + tuple(bad)

    def _window_step(self, so: SchedulerOutput,
                     last_tokens: Optional[np.ndarray],
                     out: StepOutput,
                     poison: Optional[np.ndarray] = None) -> None:
        """ONE fused ragged window call: decode slots ride at width 1, chunk
        slots at their slice length, idle slots at 0 — all inside a single
        (B, W) batch so prefill never stalls inter-token latency."""
        W = self.window or max(c.length for c in so.chunks)
        with span("engine.pack"):
            tokens, n_tok, _ = self._window_arrays(so, last_tokens, W)
        self.step_shapes.add(("window", W))
        toks, nkeys, ok = self._launch(_window_step_fn(self.cfg, W),
                                       (tokens, n_tok), poison)
        self._emit(so, toks, nkeys, ok, out)
        out.n_valid_tokens += int(n_tok.sum())
        out.n_batch_tokens += self.B * W

    def _packed_step(self, so: SchedulerOutput,
                     last_tokens: Optional[np.ndarray],
                     out: StepOutput,
                     poison: Optional[np.ndarray] = None) -> None:
        """ONE fused packed call: every valid token of the step — decode
        slots and prompt chunks alike — rides in a single dense (T,) stream
        (T = pow-2 bucket), so no slot drags padded columns through the
        model. See ``models.transformer.serve_step_packed``."""
        from repro.serving.scheduler import pack_step
        with span("engine.pack"):
            for c in so.chunks:
                if c.start == 0:        # new request: seed sampling state
                    self._set_sampling(c.slot, c.req.sampling,
                                       c.req.resume_key)
            ps = pack_step(so, last_tokens, self._host_pos, self.B,
                           self.window or 1)
            packed = (ps.tokens, ps.slot_ids, ps.positions,
                      np.asarray(ps.new_pos, np.int32),
                      np.asarray(ps.emit_idx, np.int32))
        self.step_shapes.add(("packed", ps.n_batch))
        if self.paged:
            fn = _paged_step_fn(self.cfg, ps.n_batch)
            args = (self.pager.page_table,) + packed
        elif self.variants:
            fn = _mm_packed_step_fn(self.cfg, ps.n_batch)
            args = packed + (self.model_ids,)
        else:
            fn = _packed_step_fn(self.cfg, ps.n_batch)
            args = packed
        toks, nkeys, ok = self._launch(fn, args, poison)
        self._host_pos[:] = ps.new_pos
        self._emit(so, toks, nkeys, ok, out)
        out.n_valid_tokens += ps.n_valid
        out.n_batch_tokens += ps.n_batch

    def _paged_window_step(self, so: SchedulerOutput,
                           last_tokens: Optional[np.ndarray],
                           out: StepOutput,
                           poison: Optional[np.ndarray] = None) -> None:
        """Paged counterpart of ``_window_step``: the same (B, W) ragged
        window, flattened inside the jit onto the paged packed trunk
        (``serve_step_window_paged``) — one call, two steady-state shapes
        (W = chunk_size, W = 1), K/V written straight into granted pages."""
        W = self.window or max(c.length for c in so.chunks)
        with span("engine.pack"):
            tokens, n_tok, fresh = self._window_arrays(so, last_tokens, W)
            self._host_pos[fresh] = 0
        self.step_shapes.add(("window", W))
        toks, nkeys, ok = self._launch(
            _paged_window_step_fn(self.cfg, W),
            (self.pager.page_table, tokens, n_tok), poison)
        self._host_pos[:] = self._host_pos + n_tok
        self._emit(so, toks, nkeys, ok, out)
        out.n_valid_tokens += int(n_tok.sum())
        out.n_batch_tokens += self.B * W

    def _mm_window_step(self, so: SchedulerOutput,
                        last_tokens: Optional[np.ndarray],
                        out: StepOutput,
                        poison: Optional[np.ndarray] = None) -> None:
        """Multi-model counterpart of ``_window_step``: the same (B, W)
        ragged window, flattened inside the jit onto the packed multi trunk
        (``serve_step_window_multi``) with each slot's tokens routed to its
        stacked alpha variant by ``model_ids``. Pure-decode steps ride the
        W = 1 shape, booked as ``("decode", 1)`` so compile accounting
        matches the single-model window engine (two steady-state shapes)."""
        W = ((self.window or max(c.length for c in so.chunks))
             if so.chunks else 1)
        with span("engine.pack"):
            tokens, n_tok, fresh = self._window_arrays(so, last_tokens, W)
            self._host_pos[fresh] = 0
        self.step_shapes.add(("window", W) if so.chunks else ("decode", 1))
        toks, nkeys, ok = self._launch(_mm_window_step_fn(self.cfg, W),
                                       (tokens, n_tok, self.model_ids),
                                       poison)
        self._host_pos[:] = self._host_pos + n_tok
        self._emit(so, toks, nkeys, ok, out)
        out.n_valid_tokens += int(n_tok.sum())
        out.n_batch_tokens += self.B * W
