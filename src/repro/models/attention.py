"""GQA attention with KV cache, RoPE, causal/bidir/cross modes.

Decode attends over the full cache buffer with a position mask; with
``flash_decode_seq_shard`` the cache is sharded over the *sequence* dim on the
'model' mesh axis so the memory-bound KV read is split across chips (the SP /
flash-decoding analogue of the paper's "parallelise the dominant memory term").
GSPMD inserts the partial-softmax all-reduces automatically.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def attn_init(key: jax.Array, cfg: ModelConfig, *, cross: bool = False,
              prefix: str = "attn") -> dict:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "q": L.linear_init(ks[0], cfg, f"{prefix}_q", d, H * hd, bias=cfg.qkv_bias),
        "k": L.linear_init(ks[1], cfg, f"{prefix}_k", d, Hkv * hd, bias=cfg.qkv_bias),
        "v": L.linear_init(ks[2], cfg, f"{prefix}_v", d, Hkv * hd, bias=cfg.qkv_bias),
        "o": L.linear_init(ks[3], cfg, f"{prefix}_o", H * hd, d, bias=False),
    }


def _split_heads(x: jnp.ndarray, n: int, hd: int) -> jnp.ndarray:
    B, S, _ = x.shape
    return x.reshape(B, S, n, hd)


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
         mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Grouped scaled-dot-product attention. q:(B,S,H,hd) k/v:(B,T,Hkv,hd).

    K/V stay in their storage dtype (bf16) with f32 MXU accumulation
    (preferred_element_type) — casting the cache to f32 would make XLA
    materialise an f32 copy of the whole KV buffer every layer, tripling
    decode HBM traffic (measured in EXPERIMENTS.md §Perf iteration 1).
    """
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    logits = jnp.einsum("bsngd,btnd->bnsgt", qs.reshape(B, S, Hkv, G, hd), k,
                        preferred_element_type=jnp.float32)
    if mask is not None:
        # mask: (B, S, T) or (S, T); True = attend
        m = mask[:, None, :, None, :] if mask.ndim == 3 else mask[None, None, :, None, :]
        logits = jnp.where(m, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnsgt,btnd->bsngd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, hd).astype(q.dtype)


@jax.named_scope("attention")
def attn_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray, *,
               positions: jnp.ndarray,
               mode: str = "causal",                 # causal | bidir | cross
               kv_src: Optional[jnp.ndarray] = None, # cross-attn source
               cache: Optional[dict] = None,         # {"k","v"} buffers (B,T,Hkv,hd)
               cache_pos: Optional[jnp.ndarray] = None,
               ) -> tuple[jnp.ndarray, Optional[dict]]:
    """Returns (output, updated_cache)."""
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    B, S, _ = x.shape
    q = _split_heads(L.linear_apply(p["q"], x, cfg, "attn_q"), H, hd)
    src = kv_src if kv_src is not None else x
    k = _split_heads(L.linear_apply(p["k"], src, cfg, "attn_k"), Hkv, hd)
    v = _split_heads(L.linear_apply(p["v"], src, cfg, "attn_v"), Hkv, hd)

    if mode != "cross":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and mode != "cross":
        # scatter the S new steps at cache_pos, then attend over the buffer
        T = cache["k"].shape[1]
        kd = cache["k"].dtype
        idx = (cache_pos + jnp.arange(S))                       # (S,)
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], _quant_like(k, kd), cache_pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], _quant_like(v, kd), cache_pos, axis=1)
        new_cache = {"k": ck, "v": cv}
        t = jnp.arange(T)
        # position t valid if t <= query_position (causal over filled region)
        mask = t[None, :] <= idx[:, None]                       # (S, T)
        out = sdpa(q, _dequant(ck, q.dtype), _dequant(cv, q.dtype), mask)
    elif cache is not None and mode == "cross":
        out = sdpa(q, _dequant(cache["k"], q.dtype),
                   _dequant(cache["v"], q.dtype), None)
        new_cache = cache
    else:
        if mode == "causal":
            t = jnp.arange(S)
            mask = t[None, :] <= t[:, None]
        else:
            mask = None
        out = sdpa(q, k, v, mask)

    y = L.linear_apply(p["o"], out.reshape(B, S, H * hd), cfg, "attn_o")
    return y, new_cache


@jax.named_scope("attention")
def attn_apply_packed(p: dict, cfg: ModelConfig, x: jnp.ndarray, *,
                      positions: jnp.ndarray, slot_ids: jnp.ndarray,
                      cache: dict,
                      mids: Optional[jnp.ndarray] = None
                      ) -> tuple[jnp.ndarray, dict]:
    """Packed-query attention over a stacked per-slot KV cache.

    ``x`` is (1, T, d): T tokens from *different* sequences flattened into one
    dense stream (the serving engine's token-packed step). ``slot_ids`` /
    ``positions`` are (T,): each token's cache row and its position inside
    that row. ``cache["k"]/["v"]`` are (B, Tbuf, Hkv, hd) stacked slot
    buffers. Padding tokens carry ``slot_id == B``: their scatter rows are
    out of bounds and dropped (``mode="drop"``), and their gather index is
    clipped back into range — they read slot ``B - 1``'s buffer (compute
    wasted, result discarded by the caller).

    Scatter-then-attend makes intra-step causality fall out of the position
    mask: every new K/V lands at its true (slot, pos) first, then token t
    attends its own slot's buffer at positions ``<= positions[t]`` — earlier
    same-step tokens of the same slot are visible (p' < p), later ones and
    stale rows from a previous occupant (p' > p) are masked. Duplicate
    (slot, pos) pairs never occur among valid tokens: the scheduler packs
    each slot's tokens at consecutive, unique positions.

    ``mids`` (T,) selects each token's model variant when the OVSF alpha
    banks are stacked (multi-model gateway batching); None = single model.
    """
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    T = x.shape[1]
    B, Tbuf = cache["k"].shape[0], cache["k"].shape[1]
    m2 = None if mids is None else mids[None, :]            # (1, T)
    q = _split_heads(L.linear_apply(p["q"], x, cfg, "attn_q", mids=m2), H, hd)
    k = _split_heads(L.linear_apply(p["k"], x, cfg, "attn_k", mids=m2), Hkv,
                     hd)
    v = _split_heads(L.linear_apply(p["v"], x, cfg, "attn_v", mids=m2), Hkv,
                     hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)

    kd = cache["k"].dtype
    ck = cache["k"].at[slot_ids, positions].set(_quant_like(k[0], kd),
                                                mode="drop")
    cv = cache["v"].at[slot_ids, positions].set(_quant_like(v[0], kd),
                                                mode="drop")
    sid = jnp.clip(slot_ids, 0, B - 1)
    kt = jnp.take(ck, sid, axis=0)          # (T, Tbuf, Hkv, hd)
    vt = jnp.take(cv, sid, axis=0)
    t = jnp.arange(Tbuf)
    mask = t[None, None, :] <= positions[:, None, None]     # (T, 1, Tbuf)
    out = sdpa(q[0][:, None], _dequant(kt, q.dtype),
               _dequant(vt, q.dtype), mask)                 # (T, 1, H, hd)
    y = L.linear_apply(p["o"], out.reshape(1, T, H * hd), cfg, "attn_o",
                       mids=m2)
    return y, {"k": ck, "v": cv}


@jax.named_scope("attention")
def attn_apply_paged(p: dict, cfg: ModelConfig, x: jnp.ndarray, *,
                     positions: jnp.ndarray, slot_ids: jnp.ndarray,
                     page_table: jnp.ndarray,
                     cache: dict) -> tuple[jnp.ndarray, dict]:
    """Packed-query attention over *paged* K/V pools (serving/kvcache.py).

    Same contract as ``attn_apply_packed`` except the cache is a shared
    page pool instead of per-slot worst-case buffers: ``cache["k"]/["v"]``
    are (P, page_size, Hkv, hd) and ``page_table`` is (n_slots + 1,
    max_pages) int32 mapping (slot, page-index) -> physical page. Position
    ``pos`` of a slot lives at ``(page_table[slot, pos // ps], pos % ps)``,
    so a slot's pages in list order ARE its contiguous buffer virtually —
    with ``max_pages * ps == Tbuf`` the gathered view, the position mask
    and therefore the outputs are bit-identical to the contiguous path.

    Sentinel entries (ungranted pages, and the whole padding row
    ``n_slots``) carry P: scatters through them go out of bounds and drop
    (``mode="drop"``), gathers clamp to page P-1 — reachable only at
    virtual positions the ``<= positions[t]`` mask already excludes (the
    engine grants pages covering every position written this step before
    calling in). The segment-aware Pallas form of this gather-free walk is
    ``kernels.decode_attn.paged_flash_decode``; this jnp path is the
    oracle-equivalent used on hosts without a TPU lowering.
    """
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    T = x.shape[1]
    P, ps = cache["k"].shape[0], cache["k"].shape[1]
    n_slots = page_table.shape[0] - 1
    npg = page_table.shape[1]
    q = _split_heads(L.linear_apply(p["q"], x, cfg, "attn_q"), H, hd)
    k = _split_heads(L.linear_apply(p["k"], x, cfg, "attn_k"), Hkv, hd)
    v = _split_heads(L.linear_apply(p["v"], x, cfg, "attn_v"), Hkv, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)

    kd = cache["k"].dtype
    page_of = page_table[jnp.clip(slot_ids, 0, n_slots), positions // ps]
    off = positions % ps
    ck = cache["k"].at[page_of, off].set(_quant_like(k[0], kd), mode="drop")
    cv = cache["v"].at[page_of, off].set(_quant_like(v[0], kd), mode="drop")

    sid = jnp.clip(slot_ids, 0, n_slots - 1)
    pages = jnp.clip(page_table[sid], 0, P - 1)              # (T, npg)
    kt = ck[pages].reshape(T, npg * ps, Hkv, hd)
    vt = cv[pages].reshape(T, npg * ps, Hkv, hd)
    t = jnp.arange(npg * ps)
    mask = t[None, None, :] <= positions[:, None, None]      # (T, 1, npg*ps)
    out = sdpa(q[0][:, None], _dequant(kt, q.dtype),
               _dequant(vt, q.dtype), mask)                  # (T, 1, H, hd)
    y = L.linear_apply(p["o"], out.reshape(1, T, H * hd), cfg, "attn_o")
    return y, {"k": ck, "v": cv}


@jax.named_scope("attention")
def cross_attn_packed(p: dict, cfg: ModelConfig, x: jnp.ndarray, *,
                      slot_ids: jnp.ndarray, cache: dict,
                      mids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Packed-query cross attention: each token attends its slot's
    precomputed encoder K/V ((B, Te, Hkv, hd) stacked buffers), no mask."""
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    T = x.shape[1]
    B = cache["k"].shape[0]
    m2 = None if mids is None else mids[None, :]
    q = _split_heads(L.linear_apply(p["q"], x, cfg, "attn_q", mids=m2), H, hd)
    sid = jnp.clip(slot_ids, 0, B - 1)
    kt = jnp.take(cache["k"], sid, axis=0)
    vt = jnp.take(cache["v"], sid, axis=0)
    out = sdpa(q[0][:, None], _dequant(kt, q.dtype),
               _dequant(vt, q.dtype), None)
    return L.linear_apply(p["o"], out.reshape(1, T, H * hd), cfg, "attn_o",
                          mids=m2)


def make_cross_cache(p: dict, cfg: ModelConfig, src: jnp.ndarray) -> dict:
    """Precompute encoder K/V for cross attention (prefill of enc-dec)."""
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    k = _split_heads(L.linear_apply(p["k"], src, cfg, "attn_k"), Hkv, hd)
    v = _split_heads(L.linear_apply(p["v"], src, cfg, "attn_v"), Hkv, hd)
    return {"k": k, "v": v}


# --- int8 KV quantisation (beyond-paper memory opt; symmetric per-head) -----

_KV_SCALE = 127.0 / 8.0   # static scale; attention values are O(1) post-norm


def _quant_like(x: jnp.ndarray, dtype) -> jnp.ndarray:
    if dtype == jnp.int8:
        return jnp.clip(jnp.round(x.astype(jnp.float32) * _KV_SCALE),
                        -127, 127).astype(jnp.int8)
    return x.astype(dtype)


def _dequant(x: jnp.ndarray, dtype) -> jnp.ndarray:
    if x.dtype == jnp.int8:
        return (x.astype(jnp.float32) / _KV_SCALE).astype(dtype)
    return x.astype(dtype)
