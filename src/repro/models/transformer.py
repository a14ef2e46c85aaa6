"""Model assembly for every assigned family.

One parametric stack covers: dense/GQA LMs (qwen*, tinyllama, starcoder2),
MoE LMs (kimi-k2, olmoe), pure-SSM (falcon-mamba), hybrid mamba2+shared-attn
(zamba2), encoder-decoder with stub audio frontend (whisper-tiny), and a
VLM backbone with stub anyres frontend (llava-next).

Layer stacks are ``lax.scan`` over stacked params (small HLO => the 1T-param
kimi config lowers in seconds); blocks are ``jax.checkpoint``-wrapped when
cfg.remat. Decode carries an explicit cache pytree so ``serve_step`` is a
single (1-token) step against a seq_len-deep KV/SSM cache.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _mlp_init(key: jax.Array, cfg: ModelConfig, d: int, f: int) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "up": L.linear_init(ks[1], cfg, "mlp_up", d, f),
        "down": L.linear_init(ks[2], cfg, "mlp_down", f, d),
    }
    if cfg.mlp_gated:
        p["gate"] = L.linear_init(ks[0], cfg, "mlp_gate", d, f)
    return p


def _mlp_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray,
               mids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    u = L.linear_apply(p["up"], x, cfg, "mlp_up", mids=mids)
    if cfg.mlp_gated:
        g = L.linear_apply(p["gate"], x, cfg, "mlp_gate", mids=mids)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    return L.linear_apply(p["down"], h, cfg, "mlp_down", mids=mids)


def block_init(key: jax.Array, cfg: ModelConfig, kind: str, *,
               cross: bool = False) -> dict:
    d = cfg.d_model
    dtype = cfg.act_dtype
    ks = jax.random.split(key, 6)
    if kind == "attn_mlp":
        p = {"norm1": L.rmsnorm_init(d, dtype),
             "attn": A.attn_init(ks[0], cfg),
             "norm2": L.rmsnorm_init(d, dtype),
             "mlp": _mlp_init(ks[1], cfg, d, cfg.d_ff)}
        if cross:
            p["norm_x"] = L.rmsnorm_init(d, dtype)
            p["cross"] = A.attn_init(ks[2], cfg, cross=True, prefix="cross")
        return p
    if kind == "moe":
        return {"norm1": L.rmsnorm_init(d, dtype),
                "attn": A.attn_init(ks[0], cfg),
                "norm2": L.rmsnorm_init(d, dtype),
                "moe": M.moe_init(ks[1], cfg)}
    if kind == "mamba1":
        return {"norm1": L.rmsnorm_init(d, dtype),
                "mamba": S.mamba1_init(ks[0], cfg)}
    if kind == "mamba2":
        return {"norm1": L.rmsnorm_init(d, dtype),
                "mamba": S.mamba2_init(ks[0], cfg)}
    raise ValueError(kind)


def block_apply(p: dict, cfg: ModelConfig, kind: str, x: jnp.ndarray, *,
                positions: jnp.ndarray,
                mode: str = "causal",
                enc_out: Optional[jnp.ndarray] = None,
                cache: Optional[dict] = None,
                cache_pos: Optional[jnp.ndarray] = None,
                ) -> tuple[jnp.ndarray, Optional[dict], jnp.ndarray]:
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.float32(0.0)
    new_cache: Optional[dict] = dict(cache) if cache is not None else None
    if kind in ("attn_mlp", "moe"):
        h = L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        attn_cache = ({"k": cache["k"], "v": cache["v"]}
                      if cache is not None and "k" in cache else None)
        y, upd = A.attn_apply(p["attn"], cfg, h, positions=positions, mode=mode,
                              cache=attn_cache, cache_pos=cache_pos)
        x = x + y
        if upd is not None and new_cache is not None:
            new_cache.update(upd)
        if "cross" in p:
            h = L.rmsnorm_apply(p["norm_x"], x, cfg.norm_eps)
            if cache is not None and "xk" in cache:
                y, _ = A.attn_apply(p["cross"], cfg, h, positions=positions,
                                    mode="cross",
                                    cache={"k": cache["xk"], "v": cache["xv"]})
            else:
                y, _ = A.attn_apply(p["cross"], cfg, h, positions=positions,
                                    mode="cross", kv_src=enc_out)
            x = x + y
        h = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        if kind == "moe":
            y, aux = M.moe_apply(p["moe"], cfg, h)
        else:
            y = _mlp_apply(p["mlp"], cfg, h)
        return x + y, new_cache, aux
    if kind in ("mamba1", "mamba2"):
        h = L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        fn = S.mamba1_apply if kind == "mamba1" else S.mamba2_apply
        mcache = ({"conv": cache["conv"], "ssm": cache["ssm"]}
                  if cache is not None else None)
        y, upd = fn(p["mamba"], cfg, h, cache=mcache)
        if upd is not None and new_cache is not None:
            new_cache.update(upd)
        return x + y, new_cache, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Stacks (scan over stacked layer params)
# ---------------------------------------------------------------------------

def _stacked_init(key: jax.Array, n: int, init_fn) -> dict:
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _scan_stack(params: dict, cfg: ModelConfig, kind: str, x: jnp.ndarray, *,
                positions: jnp.ndarray, mode: str = "causal",
                enc_out: Optional[jnp.ndarray] = None,
                cache: Optional[dict] = None,
                cache_pos: Optional[jnp.ndarray] = None,
                remat: bool = False,
                ) -> tuple[jnp.ndarray, Optional[dict], jnp.ndarray]:
    """Scan a homogeneous stack. params/cache leaves have leading n_layers."""

    def body(carry, scanned):
        xx, aux = carry
        pp, cc = scanned
        xx, new_c, a = block_apply(pp, cfg, kind, xx, positions=positions,
                                   mode=mode, enc_out=enc_out, cache=cc,
                                   cache_pos=cache_pos)
        return (xx, aux + a), new_c

    if remat:
        body = jax.checkpoint(body)
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                       (params, cache))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def _layer_kind(cfg: ModelConfig) -> str:
    return {"dense": "attn_mlp", "vlm": "attn_mlp", "moe": "moe",
            "ssm": "mamba1", "hybrid": "mamba2", "encdec": "attn_mlp"}[cfg.family]


def model_init(key: jax.Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    dtype = cfg.act_dtype
    kind = _layer_kind(cfg)
    p: dict = {
        "embed": L.embed_init(ks[0], cfg.vocab, cfg.d_model, dtype),
        "blocks": _stacked_init(
            ks[1], cfg.n_layers,
            lambda k: block_init(k, cfg, kind, cross=cfg.family == "encdec")),
        "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = {"w": jax.random.normal(
            ks[2], (cfg.d_model, cfg.vocab), dtype) * 0.02}
    if cfg.family == "hybrid":
        p["shared_attn"] = block_init(ks[3], cfg, "attn_mlp")
    if cfg.family == "encdec":
        p["encoder"] = {
            "blocks": _stacked_init(
                ks[4], cfg.encoder_layers,
                lambda k: block_init(k, cfg, "attn_mlp")),
            "norm": L.rmsnorm_init(cfg.d_model, dtype),
        }
    return p


def _hybrid_groups(cfg: ModelConfig) -> list[tuple[int, int, bool]]:
    """[(start, end, attn_after)] runs of mamba2 blocks (zamba2 pattern)."""
    k = cfg.attn_every
    out = []
    i = 0
    while i < cfg.n_layers:
        j = min(i + k, cfg.n_layers)
        out.append((i, j, j - i == k))
        i = j
    return out


def _trunk(params: dict, cfg: ModelConfig, x: jnp.ndarray, *,
           positions: jnp.ndarray, enc_out: Optional[jnp.ndarray],
           cache: Optional[dict], cache_pos, remat: bool
           ) -> tuple[jnp.ndarray, Optional[dict], jnp.ndarray]:
    kind = _layer_kind(cfg)
    if cfg.family != "hybrid":
        return _scan_stack(params["blocks"], cfg, kind, x, positions=positions,
                           mode="causal", enc_out=enc_out, cache=cache,
                           cache_pos=cache_pos, remat=remat)
    # zamba2: runs of mamba2 blocks with a weight-shared attn block between
    aux_total = jnp.float32(0.0)
    new_cache: Optional[dict] = dict(cache) if cache is not None else None
    app = 0
    for (i, j, attn_after) in _hybrid_groups(cfg):
        sl = lambda a: a[i:j]
        sub_cache = None
        if cache is not None:
            sub_cache = {"conv": cache["conv"][i:j], "ssm": cache["ssm"][i:j]}
        x, upd, aux = _scan_stack(
            jax.tree_util.tree_map(sl, params["blocks"]), cfg, "mamba2", x,
            positions=positions, cache=sub_cache, cache_pos=cache_pos,
            remat=remat)
        aux_total = aux_total + aux
        if new_cache is not None and upd is not None:
            new_cache["conv"] = new_cache["conv"].at[i:j].set(upd["conv"])
            new_cache["ssm"] = new_cache["ssm"].at[i:j].set(upd["ssm"])
        if attn_after:
            acache = None
            if cache is not None:
                acache = {"k": cache["k"][app], "v": cache["v"][app]}
            x, upd, aux = block_apply(params["shared_attn"], cfg, "attn_mlp",
                                      x, positions=positions, cache=acache,
                                      cache_pos=cache_pos)
            aux_total = aux_total + aux
            if new_cache is not None and upd is not None:
                new_cache["k"] = new_cache["k"].at[app].set(upd["k"])
                new_cache["v"] = new_cache["v"].at[app].set(upd["v"])
            app += 1
    return x, new_cache, aux_total


def _embed_inputs(params: dict, cfg: ModelConfig, batch: dict) -> jnp.ndarray:
    x = L.embed_apply(params["embed"], batch["tokens"])
    if cfg.family == "vlm" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(x.dtype)
        n_img = img.shape[1]
        x = jnp.concatenate([img, x[:, n_img:]], axis=1)
    return x


def _encode(params: dict, cfg: ModelConfig, frames: jnp.ndarray,
            remat: bool) -> jnp.ndarray:
    enc_pos = jnp.arange(frames.shape[1])
    h, _, _ = _scan_stack(params["encoder"]["blocks"], cfg, "attn_mlp",
                          frames.astype(cfg.act_dtype), positions=enc_pos,
                          mode="bidir", remat=remat)
    return L.rmsnorm_apply(params["encoder"]["norm"], h, cfg.norm_eps)


@jax.named_scope("unembed")
def _unembed(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        return x @ params["embed"]["table"].T.astype(x.dtype)
    return x @ params["lm_head"]["w"].astype(x.dtype)


def model_apply(params: dict, cfg: ModelConfig, batch: dict, *,
                cache: Optional[dict] = None, train: bool = False,
                return_features: bool = False
                ) -> tuple[jnp.ndarray, Optional[dict], jnp.ndarray]:
    """Forward pass. Returns (logits-or-features, new_cache, aux_loss).

    batch: {"tokens": (B,S)} [+ "frames" (encdec) | "image_embeds" (vlm)].
    With a cache, tokens are appended at cache["pos"]. ``return_features``
    skips the unembed so losses can chunk it (full (B,S,V) logits would
    dominate activation memory at 160k-vocab scale).
    """
    tokens = batch["tokens"]
    B, Snew = tokens.shape
    x = _embed_inputs(params, cfg, batch)

    enc_out = None
    if cfg.family == "encdec" and "frames" in batch:
        enc_out = _encode(params, cfg, batch["frames"], cfg.remat and train)

    if cache is not None:
        pos0 = cache["pos"]
        positions = pos0 + jnp.arange(Snew)
        layer_cache = {k: v for k, v in cache.items() if k != "pos"}
    else:
        pos0 = None
        positions = jnp.arange(Snew)
        layer_cache = None

    x, new_layer_cache, aux = _trunk(
        params, cfg, x, positions=positions, enc_out=enc_out,
        cache=layer_cache, cache_pos=pos0, remat=cfg.remat and train)

    out = x if return_features else _unembed(params, cfg, x)
    new_cache = None
    if cache is not None:
        new_cache = dict(new_layer_cache or {})
        new_cache["pos"] = cache["pos"] + Snew
    return out, new_cache, aux


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, B: int, T: int) -> dict[str, Any]:
    """ShapeDtypeStruct pytree for the serving cache (buffer length T)."""
    sd = jax.ShapeDtypeStruct
    kv_dtype = jnp.dtype(cfg.kv_cache_dtype or cfg.dtype)
    Hkv, hd, nl = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    spec: dict[str, Any] = {"pos": sd((), jnp.int32)}
    if cfg.family in ("dense", "vlm", "moe", "encdec"):
        spec["k"] = sd((nl, B, T, Hkv, hd), kv_dtype)
        spec["v"] = sd((nl, B, T, Hkv, hd), kv_dtype)
    if cfg.family == "encdec":
        Te = cfg.encoder_seq
        spec["xk"] = sd((nl, B, Te, Hkv, hd), jnp.dtype(cfg.dtype))
        spec["xv"] = sd((nl, B, Te, Hkv, hd), jnp.dtype(cfg.dtype))
    if cfg.family == "ssm":
        m = S.mamba1_cache_spec(cfg, B)
        spec["conv"] = sd((nl,) + m["conv"].shape, m["conv"].dtype)
        spec["ssm"] = sd((nl,) + m["ssm"].shape, m["ssm"].dtype)
    if cfg.family == "hybrid":
        m = S.mamba2_cache_spec(cfg, B)
        spec["conv"] = sd((nl,) + m["conv"].shape, m["conv"].dtype)
        spec["ssm"] = sd((nl,) + m["ssm"].shape, m["ssm"].dtype)
        n_apps = sum(1 for *_r, a in _hybrid_groups(cfg) if a)
        spec["k"] = sd((n_apps, B, T, Hkv, hd), kv_dtype)
        spec["v"] = sd((n_apps, B, T, Hkv, hd), kv_dtype)
    return spec


def init_cache(cfg: ModelConfig, B: int, T: int) -> dict[str, Any]:
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  cache_spec(cfg, B, T))


def paged_cache_spec(cfg: ModelConfig, B: int, page_size: int,
                     n_pages: int) -> dict[str, Any]:
    """ShapeDtypeStruct pytree for the *paged* serving cache.

    K/V are shared ``(nl, n_pages, page_size, Hkv, hd)`` pools instead of
    per-slot ``(nl, B, T, ...)`` buffers — slots map into them through the
    ``serving.kvcache.PagedKVCache`` page table, so device memory scales
    with *live tokens* (rounded to pages), not ``slots x worst case``.
    Cross-attention K/V (encdec) stay per-slot dense: they are prompt-sized
    constants, not a growing decode cache. KV-cache families only.
    """
    sd = jax.ShapeDtypeStruct
    if cfg.family not in _PACKED_FAMILIES:
        raise NotImplementedError(
            f"paged cache requires a KV-cache family, got {cfg.family!r}")
    kv_dtype = jnp.dtype(cfg.kv_cache_dtype or cfg.dtype)
    Hkv, hd, nl = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    spec: dict[str, Any] = {
        "k": sd((nl, n_pages, page_size, Hkv, hd), kv_dtype),
        "v": sd((nl, n_pages, page_size, Hkv, hd), kv_dtype),
    }
    if cfg.family == "encdec":
        Te = cfg.encoder_seq
        spec["xk"] = sd((nl, B, Te, Hkv, hd), jnp.dtype(cfg.dtype))
        spec["xv"] = sd((nl, B, Te, Hkv, hd), jnp.dtype(cfg.dtype))
    return spec


def init_paged_cache(cfg: ModelConfig, B: int, page_size: int,
                     n_pages: int) -> dict[str, Any]:
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  paged_cache_spec(cfg, B, page_size, n_pages))


# ---------------------------------------------------------------------------
# Losses & serving entry points
# ---------------------------------------------------------------------------

LOSS_CHUNK = 1024   # sequence positions per unembed+CE chunk


def lm_loss(params: dict, cfg: ModelConfig, batch: dict
            ) -> tuple[jnp.ndarray, dict]:
    """Next-token CE (+ MoE aux), with the unembed chunked over the sequence
    so full (B, S, vocab) logits never materialise. VLM image positions are
    masked out of the loss."""
    feats, _, aux = model_apply(params, cfg, batch, train=True,
                                return_features=True)
    tokens = batch["tokens"]
    B, Sm1 = tokens.shape[0], tokens.shape[1] - 1
    tgt = tokens[:, 1:]
    xs = feats[:, :-1]
    mask = jnp.ones((B, Sm1), jnp.float32)
    if cfg.family == "vlm" and "image_embeds" in batch:
        n_img = batch["image_embeds"].shape[1]
        mask = mask.at[:, : max(n_img - 1, 0)].set(0.0)

    c = min(LOSS_CHUNK, Sm1)
    pad = (-Sm1) % c
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nchunks = xs.shape[1] // c

    def chunk_ce(carry, ins):
        xc, tc, mc = ins                      # (B,c,d), (B,c), (B,c)
        lg = _unembed(params, cfg, xc).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mc
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(mc)), None

    swap = lambda a: jnp.moveaxis(a.reshape(B, nchunks, c, *a.shape[2:]), 1, 0)
    (tot, cnt), _ = jax.lax.scan(
        chunk_ce, (jnp.float32(0.0), jnp.float32(0.0)),
        (swap(xs), swap(tgt), swap(mask)))
    loss = tot / jnp.maximum(cnt, 1.0)
    total = loss + cfg.router_aux_weight * aux
    return total, {"loss": loss, "aux": aux}


def serve_prefill(params: dict, cfg: ModelConfig, batch: dict, buffer_len: int
                  ) -> tuple[jnp.ndarray, dict]:
    """Run the prompt through the model, filling a fresh cache."""
    B, Sp = batch["tokens"].shape
    cache = init_cache(cfg, B, buffer_len)
    if cfg.family == "encdec" and "frames" in batch:
        enc_out = _encode(params, cfg, batch["frames"], False)
        xk, xv = [], []
        # Precompute per-layer cross K/V once (cheap: encoder_seq is small)
        blocks = params["blocks"]
        for i in range(cfg.n_layers):
            pl = jax.tree_util.tree_map(lambda a: a[i], blocks)
            cc = A.make_cross_cache(pl["cross"], cfg, enc_out)
            xk.append(cc["k"])
            xv.append(cc["v"])
        cache["xk"] = jnp.stack(xk)
        cache["xv"] = jnp.stack(xv)
        batch = dict(batch)
        del batch["frames"]
    logits, cache, _ = model_apply(params, cfg, batch, cache=cache)
    return logits[:, -1], cache


def serve_prefill_ragged(params: dict, cfg: ModelConfig, batch: dict,
                         buffer_len: int, lengths: jnp.ndarray
                         ) -> tuple[jnp.ndarray, dict]:
    """Batched prefill of right-padded prompts with per-row true lengths.

    ``batch["tokens"]`` is (B, Lb) with row b's real prompt in positions
    [0, lengths[b]) and arbitrary padding after. Causal attention means a
    row's logits at position ``lengths[b]-1`` are independent of its padding,
    so the returned (B, vocab) logits match an unpadded per-row prefill
    exactly for KV-cache families. The cache holds K/V for all Lb positions
    (padding K/V included); the serving engine re-bases each row's ``pos`` to
    its true length, after which decode overwrites each padded position
    before ever attending to it (the decode mask is position-bounded).

    Not state-safe for SSM/hybrid families: their recurrent state would run
    through the padding. Callers gate on family and fall back to exact
    per-request prefill there.
    """
    B, Lb = batch["tokens"].shape
    cache = init_cache(cfg, B, buffer_len)
    logits, cache, _ = model_apply(params, cfg, batch, cache=cache)
    idx = jnp.clip(lengths - 1, 0, Lb - 1)
    last = jnp.take_along_axis(
        logits, idx[:, None, None], axis=1)[:, 0]
    return last, cache


def serve_step(params: dict, cfg: ModelConfig, cache: dict,
               tokens: jnp.ndarray) -> tuple[jnp.ndarray, dict]:
    """One decode step: tokens (B, 1) -> (logits (B, vocab), new cache)."""
    logits, cache, _ = model_apply(params, cfg, {"tokens": tokens}, cache=cache)
    return logits[:, -1], cache


def serve_step_window(params: dict, cfg: ModelConfig, cache: dict,
                      tokens: jnp.ndarray, n_valid: jnp.ndarray
                      ) -> tuple[jnp.ndarray, dict]:
    """Ragged decode-shaped window: advance the cache by ``n_valid`` of the
    ``W`` supplied tokens (chunked prefill + decode interleaving).

    ``tokens`` is (B, W) with the real tokens in columns [0, n_valid) and
    arbitrary padding after; ``n_valid`` is a scalar (callers vmap over slots,
    so each slot carries its own count: 1 for a decode slot, up to W for a
    prompt chunk, 0 for an idle slot). Returns the (B, vocab) logits at column
    ``n_valid - 1`` — the next-token logits after the last real token — and
    the cache with ``pos`` advanced by exactly ``n_valid``.

    Exactness mirrors ``serve_prefill_ragged``: causal attention makes the
    returned logits independent of the padding columns, and the padded K/V
    written at positions [pos + n_valid, pos + W) sit beyond every reachable
    query position until the true tokens at those positions overwrite them
    (the decode mask is position-bounded, ``t <= query_pos``). Callers must
    size the cache buffer so ``pos + W`` never exceeds it — the serving core
    over-allocates by the window width so the scatter never clamps at the
    buffer edge. Not state-safe for SSM/hybrid families (recurrent state
    would run through the padding); callers gate on family.
    """
    W = tokens.shape[1]
    logits, new_cache, _ = model_apply(params, cfg, {"tokens": tokens},
                                       cache=cache)
    # model_apply advanced pos by W; re-base to the true token count.
    new_cache["pos"] = cache["pos"] + n_valid
    idx = jnp.clip(n_valid - 1, 0, W - 1)
    last = jnp.take_along_axis(
        logits, jnp.broadcast_to(idx, (logits.shape[0],))[:, None, None],
        axis=1)[:, 0]
    return last, new_cache


_PACKED_FAMILIES = ("dense", "vlm", "moe", "encdec")


def _packed_block(p: dict, cfg: ModelConfig, kind: str, x: jnp.ndarray, *,
                  slot_ids: jnp.ndarray, positions: jnp.ndarray, cache: dict,
                  mids: Optional[jnp.ndarray] = None
                  ) -> tuple[jnp.ndarray, dict, jnp.ndarray]:
    """One block over a packed token stream (x: (1, T, d)); mirrors
    ``block_apply`` for the KV-cache kinds with the packed attention path.
    ``mids`` (T,) selects each token's stacked-alpha variant (multi-model)."""
    aux = jnp.float32(0.0)
    new_cache = dict(cache)
    h = L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    y, upd = A.attn_apply_packed(p["attn"], cfg, h, positions=positions,
                                 slot_ids=slot_ids,
                                 cache={"k": cache["k"], "v": cache["v"]},
                                 mids=mids)
    x = x + y
    new_cache.update(upd)
    if "cross" in p:
        h = L.rmsnorm_apply(p["norm_x"], x, cfg.norm_eps)
        y = A.cross_attn_packed(p["cross"], cfg, h, slot_ids=slot_ids,
                                cache={"k": cache["xk"], "v": cache["xv"]},
                                mids=mids)
        x = x + y
    h = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    if kind == "moe":
        y, aux = M.moe_apply(p["moe"], cfg, h)
    else:
        # mids is (T,); MLP activations are (1, T, d) — match x.shape[:-1]
        y = _mlp_apply(p["mlp"], cfg, h,
                       mids=None if mids is None else mids[None, :])
    return x + y, new_cache, aux


def serve_step_packed(params: dict, cfg: ModelConfig, cache: dict,
                      tokens: jnp.ndarray, slot_ids: jnp.ndarray,
                      positions: jnp.ndarray, new_pos: jnp.ndarray,
                      emit_idx: jnp.ndarray, *,
                      model_ids: Optional[jnp.ndarray] = None
                      ) -> tuple[jnp.ndarray, dict]:
    """Token-packed ragged step: ONE dense pass over every valid token of a
    serving iteration, with zero padded-row model FLOPs.

    Where ``serve_step_window`` pads each slot's work to a (B, W) batch (a
    decode slot drags W-1 dead columns through every layer whenever a chunk
    is in flight), this entry point takes the scheduler's flattened layout:

    tokens / slot_ids / positions : (T,)
        all valid tokens of the step — decode slots contribute 1 token at
        their fill position, chunk tasks up to chunk_size prompt tokens at
        positions [start, start+length). T is the pow-2 *bucket*, so the
        tail is padding: those tokens carry ``slot_id == B`` (scatter
        dropped, output discarded).
    new_pos : (B,)
        each slot's post-step fill level (host-computed; fresh slots re-base
        to their consumed length, idle slots keep their old value).
    emit_idx : (B,)
        packed index of slot b's LAST valid token (0 for slots that emit
        nothing this step — their logits row is computed but meaningless).

    Returns ((B, vocab) next-token logits gathered at ``emit_idx`` BEFORE
    the unembed — only B rows pay the vocab matmul, vs B*W on the window
    path — and the cache with per-slot ``pos`` set to ``new_pos``).

    Exactness: K/V are scattered at their true (slot, position) first, then
    each token attends its own slot's buffer under the position-bounded mask
    (``p <= positions[t]``) — see ``attention.attn_apply_packed``. Per-slot
    writes never clamp (scatter, not dynamic_update_slice), so no window
    over-allocation is needed. Not state-safe for SSM/hybrid families.

    ``model_ids`` (B,) maps each slot to a stacked-alpha variant (see
    ``serve_step_packed_multi``); None = single model.
    """
    if cfg.family not in _PACKED_FAMILIES:
        raise NotImplementedError(
            f"packed step requires a KV-cache family, got {cfg.family!r}")
    kind = _layer_kind(cfg)
    mids = None
    if model_ids is not None:
        # padding tokens (slot_id == B) clip to slot B-1: their variant pick
        # is arbitrary — output discarded, scatter already dropped
        B = model_ids.shape[0]
        mids = jnp.take(model_ids, jnp.clip(slot_ids, 0, B - 1))
    x = L.embed_apply(params["embed"], tokens[None])     # (1, T, d)
    layer_cache = {k: v for k, v in cache.items() if k != "pos"}

    def body(carry, scanned):
        xx, aux = carry
        pp, cc = scanned
        xx, new_c, a = _packed_block(pp, cfg, kind, xx, slot_ids=slot_ids,
                                     positions=positions, cache=cc,
                                     mids=mids)
        return (xx, aux + a), new_c

    (x, _aux), new_layer_cache = jax.lax.scan(
        body, (x, jnp.float32(0.0)), (params["blocks"], layer_cache))
    feats = jnp.take(x[0], emit_idx, axis=0)             # (B, d)
    logits = _unembed(params, cfg, feats[None])[0]       # (B, vocab)
    new_cache = dict(new_layer_cache)
    new_cache["pos"] = new_pos
    return logits, new_cache


def _paged_block(p: dict, cfg: ModelConfig, kind: str, x: jnp.ndarray, *,
                 slot_ids: jnp.ndarray, positions: jnp.ndarray,
                 page_table: jnp.ndarray, cache: dict
                 ) -> tuple[jnp.ndarray, dict, jnp.ndarray]:
    """``_packed_block`` with the paged attention path: K/V live in this
    layer's (P, ps, Hkv, hd) page pools, addressed through ``page_table``."""
    aux = jnp.float32(0.0)
    new_cache = dict(cache)
    h = L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    y, upd = A.attn_apply_paged(p["attn"], cfg, h, positions=positions,
                                slot_ids=slot_ids, page_table=page_table,
                                cache={"k": cache["k"], "v": cache["v"]})
    x = x + y
    new_cache.update(upd)
    if "cross" in p:
        h = L.rmsnorm_apply(p["norm_x"], x, cfg.norm_eps)
        y = A.cross_attn_packed(p["cross"], cfg, h, slot_ids=slot_ids,
                                cache={"k": cache["xk"], "v": cache["xv"]})
        x = x + y
    h = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    if kind == "moe":
        y, aux = M.moe_apply(p["moe"], cfg, h)
    else:
        y = _mlp_apply(p["mlp"], cfg, h)
    return x + y, new_cache, aux


def serve_step_paged(params: dict, cfg: ModelConfig, cache: dict,
                     page_table: jnp.ndarray, tokens: jnp.ndarray,
                     slot_ids: jnp.ndarray, positions: jnp.ndarray,
                     new_pos: jnp.ndarray, emit_idx: jnp.ndarray
                     ) -> tuple[jnp.ndarray, dict]:
    """``serve_step_packed`` against the paged KV cache.

    Identical packed-token contract (tokens/slot_ids/positions (T,), new_pos/
    emit_idx (B,)) with one extra input: ``page_table`` (n_slots + 1,
    max_pages) int32 from ``serving.kvcache.PagedKVCache`` — the same table
    is shared by every layer (pools are per-layer, the mapping is not).
    K/V scatter straight into granted pages and each token walks its own
    slot's page list under the position-bounded mask, so with pages covering
    the buffer (``max_pages * page_size == buffer_len``) the emitted logits
    are bit-identical to the contiguous packed step. Not state-safe for
    SSM/hybrid families.
    """
    if cfg.family not in _PACKED_FAMILIES:
        raise NotImplementedError(
            f"paged step requires a KV-cache family, got {cfg.family!r}")
    kind = _layer_kind(cfg)
    x = L.embed_apply(params["embed"], tokens[None])     # (1, T, d)
    layer_cache = {k: v for k, v in cache.items() if k != "pos"}

    def body(carry, scanned):
        xx, aux = carry
        pp, cc = scanned
        xx, new_c, a = _paged_block(pp, cfg, kind, xx, slot_ids=slot_ids,
                                    positions=positions,
                                    page_table=page_table, cache=cc)
        return (xx, aux + a), new_c

    (x, _aux), new_layer_cache = jax.lax.scan(
        body, (x, jnp.float32(0.0)), (params["blocks"], layer_cache))
    feats = jnp.take(x[0], emit_idx, axis=0)             # (B, d)
    logits = _unembed(params, cfg, feats[None])[0]       # (B, vocab)
    new_cache = dict(new_layer_cache)
    new_cache["pos"] = new_pos
    return logits, new_cache


def serve_step_window_paged(params: dict, cfg: ModelConfig, cache: dict,
                            page_table: jnp.ndarray, tokens: jnp.ndarray,
                            n_valid: jnp.ndarray
                            ) -> tuple[jnp.ndarray, dict]:
    """``serve_step_window`` semantics on the paged cache: advance slot b by
    ``n_valid[b]`` of its W supplied tokens, returning each slot's logits at
    column ``n_valid[b] - 1``.

    Implemented by flattening the (B, W) window into the packed layout and
    delegating to ``serve_step_paged`` — ONE trunk serves both step styles,
    and because the scatter lands at exact (slot, position) pairs (never a
    clamped dynamic_update_slice), the paged window path needs no window
    over-allocation: the buffer is exactly ``buffer_len``. Padding columns
    (``col >= n_valid[b]``) become sentinel-slot tokens at position 0 —
    scatter-dropped, output discarded. ``cache["pos"]`` must be (B,)
    per-slot fill levels (the paged engine core's convention).
    """
    B, W = tokens.shape
    pos0 = cache["pos"]                                   # (B,)
    col = jnp.arange(W)
    valid = col[None, :] < n_valid[:, None]               # (B, W)
    slot_ids = jnp.where(valid, jnp.arange(B)[:, None], B
                         ).astype(jnp.int32).reshape(-1)
    positions = jnp.where(valid, pos0[:, None] + col[None, :], 0
                          ).astype(jnp.int32).reshape(-1)
    new_pos = pos0 + n_valid
    emit_idx = jnp.arange(B) * W + jnp.clip(n_valid - 1, 0, W - 1)
    return serve_step_paged(params, cfg, cache, page_table,
                            tokens.reshape(-1), slot_ids, positions,
                            new_pos, emit_idx)


# ---------------------------------------------------------------------------
# Multi-model steps: same-architecture variants batched in ONE jit'd call
# ---------------------------------------------------------------------------

def serve_step_packed_multi(params: dict, cfg: ModelConfig, cache: dict,
                            tokens: jnp.ndarray, slot_ids: jnp.ndarray,
                            positions: jnp.ndarray, new_pos: jnp.ndarray,
                            emit_idx: jnp.ndarray, model_ids: jnp.ndarray
                            ) -> tuple[jnp.ndarray, dict]:
    """``serve_step_packed`` over M stacked same-architecture variants.

    ``params`` is one pytree whose OVSF alpha leaves carry a leading (M, ...)
    model axis (every other leaf — embed, norms, idx, dense linears — is
    shared across variants; see ``serving.model_registry.VariantSet``).
    ``model_ids`` (B,) maps each slot to its variant; each packed token
    contracts against its own slot's alpha bank inside the one jit'd call
    (``kernels.ops.ovsf_matmul_multi``), so a step can mix models without
    extra traces — the compile-shape bound is the single-model one.
    """
    if cfg.family == "moe":
        raise NotImplementedError(
            "multi-model batching over MoE expert banks is not supported "
            "yet (per-expert alpha stacking)")
    return serve_step_packed(params, cfg, cache, tokens, slot_ids, positions,
                             new_pos, emit_idx, model_ids=model_ids)


def serve_step_window_multi(params: dict, cfg: ModelConfig, cache: dict,
                            tokens: jnp.ndarray, n_valid: jnp.ndarray,
                            model_ids: jnp.ndarray
                            ) -> tuple[jnp.ndarray, dict]:
    """``serve_step_window`` semantics over stacked variants: advance slot b
    by ``n_valid[b]`` of its W tokens under variant ``model_ids[b]``.

    Flattens the (B, W) window onto the packed multi trunk exactly like
    ``serve_step_window_paged`` flattens onto the paged trunk — padding
    columns become sentinel-slot tokens (scatter-dropped, output discarded).
    ``cache["pos"]`` must be (B,) per-slot fill levels (natural layout).
    """
    B, W = tokens.shape
    pos0 = cache["pos"]                                   # (B,)
    col = jnp.arange(W)
    valid = col[None, :] < n_valid[:, None]               # (B, W)
    slot_ids = jnp.where(valid, jnp.arange(B)[:, None], B
                         ).astype(jnp.int32).reshape(-1)
    positions = jnp.where(valid, pos0[:, None] + col[None, :], 0
                          ).astype(jnp.int32).reshape(-1)
    new_pos = pos0 + n_valid
    emit_idx = jnp.arange(B) * W + jnp.clip(n_valid - 1, 0, W - 1)
    return serve_step_packed_multi(params, cfg, cache, tokens.reshape(-1),
                                   slot_ids, positions, new_pos, emit_idx,
                                   model_ids)
