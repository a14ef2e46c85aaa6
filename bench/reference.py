"""Plain float32 reference of the decoder configurations (dense GQA with a
GELU or SwiGLU MLP, or top-k routed SwiGLU experts), written from the
published descriptions and importing nothing of the program.

Weights are the benchmark's own (``bench/weights.py``): each OVSF matrix is
rebuilt here as W[k, n] = sum_j H[idx[s, j], k mod L0] * alphas[s*nk + j, n]
over the kept codes of k's segment s, with H the Sylvester-Hadamard matrix
built with numpy. The forward pass runs layer by layer, so only one layer's
dense weights exist at a time, and every matmul runs at ``highest``
precision.

``precision="fp8"`` is the control: every weight matrix rounded to
float8_e4m3fn with one scale per tensor, and every matmul input to it with
one scale per row, the rest in float32.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

# what ``bench.run.model_of`` can describe and this reference does not
# compute: key -> the only value it computes (beside these, the router's
# experts are the experts held, and expert and MLP widths are one)
NOT_COMPUTED = {"mla": None, "n_shared_experts": 0, "first_dense": 0,
                "router_scoring": "softmax", "router_bias": False,
                "routed_scaling": 1.0, "rope_scaling": None}


def check_model(m: dict) -> None:
    """Raise ValueError, naming each key, where ``m`` states what this
    reference does not compute; such a configuration names a reference of
    its own (``modules.reference``)."""
    computes = dict(NOT_COMPUTED, router_experts=m["n_experts"],
                    moe_d_ff=m["d_ff"])
    bad = {k: m[k] for k, v in computes.items() if m[k] != v}
    if bad:
        raise ValueError(f"bench/reference.py does not compute {bad}: GQA, "
                         "softmax routing over the experts held, no shared "
                         "expert, no leading dense layer, rope unscaled")


def hadamard(L: int) -> np.ndarray:
    H = np.ones((1, 1), np.float32)
    while H.shape[0] < L:
        H = np.block([[H, H], [H, -H]])
    return H


def dense_weight(alphas, idx, seg: int):
    """(J, d_out) alphas and (n_seg, n_keep) code ids -> (d_in, d_out) f32."""
    ns, nk = idx.shape
    H = jnp.asarray(hadamard(seg))
    codes = H[idx]                                        # (ns, nk, L0)
    al = alphas.astype(jnp.float32).reshape(ns, nk, -1)
    return jnp.einsum("skl,skn->sln", codes, al).reshape(ns * seg, -1)


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(F8).astype(jnp.float32) * s


def mm(a, w, prec: str):
    """a @ w, at float32 or as the fp8 control."""
    if prec == "fp8":
        a = _q8(a, -1)
        w = _q8(w, None)
    return a @ w


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, :, None].astype(jnp.float32) * freqs       # (B, S, hd/2)
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _linear(p, x, m, prec):
    return mm(x, dense_weight(p["alphas"], p["idx"], m["ovsf"]["seg_len"]),
              prec)


def _attention(p, h, pos, m, prec):
    B, S, _ = h.shape
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _linear(p["q"], h, m, prec).reshape(B, S, H, hd)
    k = _linear(p["k"], h, m, prec).reshape(B, S, Hkv, hd)
    v = _linear(p["v"], h, m, prec).reshape(B, S, Hkv, hd)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
    return _linear(p["o"], o.reshape(B, S, H * hd), m, prec)


def _mlp(p, h, m, prec):
    u = _linear(p["up"], h, m, prec)
    if m["mlp"] == "swiglu":
        u = jax.nn.silu(_linear(p["gate"], h, m, prec)) * u
    elif m["mlp"] == "gelu_tanh":
        u = gelu_tanh(u)
    else:
        raise ValueError(f"unknown mlp {m['mlp']!r}")
    return _linear(p["down"], u, m, prec)


def _moe(p, h, m, prec):
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    E, k = m["n_experts"], m["top_k"]
    probs = jax.nn.softmax(mm(x, p["router"]["w"].astype(jnp.float32), prec),
                           -1)
    top, ids = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros((B * S, E), jnp.float32).at[
        jnp.arange(B * S)[:, None], ids].set(top)
    seg = m["ovsf"]["seg_len"]

    def expert(y, e):
        g = mm(x, dense_weight(p["gate"]["alphas"][e], p["gate"]["idx"], seg),
               prec)
        u = mm(x, dense_weight(p["up"]["alphas"][e], p["up"]["idx"], seg),
               prec)
        o = mm(jax.nn.silu(g) * u,
               dense_weight(p["down"]["alphas"][e], p["down"]["idx"], seg),
               prec)
        return y + gates[:, e][:, None] * o, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return y.reshape(B, S, d)


def _layer(m_json: str, prec: str):
    m = json.loads(m_json)

    def layer(x, p, pos):
        h = rmsnorm(x, p["norm1"]["scale"], m["norm_eps"])
        x = x + _attention(p["attn"], h, pos, m, prec)
        h = rmsnorm(x, p["norm2"]["scale"], m["norm_eps"])
        y = _moe(p["moe"], h, m, prec) if m.get("n_experts") else \
            _mlp(p["mlp"], h, m, prec)
        return x + y
    return layer


@functools.lru_cache(maxsize=8)
def _jitted(m_json: str, prec: str):
    m = json.loads(m_json)
    layer = jax.jit(_layer(m_json, prec))

    @jax.jit
    def embed(table, tokens):
        t = table.astype(jnp.float32)
        return (_q8(t, None) if prec == "fp8" else t)[tokens]

    @jax.jit
    def head(x, norm, w):
        return mm(rmsnorm(x, norm, m["norm_eps"]), w.astype(jnp.float32),
                  prec)
    return embed, layer, head


def logits(params, m: dict, seqs: list, rows: list, prec: str = "f32",
           max_tokens: int = 12288):
    """Reference logits at chosen positions.

    ``seqs`` are token id arrays, run causally in right-padded batches of
    ``max_tokens`` tokens (lengths rounded up to 256, rows padded to fill the
    batch, so that runs reuse a few compiled shapes); ``rows`` lists, per
    sequence, the positions whose next-token logits are wanted. Returns one
    (len(rows[b]), vocab) float32 numpy array per sequence."""
    out, group, width = [], [], 0
    for b, s in enumerate(seqs):
        w = -(-len(s) // 256) * 256
        if group and max(width, w) * (len(group) + 1) > max_tokens:
            out += _logits_batch(params, m, [seqs[i] for i in group],
                                 [rows[i] for i in group], prec, width,
                                 max(max_tokens // width, len(group)))
            group, width = [], 0
        group.append(b)
        width = max(width, w)
    out += _logits_batch(params, m, [seqs[i] for i in group],
                         [rows[i] for i in group], prec, width,
                         max(max_tokens // width, len(group)))
    return out


def _logits_batch(params, m: dict, seqs: list, rows: list, prec: str,
                  S: int, n_rows: int):
    m_json = json.dumps(m, sort_keys=True)
    embed, layer, head = _jitted(m_json, prec)
    toks = np.zeros((n_rows, S), np.int32)
    for b, s in enumerate(seqs):
        toks[b, :len(s)] = s
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), toks.shape)
    blocks = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = embed(params["embed"]["table"], jnp.asarray(toks))
        for li in range(m["n_layers"]):
            lp = jax.tree.map(lambda a: a[li], blocks)
            x = layer(x, lp, pos)
        sel_b = np.concatenate([np.full(len(r), b) for b, r in
                                enumerate(rows)]).astype(np.int32)
        sel_t = np.concatenate([np.asarray(r) for r in rows]).astype(np.int32)
        feats = x[jnp.asarray(sel_b), jnp.asarray(sel_t)]
        out = np.asarray(head(feats, params["final_norm"]["scale"],
                              params["lm_head"]["w"]), np.float32)
    splits = np.cumsum([len(r) for r in rows])[:-1]
    return np.split(out, splits)
