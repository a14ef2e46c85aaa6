"""Base layers: Linear (dense or OVSF-compressed), norms, embedding, RoPE.

Params are plain nested dicts of jnp arrays; every layer is (init, apply)
function pairs so stacks can be scanned/vmapped and sharded by path rules.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, OVSFConfig
from repro.core import ovsf
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# Linear — the single place the paper's technique plugs into the model zoo
# ---------------------------------------------------------------------------

def ovsf_eligible(cfg: ModelConfig, name: str, d_in: int, d_out: int) -> bool:
    oc = cfg.ovsf
    if not oc.enable or min(d_in, d_out) < oc.min_dim:
        return False
    group = name.split("_")[0]          # attn_q -> attn, mlp_up -> mlp
    return group in oc.targets and oc.rho_for(name) < 1.0 + 1e-9


def linear_init(key: jax.Array, cfg: ModelConfig, name: str, d_in: int,
                d_out: int, bias: bool = False, scale: float = 1.0) -> dict:
    dtype = cfg.act_dtype
    p: dict = {}
    if ovsf_eligible(cfg, name, d_in, d_out):
        seg = cfg.ovsf.seg_len if (cfg.ovsf.seg_len
                                   and d_in % cfg.ovsf.seg_len == 0) else 0
        spec = ovsf.OVSFSpec(d_in, d_out, rho=cfg.ovsf.rho_for(name),
                             strategy=cfg.ovsf.strategy,  # type: ignore[arg-type]
                             seg=seg)
        p.update(ovsf.init_ovsf(key, spec, scale=scale, dtype=dtype))
        if cfg.ovsf.alpha_dtype:
            p = ovsf.quantize_params(p, cfg.ovsf.alpha_dtype)
    else:
        std = float(np.sqrt(scale / d_in))
        p["w"] = jax.random.normal(key, (d_in, d_out), dtype) * std
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def layer_plan(cfg: ModelConfig, name: str):
    """Resolve the mapper's LayerPlan for a weight-type name (or None)."""
    ep = getattr(cfg, "exec_plan", None)
    if ep is None or not name:
        return None
    return ep.plan_for(name)


def linear_apply(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                 name: str = "", mids: Optional[jnp.ndarray] = None
                 ) -> jnp.ndarray:
    """Apply a linear layer. ``name`` (weight type, e.g. "mlp_up") keys the
    hardware-aware execution plan when ``cfg.exec_plan`` is set; OVSF layers
    then dispatch per-layer (path, blocks, cache) instead of the uniform
    ``cfg.ovsf.exec_path``. ``mids`` (x.shape[:-1] int32) selects a
    per-token variant when the alpha bank is stacked (M, J, d_out) — the
    multi-model gateway's same-architecture batching; dense and unstacked
    OVSF leaves are variant-shared and ignore it."""
    with jax.named_scope(f"linear.{name}" if name else "linear"):
        if "alphas" in p or "alphas_q8" in p or "alphas_q4" in p:
            al, scale, adt = ovsf.alpha_params(p)
            plan = layer_plan(cfg, name)
            if mids is not None and al.ndim == 3:
                y = kops.ovsf_matmul_multi(x, al, p["idx"], mids,
                                           alpha_scale=scale, alpha_dtype=adt)
            elif plan is not None:
                y = kops.ovsf_matmul(x, al, p["idx"], plan=plan,
                                     alpha_scale=scale, alpha_dtype=adt)
            else:
                y = kops.ovsf_matmul(x, al, p["idx"], path=cfg.ovsf.exec_path,
                                     alpha_scale=scale, alpha_dtype=adt)
        else:
            y = x @ p["w"].astype(x.dtype)
        if "b" in p:
            y = y + p["b"].astype(y.dtype)
        return y


def linear_convert_to_ovsf(p: dict, rho: float, strategy: str = "iterative",
                           seg: int = 16, alpha_dtype: str = "") -> dict:
    """Compress a dense linear param dict into OVSF form (paper's Converter).

    ``alpha_dtype`` "int8"/"int4" emits the quantised storage form
    (alphas_q8/alphas_q4 + per-segment alpha_scale)."""
    w = p["w"]
    if seg and w.shape[0] % seg:
        seg = 0
    spec = ovsf.OVSFSpec(w.shape[0], w.shape[1], rho=rho, strategy=strategy,  # type: ignore[arg-type]
                         seg=seg, alpha_dtype=alpha_dtype)
    out = ovsf.compress_matrix(jnp.asarray(w, jnp.float32), spec)
    if "alphas" in out:
        out = {"alphas": out["alphas"].astype(w.dtype), "idx": out["idx"]}
    if "b" in p:
        out["b"] = p["b"]
    return out


# ---------------------------------------------------------------------------
# Norms / embedding
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: dict, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p: dict, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype) -> dict:
    return {"table": jax.random.normal(key, (vocab, d), dtype) * 0.02}


@jax.named_scope("embed")
def embed_apply(p: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["table"], tokens, axis=0)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float
               ) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return y.astype(x.dtype)
