"""Mixture-of-Experts block: top-k routing with capacity (GShard-style einsum
dispatch) so GSPMD emits all-to-alls when experts are sharded over the 'model'
mesh axis (EP). Expert FFN weights are the paper's memory-wall case at
trillion-param scale (kimi-k2): at decode every routed expert's weights must be
read from HBM, so OVSF compression of expert matrices cuts the dominant term.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import ovsf
from repro.kernels import ops as kops
from repro.models import layers as L


def moe_init(key: jax.Array, cfg: ModelConfig) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 8)
    dtype = cfg.act_dtype
    p: dict = {"router": {"w": jax.random.normal(ks[0], (d, E), dtype) * 0.02}}
    p.update(_expert_bank_init(ks[1], cfg, E, d, f, "expert"))
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = {
            "gate": L.linear_init(ks[2], cfg, "mlp_gate", d, fs),
            "up": L.linear_init(ks[3], cfg, "mlp_up", d, fs),
            "down": L.linear_init(ks[4], cfg, "mlp_down", fs, d),
        }
    return p


def _expert_bank_init(key: jax.Array, cfg: ModelConfig, E: int, d: int, f: int,
                      name: str) -> dict:
    """Stacked (E, ...) expert weights, OVSF-compressed when enabled."""
    ks = jax.random.split(key, 3)
    dtype = cfg.act_dtype
    out: dict = {}
    for i, (nm, d_in, d_out) in enumerate(
            [("gate", d, f), ("up", d, f), ("down", f, d)]):
        full = f"{name}_{nm}"
        if L.ovsf_eligible(cfg, full, d_in, d_out):
            seg = cfg.ovsf.seg_len if (cfg.ovsf.seg_len
                                       and d_in % cfg.ovsf.seg_len == 0) else 0
            spec = ovsf.OVSFSpec(d_in, d_out, rho=cfg.ovsf.rho_for(full),
                                 strategy=cfg.ovsf.strategy,  # type: ignore[arg-type]
                                 seg=seg)
            sub = jax.vmap(lambda k: ovsf.init_ovsf(k, spec, dtype=dtype)["alphas"]
                           )(jax.random.split(ks[i], E))
            idx = ovsf.init_ovsf(ks[i], spec, dtype=dtype)["idx"]
            out[nm] = {"alphas": sub, "idx": idx}        # (E, J, d_out), shared idx
        else:
            std = float(np.sqrt(1.0 / d_in))
            out[nm] = {"w": jax.random.normal(ks[i], (E, d_in, d_out), dtype) * std}
    return out


# What each distinct expert-bank call traced ran, noted at trace time
# (``expert_notes``).
_EXPERT_NOTES: dict = {}


def expert_notes() -> list:
    """One dict per distinct expert-bank call this process has traced: the
    bank's weight type ``name``, its ``path`` (``dense``: stored weights;
    else the mapper's path, or ``cfg.ovsf.exec_path`` unplanned), the
    ``experts``, the ``rows`` each expert contracts, ``d_in``, ``d_out``,
    ``j`` (the alpha rows; 0 for a dense bank) and ``dense_w_bytes``, the
    dense W the call materialises (0 but on the ``materialize`` path)."""
    return [dict(n) for n in _EXPERT_NOTES.values()]


def _bank_path(p: dict, cfg: ModelConfig, name: str) -> str:
    if "alphas" not in p:
        return "dense"
    plan = L.layer_plan(cfg, name)
    return plan.path if plan is not None else cfg.ovsf.exec_path


def _segment_basis(idx: jnp.ndarray, d_in: int, dtype) -> jnp.ndarray:
    """(d_in, J) block-diagonal +-1 matrix of segmented codes (n_seg, n_keep):
    segment s's block holds the columns idx[s] of the L0-point Hadamard
    matrix, so x @ basis is each segment's WHT at its kept codes."""
    ns, nk = idx.shape
    cols = ovsf.hadamard_matrix(d_in // ns, dtype)[:, idx]   # (L0, ns, nk)
    return jnp.einsum("lsk,st->sltk", cols, jnp.eye(ns, dtype=dtype)
                      ).reshape(d_in, ns * nk)


def _bank_input(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                name: str) -> jnp.ndarray:
    """What a bank contracts, per row of x (..., d_in): for a spectral bank
    the kept-code coefficients (..., J), summed in float32 and rounded once
    to x's dtype; else x itself. The codes are shared by every expert of
    the bank, so the transform commutes with dispatch. Segmented codes take
    one GEMM against their +-1 basis, whose products are exact: on a v5e
    an OLMoE layer of 256 tokens ran in 2.0 ms so, and in 126 ms with a
    butterfly over the 16-wide segments and a gather of the kept codes."""
    if _bank_path(p, cfg, name) != "spectral":
        return x
    idx = p["idx"]
    if idx.ndim == 1:
        return kops.spectral_transform(x.astype(jnp.float32), idx
                                       ).astype(x.dtype)
    return jnp.einsum("...d,dj->...j", x,
                      _segment_basis(idx, x.shape[-1], x.dtype),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _contract(x: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """(G, E, C, J) codes x (E, J, d_out) alphas, accumulated in float32 and
    rounded once to x's dtype. The CPU backend has no batched bf16 dot with
    a float32 result; it takes the operands in float32, whose products of
    bf16 values are exact, so the sum is the same float32 accumulation."""
    if not kops.on_tpu():
        x32, a32 = x.astype(jnp.float32), a.astype(jnp.float32)
        return jnp.einsum("gecj,ejn->gecn", x32, a32).astype(x.dtype)
    return jnp.einsum("gecj,ejn->gecn", x, a,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _expert_matmul(p: dict, x: jnp.ndarray, cfg: ModelConfig, name: str,
                   d_in: int) -> jnp.ndarray:
    """x: (G, E, C, k) per-expert rows of the bank's ``_bank_input`` (k = J
    for a spectral bank, else d_in) -> (G, E, C, d_out).

    A spectral bank contracts the codes against its alphas (bf16 operands
    in a bf16 model, float32 accumulation): no dense W exists. Otherwise an
    OVSF bank is decompressed into (E, d_in, d_out) and contracted densely;
    the fused path has no per-expert kernel and the mapper plans none."""
    path = _bank_path(p, cfg, name)
    w = p["w"] if path == "dense" else p["alphas"]
    E, j, d_out = w.shape
    dense_w = 0 if path in ("dense", "spectral") else \
        E * d_in * d_out * w.dtype.itemsize
    note = dict(name=name, path=path, experts=E,
                rows=x.shape[0] * x.shape[2], d_in=d_in, d_out=d_out,
                j=0 if path == "dense" else j, dense_w_bytes=dense_w)
    _EXPERT_NOTES.setdefault(tuple(note.items()), note)
    if path == "spectral":
        return _contract(x, w.astype(x.dtype))
    if path != "dense":
        w = jax.vmap(lambda a: kops.decompress(a, p["idx"], d_in))(w)
    return jnp.einsum("gecd,edn->gecn", x, w.astype(x.dtype))


MOE_GROUP = 1024   # tokens per routing group; aligned to data shards for
                   # train shapes so queue-position cumsums stay shard-local.


@jax.named_scope("moe")
def moe_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss). Grouped top-k dispatch with capacity."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    g = min(MOE_GROUP, T)
    pad = (-T) % g
    xt = x.reshape(T, d)
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
    G = xt.shape[0] // g
    xg = xt.reshape(G, g, d)

    with jax.named_scope("moe.router"):
        logits = jnp.einsum("gtd,de->gte", xg, p["router"]["w"].astype(
            xg.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                 # (G, g, E)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)           # (G, g, k)
        gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True)
                                 + 1e-9)

    with jax.named_scope("moe.dispatch"):
        cap = max(int(np.ceil(cfg.capacity_factor * k * g / E)), 1)
        # queue position of each (token, choice) within its expert, per
        # group
        onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)   # (G,g,k,E)
        flat = onehot.reshape(G, g * k, E)
        pos_all = jnp.cumsum(flat, axis=1) - flat               # (G,g*k,E)
        pos = jnp.sum(pos_all * flat, axis=-1).reshape(G, g, k)
        keep = pos < cap
        gate_vals = gate_vals * keep

        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                                dtype=xg.dtype)[..., :cap]      # (G,g,k,cap)
        oh = onehot.astype(xg.dtype)
        disp = jnp.einsum("gtke,gtkc->gtec", oh, pos_oh)        # (G,g,E,cap)
        comb = jnp.einsum("gtk,gtke,gtkc->gtec", gate_vals.astype(xg.dtype),
                          oh, pos_oh)

    with jax.named_scope("moe.experts"):
        # gate and up take their inputs per token, before dispatch: a
        # spectral bank's codes once per token, not once per expert slot
        xs = [_bank_input(p[nm], xg, cfg, f"expert_{nm}")
              for nm in ("gate", "up")]

    with jax.named_scope("moe.dispatch"):
        if xs[0] is xs[1]:
            gin = uin = jnp.einsum("gtec,gtd->gecd", disp, xg)  # (G,E,cap,d)
        else:
            both = jnp.einsum("gtec,gtd->gecd", disp,
                              jnp.concatenate(xs, axis=-1))
            gin, uin = jnp.split(both, [xs[0].shape[-1]], axis=-1)

    with jax.named_scope("moe.experts"):
        gg = _expert_matmul(p["gate"], gin, cfg, "expert_gate", d)
        uu = _expert_matmul(p["up"], uin, cfg, "expert_up", d)
        h = jax.nn.silu(gg.astype(jnp.float32)).astype(uu.dtype) * uu
        ex_out = _expert_matmul(
            p["down"], _bank_input(p["down"], h, cfg, "expert_down"), cfg,
            "expert_down", h.shape[-1])

    with jax.named_scope("moe.combine"):
        y = jnp.einsum("gtec,gecd->gtd", comb, ex_out).reshape(G * g, d)
        y = y[:T].reshape(B, S, d)

    if "shared" in p:
        sp = p["shared"]
        g2 = L.linear_apply(sp["gate"], x, cfg, "mlp_gate")
        u2 = L.linear_apply(sp["up"], x, cfg, "mlp_up")
        y = y + L.linear_apply(
            sp["down"], jax.nn.silu(g2.astype(jnp.float32)).astype(u2.dtype) * u2,
            cfg, "mlp_down")

    # load-balance auxiliary loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(jnp.sum(onehot, axis=2).astype(jnp.float32), axis=(0, 1))
    pe = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(me * pe) / k
    return y.astype(x.dtype), aux
