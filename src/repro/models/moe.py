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


def _expert_matmul(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                   name: str = "") -> jnp.ndarray:
    """x: (G, E, C, d_in) batched per-expert GEMM -> (G, E, C, d_out)."""
    if "alphas" in p:
        plan = L.layer_plan(cfg, name)
        path = plan.path if plan is not None else cfg.ovsf.exec_path
        # spectral path vectorised over experts (shared idx)
        if path == "spectral":
            d_in = x.shape[-1]
            idx = p["idx"]
            if idx.ndim == 2:                                    # segmented
                ns, nk = idx.shape
                L0 = d_in // ns
                xs = x.reshape(x.shape[:-1] + (ns, L0))
                xh = kops.fwht(xs, use_pallas=False)
                xk = jnp.take_along_axis(
                    xh, jnp.broadcast_to(idx, xh.shape[:-1] + (nk,)), axis=-1)
                xk = xk.reshape(x.shape[:-1] + (ns * nk,))
            else:
                Lc = ovsf.next_pow2(d_in)
                if Lc != d_in:
                    x = jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                                + ((0, Lc - d_in),))
                xh = kops.fwht(x)
                xk = jnp.take(xh, idx, axis=-1)                  # (G, E, C, J)
            return jnp.einsum("gecj,ejn->gecn", xk,
                              p["alphas"].astype(xk.dtype))
        # No per-expert fused (TiWGen) kernel yet: a plan with path="fused"
        # falls back to the decompress dataflow below (see ROADMAP open
        # items). Numerics are unchanged; only the modeled HBM win is lost.
        if plan is not None and plan.cache_weights:
            W = kops.cached_decompress(
                p["alphas"], p["idx"], x.shape[-1],
                cache_key=plan.cache_key or name)                 # (E, d_in, d_out)
        else:
            W = jax.vmap(lambda a: kops.decompress(a, p["idx"], x.shape[-1])
                         )(p["alphas"])                           # (E, d_in, d_out)
        return jnp.einsum("gecd,edn->gecn", x, W.astype(x.dtype))
    return jnp.einsum("gecd,edn->gecn", x, p["w"].astype(x.dtype))


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
        ex_in = jnp.einsum("gtec,gtd->gecd", disp, xg)          # (G,E,cap,d)

    with jax.named_scope("moe.experts"):
        gg = _expert_matmul(p["gate"], ex_in, cfg, "expert_gate")
        uu = _expert_matmul(p["up"], ex_in, cfg, "expert_up")
        h = jax.nn.silu(gg.astype(jnp.float32)).astype(uu.dtype) * uu
        ex_out = _expert_matmul(p["down"], h, cfg, "expert_down")

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
