"""Least work of a served step, from shapes alone.

Least work is what any exact implementation of the configuration must do for
the tokens a step processed, so no path can read above 100% of a peak:

* an OVSF linear over M valid tokens: 2*M*J*d_out flops with J = rho*d_in
  kept codes (the spectral identity y = WHT(x)[:, idx] @ alphas; the
  transform's additions are not counted). Bytes: the stored alphas at their
  dtype, the code ids, and the activations in and out;
* a dense linear (router, unembed) at its own size, the unembed only for the
  rows whose token is consumed;
* attention: QK and PV of each token over its context (its position + 1);
  flops only, since a chunk's tokens share the keys they read;
* MoE: the top-k experts of each token, not capacity slots.

Nothing here imports the program: sizes come from the configuration file,
under the names ``bench.run.model_of`` gives them.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Linear:
    name: str
    d_in: int
    d_out: int


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n)

    def least_seconds(self, peak_flops: float, peak_bytes_s: float) -> float:
        return max(self.flops / peak_flops, self.bytes / peak_bytes_s)


@dataclasses.dataclass(frozen=True)
class StepTokens:
    """What one step processed: valid tokens, the sum of their contexts
    (keys each attends to), and the rows whose next token is consumed."""
    n_tokens: int
    ctx_sum: int
    n_emit: int


def kept_codes(d_in: int, ovsf: dict) -> int:
    """J, the kept codes of a (d_in, .) matrix: round(rho * L0) per length-L0
    segment, or round(rho * next_pow2(d_in)) with monolithic codes."""
    seg = ovsf["seg_len"]
    if seg and d_in % seg == 0:
        return (d_in // seg) * max(1, round(ovsf["rho"] * seg))
    return max(1, round(ovsf["rho"] * (1 << (d_in - 1).bit_length())))


def is_ovsf(li: Linear, ovsf: dict) -> bool:
    return min(li.d_in, li.d_out) >= ovsf["min_dim"]


def trunk_linears(m: dict) -> list[Linear]:
    """Per-layer linears outside any expert bank: the attention projections
    and, for a dense model, the MLP."""
    d, H, Hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    out = [Linear("attn_q", d, H * hd), Linear("attn_k", d, Hkv * hd),
           Linear("attn_v", d, Hkv * hd), Linear("attn_o", H * hd, d)]
    if not m.get("n_experts"):
        f = m["d_ff"]
        out += [Linear("mlp_up", d, f), Linear("mlp_down", f, d)]
        if m["mlp"] == "swiglu":
            out.append(Linear("mlp_gate", d, f))
    return out


def expert_linears(m: dict) -> list[Linear]:
    d, f = m["d_model"], m["d_ff"]
    return [Linear("expert_gate", d, f), Linear("expert_up", d, f),
            Linear("expert_down", f, d)]


def ovsf_linear(li: Linear, M: int, ovsf: dict, act_bytes: int = 2,
                alpha_bytes: int = 2) -> Work:
    """One OVSF linear over M tokens."""
    if M <= 0:
        return Work()
    J = kept_codes(li.d_in, ovsf)
    return Work(2.0 * M * J * li.d_out,
                J * li.d_out * alpha_bytes + J * 4
                + M * (li.d_in + li.d_out) * act_bytes)


def dense_linear(li: Linear, M: int, act_bytes: int = 2,
                 w_bytes: int = 2) -> Work:
    if M <= 0:
        return Work()
    return Work(2.0 * M * li.d_in * li.d_out,
                li.d_in * li.d_out * w_bytes + M * (li.d_in + li.d_out)
                * act_bytes)


def linear(li: Linear, M: int, ovsf: dict) -> Work:
    return ovsf_linear(li, M, ovsf) if is_ovsf(li, ovsf) else dense_linear(li, M)


def step_work(m: dict, st: StepTokens) -> Work:
    """Least work of one step of configuration ``m``."""
    ovsf = m["ovsf"]
    nl, d = m["n_layers"], m["d_model"]
    layer = Work()
    for li in trunk_linears(m):
        layer = layer + linear(li, st.n_tokens, ovsf)
    E = m.get("n_experts", 0)
    if E:
        routed = st.n_tokens * m["top_k"]
        for li in expert_linears(m):
            one = linear(li, routed, ovsf)
            # the alphas of at most min(E, routed) distinct experts are read
            extra = min(E, routed) - 1
            J = kept_codes(li.d_in, ovsf) if is_ovsf(li, ovsf) else li.d_in
            layer = layer + one + Work(0.0, extra * J * li.d_out * 2)
        layer = layer + dense_linear(Linear("router", d, E), st.n_tokens)
    H, hd = m["n_heads"], m["head_dim"]
    # QK^T and PV, two flops per multiply-add, for every query head
    layer = layer + Work(4.0 * H * hd * st.ctx_sum, 0.0)
    return layer * nl + dense_linear(Linear("unembed", d, m["vocab"]),
                                     st.n_emit)
