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
  flops only, since a chunk's tokens share the keys they read; GQA's
  projections, or latent attention's five (``attention_linears``);
* MoE: the top-k experts of each token, not capacity slots; where a chip
  holds E of the router's R experts, E / R of the routed picks (uniform
  routing); shared experts on every token of an expert layer. The experts'
  weight bytes are an estimate, not a least count: those of min(E, routed)
  experts, each pick reaching an expert of its own until all E are read.
  That is at or above the expected number of distinct experts reached under
  uniform routing, E * (1 - (1 - k / R) ** n), by at most routed**2 / (2E).
  No metric reads these bytes: ``mfu`` reads flops, ``ovsf_gemm_roofline``
  the trunk linears;
* layers: the leading ``first_dense`` layers of a model with experts have a
  dense MLP at ``d_ff``, the rest experts at ``moe_d_ff``.

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


def attention_linears(m: dict) -> list[Linear]:
    """The attention projections of a layer: GQA's q, k, v and o, or latent
    attention's (``m["mla"]``) q_a, q_b, kv_a (the latent and the shared
    rope key), kv_b and o."""
    d, H = m["d_model"], m["n_heads"]
    mla = m["mla"]
    if mla:
        nope, rope = mla["qk_nope_head_dim"], mla["qk_rope_head_dim"]
        r, kv, v = mla["q_lora_rank"], mla["kv_lora_rank"], mla["v_head_dim"]
        return [Linear("attn_q_a", d, r),
                Linear("attn_q_b", r, H * (nope + rope)),
                Linear("attn_kv_a", d, kv + rope),
                Linear("attn_kv_b", kv, H * (nope + v)),
                Linear("attn_o", H * v, d)]
    Hkv, hd = m["n_kv_heads"], m["head_dim"]
    return [Linear("attn_q", d, H * hd), Linear("attn_k", d, Hkv * hd),
            Linear("attn_v", d, Hkv * hd), Linear("attn_o", H * hd, d)]


def mlp_linears(m: dict, prefix: str, f: int) -> list[Linear]:
    d = m["d_model"]
    out = [Linear(f"{prefix}_up", d, f), Linear(f"{prefix}_down", f, d)]
    if m["mlp"] == "swiglu":
        out.append(Linear(f"{prefix}_gate", d, f))
    return out


def expert_linears(m: dict) -> list[Linear]:
    d, f = m["d_model"], m["moe_d_ff"]
    return [Linear("expert_gate", d, f), Linear("expert_up", d, f),
            Linear("expert_down", f, d)]


def dense_layers(m: dict) -> int:
    """Layers with a dense MLP: all of a dense model's, the leading
    ``first_dense`` of a model with experts."""
    return m["first_dense"] if m["n_experts"] else m["n_layers"]


def layer_linears(m: dict) -> list[tuple[Linear, int]]:
    """The linears outside any expert bank, which every token of a step
    passes in the layers that hold them, each with that number of layers:
    attention in every layer, the dense MLP at ``d_ff`` in the dense ones,
    the shared experts (one MLP of their summed width) in the expert ones."""
    nl, nd, S = m["n_layers"], dense_layers(m), m["n_shared_experts"]
    out = [(li, nl) for li in attention_linears(m)]
    if nd:
        out += [(li, nd) for li in mlp_linears(m, "mlp", m["d_ff"])]
    if S and nl > nd:
        out += [(li, nl - nd)
                for li in mlp_linears(m, "shared", m["moe_d_ff"] * S)]
    return out


def trunk_linears(m: dict) -> list[Linear]:
    return [li for li, _ in layer_linears(m)]


def ovsf_linear(li: Linear, M: float, ovsf: dict, act_bytes: int = 2,
                alpha_bytes: int = 2, copies: float = 1) -> Work:
    """One OVSF linear over M tokens; ``copies`` matrices of this shape
    (experts reached) have their alphas read."""
    if M <= 0:
        return Work()
    J = kept_codes(li.d_in, ovsf)
    return Work(2.0 * M * J * li.d_out,
                copies * J * li.d_out * alpha_bytes + J * 4
                + M * (li.d_in + li.d_out) * act_bytes)


def dense_linear(li: Linear, M: float, act_bytes: int = 2,
                 w_bytes: int = 2, copies: float = 1) -> Work:
    if M <= 0:
        return Work()
    return Work(2.0 * M * li.d_in * li.d_out,
                copies * li.d_in * li.d_out * w_bytes
                + M * (li.d_in + li.d_out) * act_bytes)


def linear(li: Linear, M: float, ovsf: dict, copies: float = 1) -> Work:
    if is_ovsf(li, ovsf):
        return ovsf_linear(li, M, ovsf, copies=copies)
    return dense_linear(li, M, copies=copies)


def attention_flops(m: dict, ctx_sum: int) -> float:
    """QK^T and PV of every query head over ``ctx_sum`` context positions,
    two flops per multiply-add: at head width hd for GQA, at nope + rope
    for the scores and v for PV under latent attention."""
    H, mla = m["n_heads"], m["mla"]
    if mla:
        qk = mla["qk_nope_head_dim"] + mla["qk_rope_head_dim"]
        return (2.0 * H * qk + 2.0 * H * mla["v_head_dim"]) * ctx_sum
    return 4.0 * H * m["head_dim"] * ctx_sum


def expert_work(m: dict, n_tokens: int) -> Work:
    """The router and the routed experts held here, for ``n_tokens``: the
    chip that holds E of the router's R experts sees routed = n_tokens *
    top_k * E / R of the picks, and reads the weights of min(E, routed)
    experts (an estimate; the module's docstring says how close)."""
    E, R = m["n_experts"], m["router_experts"]
    routed = n_tokens * m["top_k"] * E / R
    w = dense_linear(Linear("router", m["d_model"], R), n_tokens)
    for li in expert_linears(m):
        w = w + linear(li, routed, m["ovsf"], copies=min(E, routed))
    return w


def step_work(m: dict, st: StepTokens) -> Work:
    """Least work of one step of configuration ``m``: attention over the
    context, the linears of ``layer_linears`` in their layers, the experts
    in the expert layers, then the unembedding."""
    nl = m["n_layers"]
    total = Work(attention_flops(m, st.ctx_sum) * nl, 0.0)
    for li, layers in layer_linears(m):
        total = total + linear(li, st.n_tokens, m["ovsf"]) * layers
    if m["n_experts"]:
        total = total + expert_work(m, st.n_tokens) * (nl - dense_layers(m))
    return total + dense_linear(Linear("unembed", m["d_model"], m["vocab"]),
                                st.n_emit)
