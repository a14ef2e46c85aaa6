"""jit'd public wrappers around the OVSF kernels + execution-path dispatch.

Execution paths for an OVSF linear layer y = x @ W(alphas, idx):

``materialize``  paper-faithful weight-stationary: W is regenerated once per
                 layer invocation (Pallas ``ovsf_decompress`` on TPU, FWHT-based
                 jnp on other backends) and consumed by a standard GEMM.
``fused``        paper-faithful TiWGen: generation fused into the GEMM tiles
                 (Pallas ``ovsf_gemm``); best when the GEMM is memory-bound
                 (decode) because the dense W never exists in HBM.
``spectral``     beyond-paper: y = fwht(pad(x))[:, idx] @ alphas. Exact
                 (x @ S^T = WHT(x_pad) restricted to kept codes), shrinks BOTH
                 the weight bytes AND the main GEMM FLOPs to J/d_in of dense,
                 at the cost of an O(L log L) activation transform. The FPGA
                 engine could not reshape its dataflow this way; the TPU can.

All paths are numerically validated against each other in tests.
"""
from __future__ import annotations

from typing import Any, Literal, Optional

import jax
import jax.numpy as jnp

from repro.core import ovsf
from repro.kernels import ref as kref
from repro.kernels.fwht import fwht_pallas
from repro.kernels.ovsf_gemm import ovsf_gemm, ovsf_decompress

ExecPath = Literal["materialize", "fused", "spectral"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fwht(x: jnp.ndarray, *, use_pallas: bool | None = None,
         interpret: bool = False) -> jnp.ndarray:
    """WHT along last axis; Pallas on TPU, jnp butterfly elsewhere."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        return fwht_pallas(x, interpret=interpret)
    return ovsf.fwht(x, axis=-1)


def decompress(alphas: jnp.ndarray, idx: jnp.ndarray, d_in: int, *,
               alpha_scale=None, alpha_dtype: str = "",
               use_pallas: bool | None = None, interpret: bool = False
               ) -> jnp.ndarray:
    """Dense (d_in, d_out) weights from OVSF params.

    idx (J,) -> monolithic codes; idx (n_seg, n_keep) -> segmented codes
    (the paper's Alg. 1 layout). Quantised alphas (``alpha_dtype`` int8/int4
    + ``alpha_scale``): the Pallas path dequantises inside the generator
    loop; the jnp paths dequantise up front (XLA fuses the convert into the
    consumer, and materialize's dataflow round-trips dense W regardless).
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if idx.ndim == 2:
        if alpha_dtype:
            alphas = ovsf.dequantize_alphas(alphas, alpha_scale, alpha_dtype)
        return _segmented_decompress(alphas, idx, d_in)
    if use_pallas:
        return ovsf_decompress(alphas, idx, d_in=d_in, alpha_scale=alpha_scale,
                               alpha_dtype=alpha_dtype, interpret=interpret)
    if alpha_dtype:
        alphas = ovsf.dequantize_alphas(alphas, alpha_scale, alpha_dtype)
    # FWHT-based decompression: no LxL temp, HLO stays small for dry-runs.
    return kref.fwht_decompress_ref(alphas, idx, d_in)


def _segmented_decompress(alphas: jnp.ndarray, idx: jnp.ndarray, d_in: int
                          ) -> jnp.ndarray:
    ns, nk = idx.shape
    L0 = d_in // ns
    d_out = alphas.shape[-1]
    al = alphas.reshape(ns, nk, d_out)
    full = jnp.zeros((ns, L0, d_out), alphas.dtype)
    # scatter kept coefficients into each segment's spectrum, then per-seg WHT
    full = jax.vmap(lambda f, a, i: f.at[i, :].set(a))(full, al, idx)
    w = ovsf.fwht(jnp.swapaxes(full, 1, 2), axis=-1)   # (ns, d_out, L0)
    return jnp.swapaxes(w, 1, 2).reshape(d_in, d_out)


def spectral_matmul(x: jnp.ndarray, alphas: jnp.ndarray, idx: jnp.ndarray,
                    *, alpha_scale=None, alpha_dtype: str = "",
                    use_pallas: bool | None = None, interpret: bool = False
                    ) -> jnp.ndarray:
    """y = x @ W via the activation-transform identity (exact).

    Monolithic: y = fwht(pad(x))[:, idx] @ alphas.
    Segmented:  per length-L0 segment, y = concat_s(fwht(x_s)[:, idx_s]) @ A —
    a single dense GEMM with contraction rho*d_in (block-diagonal basis).
    Quantised alphas are dequantised before the GEMM (the alphas ARE the
    B-operand here; the int8 bytes are still what crosses HBM under fusion).
    """
    if alpha_dtype:
        alphas = ovsf.dequantize_alphas(alphas, alpha_scale, alpha_dtype)
    xk = spectral_transform(x, idx, use_pallas=use_pallas,
                            interpret=interpret)
    return (xk @ alphas.astype(xk.dtype)).astype(x.dtype)


def spectral_transform(x: jnp.ndarray, idx: jnp.ndarray, *,
                       use_pallas: bool | None = None,
                       interpret: bool = False) -> jnp.ndarray:
    """The activation-transform half of ``spectral_matmul``: (..., d_in) ->
    (..., J) kept-code coefficients. The remaining GEMM against the alpha
    bank is the caller's — ``ovsf_matmul_multi`` reuses this transform once
    per token and contracts against a *per-token-selected* bank."""
    d_in = x.shape[-1]
    if idx.ndim == 2:
        ns, nk = idx.shape
        L0 = d_in // ns
        xs = x.reshape(x.shape[:-1] + (ns, L0))
        xh = fwht(xs, use_pallas=False)                 # tiny per-seg WHT
        xk = jnp.take_along_axis(
            xh, jnp.broadcast_to(idx, xh.shape[:-1] + (nk,)), axis=-1)
        return xk.reshape(x.shape[:-1] + (ns * nk,))
    L = ovsf.next_pow2(d_in)
    if L != d_in:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, L - d_in)])
    xh = fwht(x, use_pallas=use_pallas, interpret=interpret)
    return jnp.take(xh, idx, axis=-1)                  # (..., J)


def ovsf_matmul(x: jnp.ndarray, alphas: jnp.ndarray, idx: jnp.ndarray, *,
                path: ExecPath = "materialize",
                plan: Optional[Any] = None,
                alpha_scale=None, alpha_dtype: str = "",
                use_pallas: bool | None = None,
                interpret: bool = False,
                block_m: int = 128, block_n: int = 128,
                block_k: int = 128, block_j: int = 128) -> jnp.ndarray:
    """Dispatch y = x @ W(alphas, idx) over (..., d_in) activations.

    ``plan`` (a ``runtime.mapper.LayerPlan``) overrides path and Pallas
    block sizes — the hardware-aware per-layer dispatch of paper §5. Without
    a plan, behaviour is the legacy explicit ``path=`` dispatch with default
    blocks. ``alpha_dtype``/``alpha_scale`` select the quantised
    alpha-storage form (see ``core.ovsf.alpha_params`` to unpack a param
    dict): the fused Pallas path streams the quantised bytes and dequantises
    in-kernel; the other paths dequantise at the GEMM boundary.
    """
    if plan is not None:
        path = plan.path  # type: ignore[assignment]
        block_m, block_n = plan.block_m, plan.block_n
        block_k, block_j = plan.block_k, plan.block_j
    if use_pallas is None:
        use_pallas = on_tpu()
    lead = x.shape[:-1]
    d_in = x.shape[-1]
    d_out = alphas.shape[-1] * (2 if alpha_dtype == "int4" else 1)
    x2 = x.reshape(-1, d_in)

    if path == "spectral":
        y = spectral_matmul(x2, alphas, idx, alpha_scale=alpha_scale,
                            alpha_dtype=alpha_dtype, use_pallas=use_pallas,
                            interpret=interpret)
    elif path == "fused":
        if use_pallas:
            y = ovsf_gemm(x2, alphas, idx, alpha_scale=alpha_scale,
                          alpha_dtype=alpha_dtype, interpret=interpret,
                          block_m=block_m, block_n=block_n,
                          block_k=block_k, block_j=block_j)
        else:
            y = kref.ovsf_matmul_ref(x2, alphas, idx, alpha_scale=alpha_scale,
                                     alpha_dtype=alpha_dtype)
    elif path == "materialize":
        W = decompress(alphas, idx, d_in, alpha_scale=alpha_scale,
                       alpha_dtype=alpha_dtype, use_pallas=use_pallas,
                       interpret=interpret)
        y = (x2 @ W.astype(x2.dtype)).astype(x.dtype)
    else:
        raise ValueError(f"unknown exec path: {path}")
    return y.reshape(lead + (d_out,))


def ovsf_matmul_multi(x: jnp.ndarray, alphas: jnp.ndarray, idx: jnp.ndarray,
                      mids: jnp.ndarray, *,
                      alpha_scale=None, alpha_dtype: str = "",
                      use_pallas: bool | None = None,
                      interpret: bool = False) -> jnp.ndarray:
    """y[t] = x[t] @ W(alphas[mids[t]], idx) — a stacked multi-variant GEMM.

    ``alphas`` carries a leading model axis (M, J, d_out): M same-architecture
    variants whose banks share ``idx`` (and every non-alpha leaf). ``mids``
    (x.shape[:-1]) selects each token's variant inside ONE jit'd call, so a
    step can mix models without per-model dispatch or retracing — the
    multi-LoRA analogue for on-the-fly generated weights.

    Uses the spectral identity: the activation transform is variant-
    independent (idx is shared), so only the closing GEMM is per-variant.
    Each variant runs the literal single-model ``spectral_matmul`` on the
    same flattened activations (an unrolled Python loop — M is static and
    small), and tokens select their variant's row with ``where``, which is a
    bitwise pass-through. That keeps each token's output bit-identical to
    the single-model spectral path — the license for token-exact gateway
    equivalence. A vmapped batched GEMM would be fewer ops but XLA may pick
    a different reduction order for it, breaking bit-identity. M is small
    (resident same-arch variants), so the extra FLOPs stay noise next to
    the attention + dense trunk.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m2 = mids.astype(jnp.int32).reshape(-1)
    out = None
    for m in range(alphas.shape[0]):
        ym = spectral_matmul(x2, alphas[m], idx,
                             alpha_scale=None if alpha_scale is None
                             else alpha_scale[m],
                             alpha_dtype=alpha_dtype, use_pallas=use_pallas,
                             interpret=interpret)
        out = ym if out is None else jnp.where((m2 == m)[:, None], ym, out)
    return out.reshape(lead + (out.shape[-1],))
