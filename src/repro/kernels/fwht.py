"""Pallas TPU kernel: fast Walsh-Hadamard transform as one MXU matmul per
lane chunk plus butterflies across chunks (MXU-native form of the FPGA OVSF
generator's butterfly network).

H_L = H_La (x) H_Lb with L = La * Lb, Lb = min(L, 128) one lane tile. Index
n = a * Lb + b splits into high bits a and low bits b, and the sign
(-1)^popcount(n & m) factors over the two bit fields. So the transform is a
(Lb, Lb) matmul on each Lb-wide lane chunk (the low bits) followed by
log2(La) butterfly passes that add and subtract whole chunks (the high
bits). Every slice is a static, lane-aligned one: no in-kernel reshape.
The Hadamard factor is generated *in-register* from iota + bit-parity -- no
HBM traffic for the basis, which is the paper's core on-the-fly insight
mapped to the TPU memory hierarchy (HBM->VMEM->VREG).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hwmodel.tile_balance import SCOPED_VMEM

_LANES = 128
# VMEM the row block may take: under the default scoped limit, with room
# for the compiler's own scratch.
_VMEM_BUDGET = SCOPED_VMEM * 3 // 4


def _iota_hadamard(n: int, dtype) -> jnp.ndarray:
    """(n, n) +-1 Sylvester-Hadamard built from iota + popcount parity."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    x = i & j
    # branch-free popcount parity (non-negative int32: >> is logical)
    x = x ^ (x >> 16)
    x = x ^ (x >> 8)
    x = x ^ (x >> 4)
    x = x ^ (x >> 2)
    x = x ^ (x >> 1)
    return (1 - 2 * (x & 1)).astype(dtype)


def _fwht_kernel(x_ref, o_ref, acc_ref, *, La: int, Lb: int):
    # bf16 inputs times +-1 are exact products on the one-pass MXU path;
    # fp32 inputs need the full-precision passes.
    fp32 = x_ref.dtype == jnp.float32
    H = _iota_hadamard(Lb, x_ref.dtype)
    prec = jax.lax.Precision.HIGHEST if fp32 else jax.lax.Precision.DEFAULT
    chunk = lambda a: slice(a * Lb, (a + 1) * Lb)
    for a in range(La):                     # low bits: MXU, per lane chunk
        acc_ref[:, chunk(a)] = jnp.dot(x_ref[:, chunk(a)], H, precision=prec,
                                       preferred_element_type=jnp.float32)
    h = 1
    while h < La:                           # high bits: chunk butterflies
        for a in range(La):
            if a & h:
                continue
            u = acc_ref[:, chunk(a)]
            v = acc_ref[:, chunk(a + h)]
            acc_ref[:, chunk(a)] = u + v
            acc_ref[:, chunk(a + h)] = u - v
        h *= 2
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def fwht_pallas(x: jnp.ndarray, *, block_m: int = 256, interpret: bool = False
                ) -> jnp.ndarray:
    """WHT along the last axis of (..., L); L must be a power of two.

    The row block is at most ``block_m`` and at most what fits the VMEM
    budget: the input and output blocks (two buffers each) plus the fp32
    accumulator, L wide."""
    orig_shape = x.shape
    L = orig_shape[-1]
    if L & (L - 1):
        raise ValueError(f"FWHT length must be a power of two, got {L}")
    Lb = min(L, _LANES)
    xf = x.reshape(-1, L)
    M = xf.shape[0]
    row_bytes = L * (4 * x.dtype.itemsize + 4)
    bm_vmem = max(8, _VMEM_BUDGET // row_bytes // 8 * 8)
    bm = min(block_m, bm_vmem, -(-M // 8) * 8)
    pad = (-M) % bm
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    Mp = xf.shape[0]
    out = pl.pallas_call(
        functools.partial(_fwht_kernel, La=L // Lb, Lb=Lb),
        grid=(Mp // bm,),
        in_specs=[pl.BlockSpec((bm, L), lambda m: (m, 0))],
        out_specs=pl.BlockSpec((bm, L), lambda m: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, L), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, L), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xf)
    return out[:M].reshape(orig_shape)
