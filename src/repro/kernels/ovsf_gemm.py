"""Pallas TPU kernels for on-the-fly OVSF weight generation (paper §4.2, TiWGen).

Two kernels:

``ovsf_gemm``        — the TiWGen analogue: for each (bm, bn) output tile the
                       kernel *generates* the (bk, bn) weight tile it is about
                       to consume — Hadamard sign tile built in-register from
                       iota + bit parity (zero HBM bytes for the basis), then
                       two MXU matmuls: W_tile = S_tile^T @ alpha_tile and
                       acc += x_tile @ W_tile. HBM weight traffic is only the
                       alpha coefficients: rho*L/d_in of the dense bytes.

``ovsf_decompress``  — weight-stationary variant (paper §4.2.1, "other
                       dataflows" / TPU case): materialise the dense W once per
                       layer, reuse across many activation rows. Used when the
                       consumer GEMM is compute-bound (training/prefill).

Block sizes (bm, bn, bk, bj) are the TPU analogue of the paper's
<M, T_R, T_P, T_C>; the DSE in repro.hwmodel picks them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ovsf import next_pow2
from repro.hwmodel.tile_balance import SCOPED_VMEM


def _sign_tile(codes: jnp.ndarray, j0, k0, bk: int, seg: int,
               n_keep: int) -> jnp.ndarray:
    """(bk, bj) +-1 Hadamard tile S^T, with k on sublanes and j on lanes.

    Monolithic (seg == 0): S^T[k, j] = (-1)^popcount(idx[j] & (k0+k)).
    Segmented (seg == L0, a power of two): codes only touch their own
    length-L0 segment (block-diagonal basis, paper Alg. 1):
      S^T[k, j] = (-1)^popcount(idx[j] & ((k0+k) % L0))
                  * [j0+j in rows n_keep*seg(k0+k) .. n_keep*(seg(k0+k)+1)-1]
    Built entirely from iota + bitwise ops -- the on-chip OVSF generator.
    ``codes`` is the (1, bj) row of code ids. Every operand is a
    non-negative int32 below 2**31, so arithmetic shifts act as logical
    ones, and no integer division is needed.
    """
    bj = codes.shape[-1]
    ks = k0 + jax.lax.broadcasted_iota(jnp.int32, (bk, bj), 0)
    x = codes & (ks & (seg - 1) if seg else ks)
    x = x ^ (x >> 16)
    x = x ^ (x >> 8)
    x = x ^ (x >> 4)
    x = x ^ (x >> 2)
    x = x ^ (x >> 1)
    s = (1 - 2 * (x & 1)).astype(jnp.float32)
    if seg:
        js = j0 + jax.lax.broadcasted_iota(jnp.int32, (bk, bj), 1)
        first = (ks >> (seg.bit_length() - 1)) * n_keep
        s = jnp.where((js >= first) & (js < first + n_keep), s, 0.0)
    return s


def _unpack_tile(al_c: jnp.ndarray, quant: str) -> jnp.ndarray:
    """Quantised alpha chunk -> fp32 integer values (scales are applied to
    the sign tile instead, see ``_gen_w_tile``).

    int4 nibbles unpack as ``[low nibbles | high nibbles]`` along lanes, so
    each bn-wide tile comes out with its even columns first and its odd
    columns second; ``_untile_int4`` restores the order outside the kernel.
    """
    if quant == "int4":
        p32 = al_c.astype(jnp.int32)
        lo = p32 & 0xF
        lo = lo - jnp.where(lo >= 8, 16, 0)
        al_c = jnp.concatenate([lo, p32 >> 4], axis=-1)
    return al_c.astype(jnp.float32)


def _chunk_run(bk: int, bj: int, seg: int, n_keep: int, n_chunks: int
               ) -> int:
    """Alpha chunks the generator loop runs per k-block: all of them for
    monolithic codes (seg == 0), which touch every k. Segmented, a k-block
    meets only its own segments' ``bk // seg * n_keep`` alpha rows, which
    start at a multiple of ``g = gcd(rows, bj)`` and so span at most
    ``(bj - g + rows - 1) // bj + 1`` chunks of ``bj``."""
    if not seg:
        return n_chunks
    rows = bk // seg * n_keep
    g = math.gcd(rows, bj)
    return min(n_chunks, (bj - g + rows - 1) // bj + 1)


def _first_chunk(k, bk: int, bj: int, seg: int, n_keep: int, n_chunks: int):
    """The chunk the generator loop of k-block ``k`` starts at: the one
    holding the k-block's first alpha row, moved back so that its
    ``_chunk_run`` chunks end by the last one (the rows that brings in have
    all-zero sign tiles). Chunk 0 where the loop runs them all."""
    n_run = _chunk_run(bk, bj, seg, n_keep, n_chunks)
    if n_run == n_chunks:
        return 0
    return jnp.minimum(jax.lax.div(k * (bk // seg * n_keep), bj),
                       n_chunks - n_run)


def _gen_w_tile(idx_ref, alpha_ref, k: jnp.ndarray, *, bk: int,
                seg: int = 0, n_keep: int = 0, scale_ref=None,
                quant: str = "") -> jnp.ndarray:
    """Generate the (bk, bn) fp32 weight tile for k-block ``k``.

    ``idx_ref`` is (n_chunks, 1, bj) code ids and ``alpha_ref`` the
    (n_chunks, bj, bn_store) alpha block; the loop walks the leading chunk
    axis, which needs no tiled dynamic slice. With ``quant`` set,
    ``alpha_ref`` holds int8 (or int4-packed-in-int8) coefficients and
    ``scale_ref`` (n_chunks, 1, bj) their per-row fp32 scales, folded into
    the sign tile's columns right before the MXU contraction.

    Segmented codes bound the loop to ``_chunk_run`` chunks from
    ``_first_chunk``: every chunk skipped has an all-zero sign tile, so the
    tile is the one the full loop makes.
    """
    n_chunks, bj, _ = alpha_ref.shape
    bn = 2 * alpha_ref.shape[2] if quant == "int4" else alpha_ref.shape[2]
    k0 = k * bk
    n_run = _chunk_run(bk, bj, seg, n_keep, n_chunks)
    c0 = _first_chunk(k, bk, bj, seg, n_keep, n_chunks)

    def body(i, acc):
        c = c0 + i
        st = _sign_tile(idx_ref[c], c * bj, k0, bk, seg, n_keep)     # (bk, bj)
        al_c = alpha_ref[c]                                          # (bj, bn)
        if quant:
            st = st * scale_ref[c]
            al_c = _unpack_tile(al_c, quant)
        elif al_c.dtype != jnp.float32:
            st = st.astype(al_c.dtype)  # +-1 is exact; products stay exact
        return acc + _dot(st, al_c)

    return jax.lax.fori_loop(0, n_run, body,
                             jnp.zeros((bk, bn), jnp.float32))


def _dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """MXU matmul with fp32 accumulation; fp32 operands keep fp32 accuracy."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jnp.dot(a, b, precision=prec, preferred_element_type=jnp.float32)


def _row_scales(alpha_scale, J: int, bj: int) -> jnp.ndarray:
    """(n_seg,)/(n_seg,1) per-segment scales -> (n_chunks, 1, bj) per-row fp32.

    J fp32 values -- 1/d_out of the alpha buffer; negligible HBM traffic next
    to the int8 stream it describes."""
    s = jnp.asarray(alpha_scale, jnp.float32).reshape(-1)
    if s.shape[0] <= 0 or J % s.shape[0]:
        raise ValueError(
            f"alpha_scale has {s.shape[0]} segments; J={J} not divisible")
    rows = jnp.repeat(s, J // s.shape[0])
    return _pad1(rows, bj).reshape(-1, 1, bj)


def _untile_int4(out: jnp.ndarray, bn: int) -> jnp.ndarray:
    """Undo ``_unpack_tile``'s per-tile [even | odd] column order."""
    R, N = out.shape
    return out.reshape(R, N // bn, 2, bn // 2).swapaxes(2, 3).reshape(R, N)


# ---------------------------------------------------------------------------
# Fused on-the-fly GEMM (TiWGen)
# ---------------------------------------------------------------------------

# What the generator loop of each distinct ``ovsf_gemm`` call runs, noted
# when the call is traced (``gemm_notes``).
_GEMM_NOTES: dict = {}


def gemm_notes() -> list:
    """One dict per distinct ``ovsf_gemm`` call this process has traced:
    ``d_in``, ``d_out``, the code segment length ``seg`` (0: monolithic),
    the blocks ``bk`` and ``bj``, the alpha chunks ``nc`` and ``n_run``, the
    chunks the generator loop runs per k-block. A traced call is cached, so
    calls of one shape and blocks are noted once, however many there are."""
    return [dict(n) for n in _GEMM_NOTES.values()]


def _ovsf_gemm_kernel(idx_ref, x_ref, alpha_ref, *rest,
                      bk: int, nk: int, seg: int, n_keep: int,
                      quant: str = ""):
    if quant:
        scale_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
        scale_ref = None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_tile = _gen_w_tile(idx_ref, alpha_ref, k, bk=bk, seg=seg,
                         n_keep=n_keep, scale_ref=scale_ref,
                         quant=quant)                                  # (bk, bn)
    acc_ref[...] += _dot(x_ref[...].astype(jnp.float32), w_tile)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "block_j",
                     "alpha_dtype", "interpret"))
def ovsf_gemm(x: jnp.ndarray, alphas: jnp.ndarray, idx: jnp.ndarray, *,
              alpha_scale=None, alpha_dtype: str = "",
              block_m: int = 128, block_n: int = 128, block_k: int = 128,
              block_j: int = 128, interpret: bool = False) -> jnp.ndarray:
    """y = x @ W where W[k, n] = sum_j H[idx[j], k] * alphas[j, n].

    x: (M, d_in), alphas: (J, d_out) -> (M, d_out). idx: (J,) int32 for
    monolithic codes, or (n_seg, n_keep) for the segmented (Alg. 1) layout.
    Weight bytes read from HBM: J*d_out instead of d_in*d_out.

    With ``alpha_dtype`` = "int8"/"int4" the alphas operand is the quantised
    storage form ((J, d_out) int8 or (J, d_out//2) nibble-packed int8) and
    ``alpha_scale`` the per-segment scales; the generator loop dequantises
    each tile in-register right before its S^T @ alpha contraction, so the
    quantised bytes are all that streams from HBM -- fp32 alphas are never
    materialised.

    Compiled (not interpreted), the blocks must fit the chip's (8, 128)
    tiling: ``block_m`` a multiple of 8, ``block_k`` and ``block_n`` a
    multiple of 128 (256 for int4) unless they cover the whole dimension.
    ``runtime.mapper`` plans blocks that do.
    """
    quant = alpha_dtype
    if quant not in ("", "int8", "int4"):
        raise ValueError(f"ovsf_gemm: bad alpha_dtype {alpha_dtype!r}")
    if quant and alpha_scale is None:
        raise ValueError("ovsf_gemm: alpha_scale required for quantised alphas")
    M, d_in = x.shape
    J = alphas.shape[0]
    d_out = alphas.shape[1] * (2 if quant == "int4" else 1)
    seg, keep, idx, block_k = _code_layout(idx, d_in, block_k)
    bm = min(block_m, _ceil_mult(M, 8))
    bn = min(block_n, d_out)
    if quant == "int4" and bn % 2:
        bn += 1
    bk = min(block_k, d_in)
    bj = min(block_j, _ceil_mult(J, 8))
    bn_store = bn // 2 if quant == "int4" else bn

    xp = _pad2(x, bm, bk)
    alp = _pad2(alphas, bj, bn_store)
    Mp, Kp = xp.shape
    Jp, Np_store = alp.shape
    Np = Np_store * (2 if quant == "int4" else 1)
    nk = Kp // bk
    nc = Jp // bj
    note = dict(d_in=d_in, d_out=d_out, seg=seg, bk=bk, bj=bj, nc=nc,
                n_run=_chunk_run(bk, bj, seg, keep, nc))
    _GEMM_NOTES.setdefault(tuple(note.items()), note)

    operands = [_pad1(idx.astype(jnp.int32), bj).reshape(nc, 1, bj), xp,
                alp.reshape(nc, bj, Np_store)]
    in_specs = [
        pl.BlockSpec((nc, 1, bj), lambda m, n, k: (0, 0, 0)),     # idx (whole)
        pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),           # x
        pl.BlockSpec((nc, bj, bn_store), lambda m, n, k: (0, 0, n)),  # alphas
    ]
    if quant:
        operands.append(_row_scales(alpha_scale, J, bj))
        in_specs.append(pl.BlockSpec((nc, 1, bj), lambda m, n, k: (0, 0, 0)))

    vmem = gemm_vmem_bytes(bm, bk, bn, bj, J, x_dtype=x.dtype,
                           alpha_dtype=alphas.dtype, quant=quant)
    out = pl.pallas_call(
        functools.partial(_ovsf_gemm_kernel, bk=bk, nk=nk, seg=seg,
                          n_keep=keep, quant=quant),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        # (m, n) grid dims are independent output tiles; only the k-loop
        # carries the accumulator. Declaring this lets the Mosaic pipeline
        # parallelise/overlap across m/n while keeping k sequential.
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary"), vmem),
        interpret=interpret,
    )(*operands)
    if quant == "int4":
        out = _untile_int4(out, bn)
    return out[:M, :d_out]


# ---------------------------------------------------------------------------
# Weight-stationary decompression (generate once, reuse)
# ---------------------------------------------------------------------------

def _decompress_kernel(idx_ref, alpha_ref, *rest, bk: int,
                       seg: int, n_keep: int, quant: str = ""):
    if quant:
        scale_ref, o_ref = rest
    else:
        (o_ref,) = rest
        scale_ref = None
    k = pl.program_id(0)
    o_ref[...] = _gen_w_tile(idx_ref, alpha_ref, k, bk=bk, seg=seg,
                             n_keep=n_keep, scale_ref=scale_ref,
                             quant=quant).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("d_in", "block_n", "block_k", "block_j", "alpha_dtype",
                     "interpret"))
def ovsf_decompress(alphas: jnp.ndarray, idx: jnp.ndarray, *, d_in: int,
                    alpha_scale=None, alpha_dtype: str = "",
                    block_n: int = 256, block_k: int = 256, block_j: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Materialise dense W (d_in, d_out) from (J, d_out) alphas + code ids
    ((J,) monolithic or (n_seg, n_keep) segmented). Quantised alphas
    (``alpha_dtype`` int8/int4 + ``alpha_scale``) are dequantised tile-wise
    inside the generator loop, same epilogue as ``ovsf_gemm``."""
    quant = alpha_dtype
    if quant not in ("", "int8", "int4"):
        raise ValueError(f"ovsf_decompress: bad alpha_dtype {alpha_dtype!r}")
    if quant and alpha_scale is None:
        raise ValueError("ovsf_decompress: alpha_scale required")
    J = alphas.shape[0]
    d_out = alphas.shape[1] * (2 if quant == "int4" else 1)
    seg, keep, idx, block_k = _code_layout(idx, d_in, block_k)
    L = next_pow2(d_in)
    bk = min(block_k, L if not seg else d_in)
    bn = min(block_n, d_out)
    if quant == "int4" and bn % 2:
        bn += 1
    bj = min(block_j, _ceil_mult(J, 8))
    bn_store = bn // 2 if quant == "int4" else bn

    alp = _pad2(alphas, bj, bn_store)
    Jp, Np_store = alp.shape
    Np = Np_store * (2 if quant == "int4" else 1)
    Kp = _round_up(d_in, bk)
    nc = Jp // bj
    out_dtype = jnp.float32 if quant else alphas.dtype

    operands = [_pad1(idx.astype(jnp.int32), bj).reshape(nc, 1, bj),
                alp.reshape(nc, bj, Np_store)]
    in_specs = [
        pl.BlockSpec((nc, 1, bj), lambda k, n: (0, 0, 0)),
        pl.BlockSpec((nc, bj, bn_store), lambda k, n: (0, 0, n)),
    ]
    if quant:
        operands.append(_row_scales(alpha_scale, J, bj))
        in_specs.append(pl.BlockSpec((nc, 1, bj), lambda k, n: (0, 0, 0)))

    vmem = (2 * nc * _tile_bytes(bj, bn_store, alphas.dtype)
            + 2 * nc * _tile_bytes(1, bj, jnp.int32) * (2 if quant else 1)
            + 2 * _tile_bytes(bk, bn, out_dtype)
            + _gen_temp_bytes(bk, bj, bn))
    out = pl.pallas_call(
        functools.partial(_decompress_kernel, bk=bk, seg=seg,
                          n_keep=keep, quant=quant),
        grid=(Kp // bk, Np // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bk, bn), lambda k, n: (k, n)),
        out_shape=jax.ShapeDtypeStruct((Kp, Np), out_dtype),
        compiler_params=_compiler_params(("parallel", "parallel"), vmem),
        interpret=interpret,
    )(*operands)
    if quant == "int4":
        out = _untile_int4(out, bn)
    return out[:d_in, :d_out]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

# The most a kernel raises its VMEM limit to: what the chip holds, with room
# for the compiler's scratch.
MAX_SCOPED_VMEM = 100 * 2**20


def _compiler_params(semantics: tuple, vmem_bytes: int):
    """Mosaic parameters; raises the VMEM limit only where the estimate of
    the kernel's buffers does not fit the default ``SCOPED_VMEM``."""
    limit = None
    if vmem_bytes > SCOPED_VMEM * 3 // 4:
        limit = min(MAX_SCOPED_VMEM, _round_up(vmem_bytes * 3 // 2, 2**20))
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def gemm_vmem_bytes(bm: int, bk: int, bn: int, bj: int, J: int, *, x_dtype,
                    alpha_dtype, quant: str = "") -> int:
    """VMEM estimate of one ``ovsf_gemm`` grid step: x, the whole-J alpha
    column block and the code ids (two buffers each), the output block (two
    buffers), the accumulator and the generator's temporaries. The mapper
    budgets blocks with it; the kernel raises its VMEM limit from it."""
    nc = _ceil_mult(J, bj) // bj
    bn_store = bn // 2 if quant == "int4" else bn
    return (2 * _tile_bytes(bm, bk, x_dtype)
            + 2 * nc * _tile_bytes(bj, bn_store, alpha_dtype)
            + 2 * nc * _tile_bytes(1, bj, jnp.int32) * (2 if quant else 1)
            + 2 * _tile_bytes(bm, bn, x_dtype)
            + _tile_bytes(bm, bn, jnp.float32)
            + _gen_temp_bytes(bk, bj, bn))


def _tile_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a (rows, cols) buffer padded to whole (8k, 128) tiles."""
    it = jnp.dtype(dtype).itemsize
    sub = 8 * max(4 // it, 1)                # 8 rows fp32, 16 bf16, 32 int8
    return _round_up(rows, sub) * _round_up(cols, 128) * it


def _gen_temp_bytes(bk: int, bj: int, bn: int) -> int:
    """fp32 temporaries of one generator step: the sign tile, the unpacked
    alpha chunk, the loop carry and its update."""
    f32 = jnp.float32
    return (_tile_bytes(bk, bj, f32) + _tile_bytes(bj, bn, f32)
            + 2 * _tile_bytes(bk, bn, f32))


def _code_layout(idx: jnp.ndarray, d_in: int, block_k: int):
    """(seg, n_keep, flat idx, block_k) for monolithic (J,) or segmented
    (n_seg, n_keep) code ids; a segmented k-block covers whole segments."""
    if idx.ndim != 2:
        return 0, 0, idx, block_k
    ns, keep = idx.shape
    seg = d_in // ns
    if seg & (seg - 1):
        raise ValueError(f"segment length {seg} is not a power of two")
    if block_k % seg:
        block_k = max((block_k // seg) * seg, seg)
    return seg, keep, idx.reshape(-1), block_k


def _round_up(n: int, b: int) -> int:
    return ((n + b - 1) // b) * b


def _ceil_mult(n: int, b: int) -> int:
    """Smallest multiple of b >= n, used to derive a legal block <= requested."""
    return _round_up(max(n, 1), b)


def _pad2(a: jnp.ndarray, b0: int, b1: int) -> jnp.ndarray:
    p0 = (-a.shape[0]) % b0
    p1 = (-a.shape[1]) % b1
    if p0 or p1:
        a = jnp.pad(a, ((0, p0), (0, p1)))
    return a


def _pad1(a: jnp.ndarray, b0: int) -> jnp.ndarray:
    p0 = (-a.shape[0]) % b0
    if p0:
        a = jnp.pad(a, ((0, p0),))
    return a
