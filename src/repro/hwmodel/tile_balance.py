"""Static tile/padding balancer — the TPU adaptation of the paper's input
selective PEs (§4.3).

The FPGA mechanism lets idle PEs steal rows when C < T_C. The MXU is a rigid
128x128 systolic array: there is no dynamic steal, but the *objective* —
recover utilisation lost to dim/tile mismatch — is achieved statically by
choosing kernel block shapes (and mesh padding) that minimise
ceil-waste. utilisation(dim, block) = dim / (ceil(dim/block) * block).

The paper's Eq. (7) refined-runtime model is kept for analysis: it predicts
the ceiling recovery an input-selective design would get, which we report
next to the static recovery in benchmarks/table10_balance.py.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import numpy as np


BLOCK_MENU = (64, 128, 192, 256, 384, 512)
LANE = 128      # last block dim: a multiple of this, or the whole dim
SUBLANE = 8     # second-to-last block dim: a multiple of this, or whole
# VMEM a Pallas kernel gets when it asks for no limit: the compiler's default
# scoped limit on TPU v5e, the least of the supported chips. Blocks are
# planned to fit it; a kernel whose buffers need more raises its own limit.
SCOPED_VMEM = 16 * 2**20


def util(dim: int, block: int) -> float:
    import math
    return dim / (math.ceil(dim / block) * block)


def gemm_utilisation(M: int, K: int, N: int,
                     bm: int, bk: int, bn: int) -> float:
    return util(M, bm) * util(K, bk) * util(N, bn)


def legal_blocks(dim: int, menu: Sequence[int], align: int) -> list[int]:
    """Menu sizes the chip's (8, 128) tiling accepts for one block dim: the
    multiples of ``align``, plus the whole dim where it fits the menu."""
    out = [b for b in menu if b % align == 0]
    if dim <= max(menu) and dim not in out:
        out.append(dim)
    return out


@dataclasses.dataclass
class BalanceChoice:
    bm: int
    bk: int
    bn: int
    util_naive: float      # with the default 128^3 blocks
    util_balanced: float

    @property
    def speedup(self) -> float:
        return self.util_balanced / max(self.util_naive, 1e-9)


def balance_blocks(M: int, K: int, N: int, *,
                   menu: Sequence[int] = BLOCK_MENU,
                   vmem_limit: int = SCOPED_VMEM,
                   dtype_bytes: int = 2, n_align: int = LANE,
                   footprint: Optional[Callable[[int, int, int], int]] = None
                   ) -> BalanceChoice:
    """Pick (bm, bk, bn) maximising utilisation under a VMEM footprint.

    bm is a sublane dim (multiple of 8); bk and bn are lane dims (multiples
    of 128, or of ``n_align`` for bn, unless the block is the whole dim).
    ``footprint(bm, bk, bn)`` is the kernel's VMEM estimate; the default is
    the double-buffered x, W and output tiles."""
    if footprint is None:
        def footprint(bm, bk, bn):
            return (bm * bk + bk * bn + bm * bn) * dtype_bytes * 2
    naive = gemm_utilisation(M, K, N, 128, 128, 128)
    best = None        # ties keep the 128^3 default where it is legal
    if 128 in legal_blocks(N, menu, n_align) and footprint(128, 128, 128) \
            <= vmem_limit:
        best = (128, 128, 128, naive)
    for bm in legal_blocks(M, menu, SUBLANE):
        for bk in legal_blocks(K, menu, LANE):
            for bn in legal_blocks(N, menu, n_align):
                if footprint(bm, bk, bn) > vmem_limit:
                    continue
                u = gemm_utilisation(M, K, N, bm, bk, bn)
                if best is None or u > best[3] + 1e-12:
                    best = (bm, bk, bn, u)
    if best is None:
        raise ValueError(f"no legal blocks for ({M}, {K}, {N}) fit "
                         f"{vmem_limit} bytes of VMEM")
    return BalanceChoice(best[0], best[1], best[2], naive, best[3])


def input_selective_speedup(T_R: int, T_C: int, C: int, P: int, T_P: int
                            ) -> float:
    """Paper Eq. (7) vs the naive engine runtime: predicted gain of dynamic
    work-stealing for a layer with C output columns on a T_C-wide engine."""
    import math
    if C >= T_C:
        return 1.0
    t_naive = T_R * math.ceil(P / T_P)
    rows_stolen = max(T_R * C - (T_C - C) * (C + 1), 0)
    t_sel = ((T_C - C) + math.ceil(rows_stolen / T_C)) * math.ceil(P / T_P)
    return t_naive / max(t_sel, 1)
