"""Hardware-aware layer mapper: per-layer OVSF execution-path dispatch.

This is the TPU port of the paper's automated hardware-aware methodology
(unzipFPGA §5, Table 1): given the CNN/LM-device pair, decide *per layer*
how the weights-generation mechanism should run, instead of hardcoding one
regime for the whole network. Paper terminology -> this implementation:

  paper §5 concept                      here
  ------------------------------------  ------------------------------------
  per-layer on-the-fly vs pre-gen       ``LayerPlan.path`` in {``fused``
  weights (GenConv on/off)              (TiWGen, generate-in-tile),
                                        ``materialize`` (pre-generate dense W),
                                        ``spectral`` (beyond-paper, opt-in)}
  DSE over <M, T_R, T_P, T_C>           block-size search over Pallas tiles
  (§5.3)                                ``(bm, bn, bk, bj)`` via
                                        ``hwmodel.tile_balance.balance_blocks``
  roofline bound classification         ``hwmodel.perf_model.layer_timing``
  (Eq. 5-8, {IFM, OFM, W, C})           -> ``LayerTiming.bound``
  weights kept on-chip across reuse     ``LayerPlan.cache_weights`` — generate
  (weight-stationary dataflow, §4.2.1)  dense W once, reuse across rows/steps
                                        (``kernels.ops`` decompress cache)

Mapper decisions are **pure functions of (layer shape, rho, HW)**: no device
probing, no RNG, no global state — the same inputs always give the same plan,
so plans are hashable (frozen dataclasses of tuples) and can ride inside a
``ModelConfig`` through jit-closed closures.

Default candidate paths are the paper's two regimes (``fused`` vs
``materialize``).  The beyond-paper ``spectral`` path (activation-domain
transform) is opt-in via ``paths=`` because it reshapes the dataflow of the
consumer GEMM rather than the generator, and its win profile overlaps with
``fused`` on decode shapes. Expert banks are the exception: they have no
fused kernel, so ``plan_model`` weighs ``materialize`` against ``spectral``
for them.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional, Sequence

import jax.numpy as jnp

from repro.hwmodel import perf_model as pm
from repro.hwmodel import tile_balance as tb
from repro.kernels.ovsf_gemm import gemm_vmem_bytes


DEFAULT_PATHS = ("materialize", "fused")
ALL_PATHS = ("materialize", "fused", "spectral")


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """Execution plan for one OVSF GEMM: path + Pallas blocks + cache policy."""
    path: str                       # materialize | fused | spectral
    block_m: int = 128
    block_n: int = 128
    block_k: int = 128
    block_j: int = 128
    cache_weights: bool = False     # weight-stationary: decompress once, reuse
    cache_key: str = ""             # identity key for the decompress cache
    bound: str = "C"                # roofline bound class at decision time
    ii_s: float = 0.0               # modeled initiation interval (seconds)
    alpha_dtype: str = ""           # alpha storage dtype the plan was modeled
                                    # under ("" fp / "int8" / "int4")


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Per-weight-type plans for a whole model (hashable, jit-closure safe)."""
    entries: tuple[tuple[str, LayerPlan], ...] = ()
    hw_label: str = "v5e"

    def plan_for(self, name: str) -> Optional[LayerPlan]:
        """Longest-substring match so 'mlp_up' resolves 'L3/mlp_up' etc."""
        best: Optional[LayerPlan] = None
        best_len = -1
        for pat, lp in self.entries:
            if pat == name:
                return lp
            if pat in name and len(pat) > best_len:
                best, best_len = lp, len(pat)
        return best

    def names(self) -> tuple[str, ...]:
        return tuple(n for n, _ in self.entries)


# ---------------------------------------------------------------------------
# Single-GEMM classification
# ---------------------------------------------------------------------------

def _candidate_ii(layer: pm.GemmLayer, path: str, hw: pm.HW, *,
                  weight_reuse: int, block_m: int) -> tuple[float, str]:
    """Modeled II + bound for one (layer, path) candidate.

    Refines ``pm.layer_timing`` with the two costs the runtime actually pays:
      - fused regenerates each weight tile once per M-tile of the Pallas grid
        (the TiWGen kernel has no cross-m-tile reuse), so t_wgen scales with
        ceil(M / bm);
      - materialize with an active decompress cache amortises generation and
        the dense-W write over ``weight_reuse`` invocations (serving decode:
        params are frozen, so reuse is effectively unbounded).
    """
    l = dataclasses.replace(layer, exec_path=path)
    t = pm.layer_timing(l, hw)
    if path == "fused":
        m_tiles = max(math.ceil(layer.M / max(block_m, 1)), 1)
        t = dataclasses.replace(t, t_wgen=t.t_wgen * m_tiles)
    elif path == "materialize" and weight_reuse > 1:
        by = layer.dtype_bytes
        dense_read = layer.d_in * layer.d_out * by / hw.hbm_bw
        alpha_read = 0.0 if layer.alphas_resident else \
            layer.alpha_hbm_bytes / hw.hbm_bw
        t = dataclasses.replace(
            t,
            t_wgen=t.t_wgen / weight_reuse,
            # steady state: read the cached dense W once; alphas only touched
            # on regeneration (params changed), amortised away.
            t_mem_w=dense_read + alpha_read / weight_reuse)
    return t.ii, t.bound


def classify_gemm(M: int, d_in: int, d_out: int, rho: float, *,
                  seg: int = 16, hw=pm.V5E, name: str = "gemm",
                  weight_reuse: int = 1,
                  paths: Sequence[str] = DEFAULT_PATHS,
                  alphas_resident: bool = False,
                  alpha_dtype: str = "",
                  calibration=None) -> LayerPlan:
    """Map one OVSF GEMM y[M, d_out] = x[M, d_in] @ W(alphas) to a plan.

    Pure in (shape, rho, hw, weight_reuse): evaluates each candidate path
    under the analytical model and picks the minimum-II one. First listed
    wins ties: materialize precedes fused so tiny output-bound layers keep
    the simple pre-generated dataflow, and fused precedes spectral so
    decode-shaped alpha-bandwidth ties resolve to the paper-faithful TiWGen
    path (on memory-bound decode, fused's alpha-only HBM traffic beats
    materialize's dense-W read strictly, by the 1/rho compression factor).
    ``weight_reuse`` is how many invocations see the same alphas (1 for
    training; the steps-per-request scale for frozen serving params).
    ``hw`` is an ``pm.HW`` instance or a registered target name
    (``"v5e"``/``"v5p"``/``"v6e"``/``"cpu"``).

    ``calibration`` (a ``runtime.calibrate.CalibrationTable``) closes the
    measured-vs-modeled loop: each candidate's modeled II is multiplied by
    the table's relative correction factor for ``(name, path, hw.name)``
    before the minimum is taken, so serving-measured skew re-ranks paths on
    the next planning pass (unmeasured candidates keep factor 1.0).

    ``alpha_dtype`` ("int8"/"int4") models the quantised alpha stream —
    halved/quartered t_mem_w for every path that reads alphas from HBM, so
    fused-int8 can clear an IFM bound that fused-fp left standing.
    """
    hw = pm.resolve_hw(hw)
    if seg and d_in % seg:
        seg = 0
    layer = pm.GemmLayer(name, M=M, d_in=d_in, d_out=d_out, rho=min(rho, 1.0),
                         ovsf=rho < 1.0, seg=seg,
                         alphas_resident=alphas_resident,
                         alpha_dtype=alpha_dtype if rho < 1.0 else "")
    if not layer.ovsf:
        blocks = tb.balance_blocks(M, d_in, d_out)
        t = pm.layer_timing(layer, hw)
        return LayerPlan("materialize", block_m=blocks.bm, block_n=blocks.bn,
                         block_k=blocks.bk, cache_weights=False,
                         cache_key=name, bound=t.bound, ii_s=t.ii)

    best_path, best_ii, best_bound = None, float("inf"), "C"
    for path in paths:
        ii, bound = _candidate_ii(layer, path, hw, weight_reuse=weight_reuse,
                                  block_m=128)
        if calibration is not None:
            ii *= calibration.factor(name, path, hw.name)
        if ii < best_ii:
            best_path, best_ii, best_bound = path, ii, bound
    if best_path is None:
        raise RuntimeError(
            f"mapper: no viable execution path for layer {name!r} "
            f"(candidates considered: {list(paths)}) — every candidate "
            f"produced a non-finite modeled II; check the perf model / "
            f"calibration factors for hw={hw.name!r}")

    # DSE block search over the consumer GEMM of the chosen path, in blocks
    # the chip's tiling accepts and the fused kernel's VMEM estimate fits
    # (int4 packs two columns a byte: its stored lane block is bn // 2). The
    # spectral path contracts over J (= rho * d_in) instead of d_in.
    k_eff = layer.j_total if best_path == "spectral" else d_in
    bj = min(128, _ceil8(layer.j_total))
    quant = layer.alpha_dtype
    store = {"": jnp.bfloat16, "int8": jnp.int8, "int4": jnp.int8}[quant]

    def footprint(bm, bk, bn):
        return gemm_vmem_bytes(bm, bk, bn, bj, layer.j_total,
                               x_dtype=jnp.bfloat16, alpha_dtype=store,
                               quant=quant)

    blocks = tb.balance_blocks(
        M, k_eff, d_out, n_align=2 * tb.LANE if quant == "int4" else tb.LANE,
        footprint=footprint if best_path == "fused" else None)
    bk = blocks.bk
    if seg and bk % seg:
        bk = max((bk // seg) * seg, seg)
    return LayerPlan(best_path, block_m=blocks.bm, block_n=blocks.bn,
                     block_k=bk, block_j=bj,
                     cache_weights=best_path == "materialize",
                     cache_key=name, bound=best_bound, ii_s=best_ii,
                     alpha_dtype=alpha_dtype)


def _ceil8(n: int) -> int:
    return ((max(n, 1) + 7) // 8) * 8


# ---------------------------------------------------------------------------
# Whole-model planning (LM stacks)
# ---------------------------------------------------------------------------

_LAYER_PREFIX = re.compile(r"^L\d+/")
# perf_model names a GEMM that runs once per expert with a multiplicity
# suffix ("expert_gatex64"); the model code passes the bare weight type.
_MULTIPLICITY = re.compile(r"x\d+$")

# Candidate paths of an OVSF target group whose model code runs fewer than
# the trunk: an expert bank (models.moe) has no per-expert fused kernel, so
# its choice is between decompressing the bank and the activation-domain
# contraction against its alphas.
_GROUP_PATHS = {"expert": ("materialize", "spectral")}

# perf_model workload names -> the weight-type names the model code passes to
# linear_apply (ssm.py registers its projections under the "mlp" OVSF target
# group, so its dispatch names differ from the roofline workload names).
_WTYPE_ALIASES = {"ssm_in": "mlp_in", "ssm_out": "mlp_out"}


def plan_model(cfg, shape, *, hw=pm.V5E, n_devices: int = 1,
               tp: int = 1, paths: Sequence[str] = DEFAULT_PATHS,
               weight_reuse: Optional[int] = None,
               calibration=None) -> ExecutionPlan:
    """Emit an ExecutionPlan for a ModelConfig under a workload shape.

    Expands the config into per-device GEMMs via ``pm.model_layers``,
    collapses them by weight type (transformer stacks are layer-homogeneous
    and scanned, so one plan per weight type), and classifies each with
    ``classify_gemm`` among ``paths``, or among the paths its group's model
    code runs (``_GROUP_PATHS``: expert banks). ``weight_reuse`` defaults by workload kind: decode
    serves frozen params (high reuse), train regenerates every step.
    ``hw`` accepts any registered HW target name (see ``pm.hw_by_name``)
    or an ``pm.HW`` instance; the emitted plan is stamped with its name.
    ``calibration`` threads a measured-vs-modeled correction table
    (``runtime.calibrate.CalibrationTable``) into every classification.
    """
    hw = pm.resolve_hw(hw)
    if weight_reuse is None:
        weight_reuse = 1 if shape.kind == "train" else 256
    layers = pm.model_layers(cfg, shape, n_devices=n_devices, tp=tp)
    entries: list[tuple[str, LayerPlan]] = []
    seen: set[str] = set()
    for l in layers:
        if not l.ovsf:
            continue
        wtype = _MULTIPLICITY.sub("", _LAYER_PREFIX.sub("", l.name))
        wtype = _WTYPE_ALIASES.get(wtype, wtype)
        if wtype in seen:
            continue
        seen.add(wtype)
        entries.append((wtype, classify_gemm(
            l.M, l.d_in, l.d_out, l.rho, seg=l.seg, hw=hw, name=wtype,
            weight_reuse=weight_reuse,
            paths=_GROUP_PATHS.get(wtype.split("_")[0], paths),
            alpha_dtype=l.alpha_dtype, calibration=calibration)))
    return ExecutionPlan(tuple(entries), hw_label=hw.name)


def apply_plan(cfg, plan: ExecutionPlan):
    """Return a ModelConfig carrying the plan (consumed by linear_apply)."""
    return cfg.replace(exec_plan=plan)


def plan_and_apply(cfg, shape, **kw):
    return apply_plan(cfg, plan_model(cfg, shape, **kw))


def suggest_rhos(cfg, shape, *, hw=pm.V5E, n_devices: int = 1,
                 tp: int = 1, slack: float = 1.0):
    """Hardware-aware rho autotuning (paper §6.2) for the same workload the
    mapper plans: raise each layer's OVSF ratio while generation stays off
    the critical path. Returns ``hwmodel.autotune.TuneResult``; feed the
    resulting per-layer rhos back into ``OVSFConfig.rho_overrides`` and
    re-plan."""
    from repro.hwmodel.autotune import autotune_rhos
    layers = pm.model_layers(cfg, shape, n_devices=n_devices, tp=tp)
    return autotune_rhos(layers, pm.resolve_hw(hw), slack=slack)


# ---------------------------------------------------------------------------
# CNN planning (im2col GEMMs through the same engine, paper §4.1)
# ---------------------------------------------------------------------------

def plan_cnn(cfg, *, batch: int = 1, hw=pm.V5E,
             paths: Sequence[str] = DEFAULT_PATHS,
             weight_reuse: int = 256) -> ExecutionPlan:
    """Plans for a CNNConfig: each OVSF conv is an im2col GEMM with
    R = B*H'*W' rows and P = Cin*K*K contraction (§4.1 mapping)."""
    hw = pm.resolve_hw(hw)
    entries: list[tuple[str, LayerPlan]] = []
    if cfg.depth == "squeezenet":
        specs = _squeezenet_convs(cfg)
    else:
        specs = _resnet_convs(cfg)
    for name, c_in, c_out, k, stride, rho, hw_cur in specs:
        if rho >= 1.0 or k < 3:
            continue
        M = batch * hw_cur * hw_cur
        fan_in = c_in * k * k
        entries.append((name, classify_gemm(
            M, fan_in, c_out, rho, seg=0, hw=hw, name=name,
            weight_reuse=weight_reuse, paths=paths)))
    return ExecutionPlan(tuple(entries), hw_label=hw.name)


def _resnet_convs(cfg):
    from repro.models.cnn import _resnet_layers
    hw_cur = cfg.in_hw
    out = []
    for d in _resnet_layers(cfg):
        if d["name"] == "head":
            continue
        hw_cur = max(hw_cur // max(d["stride"], 1), 1)
        if d["name"] == "stem":
            hw_cur = max(hw_cur // 2, 1)          # stem maxpool
        out.append((d["name"], d["c_in"], d["c_out"], d["k"], d["stride"],
                    d["rho"], hw_cur))
    return out


def _squeezenet_convs(cfg):
    from repro.models.cnn import _FIRE
    wm = cfg.width_mult
    hw_cur = max(cfg.in_hw // 4, 1)               # stem stride-2 + maxpool
    out = []
    c_prev = max(8, int(64 * wm))
    for i, (sq, e1, e3, stage) in enumerate(_FIRE):
        sq, e1, e3 = (max(4, int(v * wm)) for v in (sq, e1, e3))
        out.append((f"f{i}e3", sq, e3, 3, 1, cfg.block_rhos[stage], hw_cur))
        c_prev = e1 + e3
        if i in {1, 3}:
            hw_cur = max(hw_cur // 2, 1)
    return out
