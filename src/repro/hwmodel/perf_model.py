"""The paper's analytical performance model (§5) ported to TPU v5e constants.

The paper models each layer as a three-stage pipeline whose initiation
interval is the max of: input transfer, weights *generation*, engine compute,
output transfer (Eq. 5-8). On TPU the same decomposition holds per GEMM:

  t_mem   = (activation_in + alpha/weight + activation_out bytes) / HBM_bw
  t_wgen  = weights-generation FLOPs / peak  (0 for dense; the OVSF
            generation matmul or FWHT for on-the-fly layers)
  t_eng   = consumer GEMM FLOPs / peak

and II = max(...). The per-layer *bound class* {IFM, OFM, W(gen), C(ompute)}
drives the hardware-aware rho autotuning (§6.2): layers where W is NOT the
bound can afford a higher OVSF ratio for free.

This model reproduces the structure of the paper's Tables 1/4/5/6 with TPU
numbers and is cross-checked against the dry-run HLO analysis in tests.
"""
from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import numpy as np

from repro.core.ovsf import next_pow2


@dataclasses.dataclass(frozen=True)
class HW:
    """One hardware target for the analytical model (default: TPU v5e).

    Instances double as *HW targets* for the serving/mapper stack: each
    carries a ``name`` under which it can be registered (``register_hw``)
    and resolved (``hw_by_name``), so callers thread ``--hw v5p`` style
    strings instead of constructing constants.
    """
    peak_flops: float = 197e12        # bf16
    hbm_bw: float = 819e9             # B/s
    ici_bw: float = 50e9              # B/s per link
    hbm_bytes: float = 16e9
    vmem_bytes: float = 128 * 2**20
    vpu_flops: float = 197e12 / 8     # non-MXU elementwise throughput
    # Weights-generator unit. 0.0 -> generation timeshares the main unit
    # (TPU MXU: t_gen serialises into the engine stage). > 0 -> dedicated
    # pipelined generator at that peak (the paper's CNN-WGen vector unit,
    # ~7.5-11% of the DSPs per Table 9), overlapping per Eq. (8).
    wgen_flops: float = 0.0
    name: str = "v5e"

    def scaled_bw(self, factor: float) -> "HW":
        return dataclasses.replace(self, hbm_bw=self.hbm_bw * factor)


V5E = HW()

# TPU v5p: 459 TFLOP/s bf16, 95 GB HBM2e at 2765 GB/s, 6 ICI links at
# ~100 GB/s each (Google Cloud "TPU v5p system architecture").
V5P = HW(name="v5p", peak_flops=459e12, hbm_bw=2765e9, ici_bw=100e9,
         hbm_bytes=95e9, vmem_bytes=128 * 2**20, vpu_flops=459e12 / 8)

# TPU v6e (Trillium): 918 TFLOP/s bf16, 32 GB HBM at 1640 GB/s, 4 ICI
# links totalling ~3.58 Tbps one-way (Google Cloud "TPU v6e" docs).
V6E = HW(name="v6e", peak_flops=918e12, hbm_bw=1640e9, ici_bw=112e9,
         hbm_bytes=32e9, vmem_bytes=128 * 2**20, vpu_flops=918e12 / 8)

# Generic dual-socket AVX-512 server: ~2 TFLOP/s f32 across cores,
# ~100 GB/s sustained DDR5 (STREAM-like), 32 MiB LLC standing in for
# VMEM. Machine balance ~20 FLOP/B vs v5e's ~240, so mapper plans
# legitimately differ between the two targets.
CPU = HW(name="cpu", peak_flops=2e12, hbm_bw=100e9, ici_bw=0.0,
         hbm_bytes=256e9, vmem_bytes=32 * 2**20, vpu_flops=2e12)


# --- HW target registry (serving API surface: --hw v5e|v5p|v6e|cpu) --------

_HW_TARGETS: dict = {}


def register_hw(hw: HW) -> HW:
    """Register a target under ``hw.name`` (later wins, enabling overrides)."""
    _HW_TARGETS[hw.name] = hw
    return hw


for _hw in (V5E, V5P, V6E, CPU):
    register_hw(_hw)


def hw_names() -> tuple:
    return tuple(_HW_TARGETS)


def hw_by_name(name: str) -> HW:
    try:
        return _HW_TARGETS[name]
    except KeyError:
        raise KeyError(f"unknown HW target {name!r}; "
                       f"registered: {sorted(_HW_TARGETS)}") from None


def resolve_hw(hw) -> HW:
    """Accept an ``HW`` instance or a registered target name."""
    if isinstance(hw, HW):
        return hw
    return hw_by_name(hw)


# ``jax.Device.device_kind`` -> the preset of that device. A device that is
# not listed has no preset: that is an error, never a default.
DEVICE_KINDS = {
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v6 lite": "v6e",
}


def hw_for_device(device) -> HW:
    """The HW preset of a ``jax.Device``, looked up by its ``device_kind``."""
    try:
        return hw_by_name(DEVICE_KINDS[device.device_kind])
    except KeyError:
        raise KeyError(f"no HW preset for device kind {device.device_kind!r}; "
                       f"known kinds: {sorted(DEVICE_KINDS)}") from None


def check_hw_for_device(hw, device) -> None:
    """On a TPU, planning for any target but the device's own is an error."""
    if device.platform != "tpu":
        return
    want = hw_for_device(device).name
    if resolve_hw(hw).name != want:
        raise ValueError(f"--hw {resolve_hw(hw).name} does not match the "
                         f"device ({device.device_kind!r} is {want!r})")


BoundClass = Literal["IFM", "OFM", "W", "C"]


def padding_efficiency(valid_tokens: float, batch_tokens: float) -> float:
    """Valid tokens / batch tokens: THE padding-efficiency definition, shared
    by ``EngineStats``, the serving bench, and this model's wasted-FLOP term
    so the three never drift apart. 1.0 when the batch carried no padding
    (or nothing ran)."""
    return valid_tokens / batch_tokens if batch_tokens else 1.0


@dataclasses.dataclass(frozen=True)
class GemmLayer:
    """One weight application: y[M, d_out] = x[M, d_in] @ W."""
    name: str
    M: int                  # rows (tokens) per device
    d_in: int
    d_out: int
    rho: float = 1.0        # OVSF ratio; >= 1.0 -> dense layer
    ovsf: bool = False
    exec_path: str = "materialize"   # materialize | fused | spectral
    seg: int = 16           # code segment length L0 (0 = monolithic, Fig. 1)
    dtype_bytes: int = 2
    weight_resident: bool = False    # True if weights stay in VMEM across uses
    # paper Eq. (6): "alpha values transferred upfront" into the on-chip
    # Alpha buffer => no per-inference alpha traffic. True for the CNN
    # workloads (alphas fit BRAM/VMEM, checked by the caller); False for the
    # LM workloads where alphas stream from HBM each step.
    alphas_resident: bool = False
    # paper §4.1: dense weight tiles are re-transferred ceil(R/T_R) times
    # (output-stationary engine with BRAM too small to cache them). 1 on TPU
    # (weights read once per step); > 1 for the FPGA workloads. On-the-fly
    # generation removes this entire term — the paper's core win.
    weight_reread: int = 1
    # Storage dtype of the streamed alpha coefficients: "" (alphas in the
    # activation dtype, dtype_bytes each), "int8" (1 B), or "int4" (0.5 B
    # packed). Quantising the stored form shrinks the only HBM weight
    # traffic the fused path has left, raising the roofline of IFM-bound
    # rows (unzipFPGA / Petrica et al.).
    alpha_dtype: str = ""
    # KV-cache bytes this layer streams from HBM per step (attention score/
    # value reads against the cached context: 2 * M * kv_len * kv_width *
    # dtype_bytes, attached to the attn_o GEMM as the attention block's
    # memory stage). Per-token traffic — scales with valid rows like the
    # activations do, unlike the weight-side terms. A decode step at long
    # context is IFM-bound on exactly this term: the memory-wall analogue
    # of the paper's weight traffic, and what paging keeps dense (no dead
    # buffer tail is ever read — pages hold only live tokens).
    kv_bytes: float = 0.0
    # Valid rows out of M (0 = all M rows are real work). A padded serving
    # step carries dead rows — a decode slot inside a (B, W) window drags
    # W-1 padding columns through every GEMM — and the wasted-token term
    # prices that as the II this layer would shed at M = valid rows
    # (``LayerTiming.t_wasted``): token-proportional stages shrink with the
    # rows, weight-side stages do not, and the pipeline max arbitrates.
    m_valid: int = 0

    @property
    def valid_rows(self) -> int:
        return min(self.m_valid, self.M) if self.m_valid else self.M

    @property
    def wasted_row_frac(self) -> float:
        return 1.0 - padding_efficiency(self.valid_rows, self.M)

    @property
    def alpha_itemsize(self) -> float:
        """Bytes per stored alpha coefficient."""
        return {"": float(self.dtype_bytes),
                "int8": 1.0, "int4": 0.5}[self.alpha_dtype]

    @property
    def alpha_hbm_bytes(self) -> float:
        """Alpha-stream bytes per step: coefficients + per-segment fp32
        scales (the scales are J/n_keep values — noise next to the buffer,
        but modeled so int4's 8x claim stays honest)."""
        b = self.j_total * self.d_out * self.alpha_itemsize
        if self.alpha_dtype:
            b += (self.j_total // self.n_keep) * 4.0
        return b

    @property
    def L(self) -> int:
        """Code length: L0 for the segmented (Alg. 1) form."""
        if self.seg and self.d_in % self.seg == 0:
            return self.seg
        return next_pow2(self.d_in)

    @property
    def n_keep(self) -> int:
        return max(1, int(round(self.rho * self.L)))

    @property
    def j_total(self) -> int:
        """Total alpha rows = stored weights rows."""
        if self.seg and self.d_in % self.seg == 0:
            return (self.d_in // self.seg) * self.n_keep
        return self.n_keep


@dataclasses.dataclass
class LayerTiming:
    t_mem_in: float
    t_mem_w: float
    t_mem_out: float
    t_wgen: float
    t_eng: float
    pipelined_gen: bool = True   # False: gen timeshares the engine unit (TPU)
    # II seconds attributable to padding rows: this layer's ii minus the ii
    # of the identical layer at M = valid rows (GemmLayer.m_valid). 0 when
    # the batch is fully valid OR when a weight-side stage (per-weight, not
    # per-token) stays the bound either way — padding then costs nothing.
    t_wasted: float = 0.0

    @property
    def t_mem(self) -> float:
        return self.t_mem_in + self.t_mem_w + self.t_mem_out

    @property
    def ii(self) -> float:
        # paper Eq. (8): concurrent {input-transfer}, weight-gen, engine, out.
        # When generation shares the compute unit it serialises into t_eng.
        if self.pipelined_gen:
            return max(self.t_mem_in + self.t_mem_w, self.t_wgen, self.t_eng,
                       self.t_mem_out)
        return max(self.t_mem_in + self.t_mem_w, self.t_wgen + self.t_eng,
                   self.t_mem_out)

    @property
    def bound(self) -> BoundClass:
        stages = {"IFM": self.t_mem_in + self.t_mem_w, "W": self.t_wgen,
                  "C": self.t_eng, "OFM": self.t_mem_out}
        return max(stages, key=stages.get)  # type: ignore[arg-type]


def layer_timing(layer: GemmLayer, hw: HW = V5E) -> LayerTiming:
    M, di, do = layer.M, layer.d_in, layer.d_out
    by = layer.dtype_bytes
    t_in = (M * di * by + layer.kv_bytes) / hw.hbm_bw
    t_out = M * do * by / hw.hbm_bw
    t_eng = 2.0 * M * di * do / hw.peak_flops
    t_w = 0.0
    t_gen = 0.0
    pipelined = True
    if not layer.ovsf:
        if not layer.weight_resident:
            t_w = layer.weight_reread * di * do * by / hw.hbm_bw
    else:
        J = layer.j_total                       # stored alpha rows (rho*d_in)
        gen_macs_per_w = layer.n_keep           # rho*L0 MACs per weight elem
        gen_peak = hw.wgen_flops or hw.peak_flops
        pipelined = hw.wgen_flops > 0
        if not layer.alphas_resident:
            t_w = layer.alpha_hbm_bytes / hw.hbm_bw  # alphas only cross HBM
        if layer.exec_path == "spectral":
            # per-seg FWHT on activations (VPU, overlaps the MXU) +
            # rho-smaller GEMM on the MXU
            t_gen = M * di * max(np.log2(max(layer.L, 2)), 1) / hw.vpu_flops
            t_eng = 2.0 * M * J * do / hw.peak_flops
            t_in = M * di * by / hw.hbm_bw      # reads x, writes/read x_hat
            pipelined = True
        elif layer.exec_path == "fused":
            # per-tile S^T @ alpha (regenerated once per M-tile here)
            t_gen = 2.0 * gen_macs_per_w * di * do / gen_peak
        else:  # materialize: dense W round-trips HBM (generate, write, reread)
            t_gen = 2.0 * gen_macs_per_w * di * do / gen_peak
            t_w += 2.0 * di * do * by / hw.hbm_bw
    t = LayerTiming(t_in, t_w, t_out, t_gen, t_eng, pipelined)
    if layer.m_valid and layer.valid_rows < M:
        # kv_bytes is per-token traffic: the ideal step at valid rows reads
        # proportionally less cached context, like the activations
        ideal = layer_timing(
            dataclasses.replace(layer, M=layer.valid_rows, m_valid=0,
                                kv_bytes=layer.kv_bytes * layer.valid_rows
                                / M), hw)
        t.t_wasted = max(t.ii - ideal.ii, 0.0)
    return t


def model_layers(cfg, shape, *, n_devices: int = 256, tp: int = 16,
                 m_valid: int = 0, kv_len: int = 0) -> list[GemmLayer]:
    """Expand a ModelConfig x ShapeConfig into per-device GEMM workloads.

    Decode: M = batch/dp tokens; train/prefill: M = batch*seq/dp. TP divides
    d_out (column-parallel) or d_in (row-parallel) per Megatron convention.
    ``m_valid`` marks how many of the M token rows are real work (0 = all):
    a padded serving step models as M = batch tokens with m_valid = valid
    tokens, pricing the dead rows (``LayerTiming.t_wasted``). ``kv_len``
    is the mean cached context length each token row attends over; it
    attaches the per-step KV-read bytes to each attention block's output
    GEMM (``GemmLayer.kv_bytes``), growing the modeled II as the context
    grows — the serving memory wall the perf model must price.
    """
    dp = max(n_devices // tp, 1)
    if shape.kind == "decode":
        M = max(shape.global_batch // dp, 1)
    else:
        M = max(shape.global_batch * shape.seq_len // dp, 1)
    o = cfg.ovsf
    ex = o.exec_path if o.enable else "materialize"
    # m_valid is a GLOBAL token count like global_batch: shard it over dp
    # the same way M was, so the per-device wasted fraction matches the
    # global one instead of clamping to "no waste" whenever dp > 1.
    mv = min(max(m_valid // dp, 1), M) if m_valid else 0

    def mk(name, d_in, d_out, group):
        rho = o.rho_for(name) if (o.enable and group in o.targets
                                  and min(d_in, d_out) >= o.min_dim) else 1.0
        seg = o.seg_len if (o.seg_len and d_in % max(o.seg_len, 1) == 0) else 0
        is_ovsf = o.enable and rho < 1.0
        return GemmLayer(name, M, d_in, d_out, rho=rho,
                         ovsf=is_ovsf, exec_path=ex, seg=seg,
                         alpha_dtype=o.alpha_dtype if is_ovsf else "",
                         m_valid=mv)

    d, hd = cfg.d_model, cfg.hd
    # KV bytes per attention block per step: each of the M rows reads the
    # cached K AND V (hence 2x) across kv_len positions at the per-device
    # KV width. Attached to attn_o — the GEMM the attention outputs feed.
    kv_by = (2.0 * M * kv_len * max(cfg.n_kv_heads * hd // tp, hd) * 2
             if kv_len else 0.0)
    layers: list[GemmLayer] = []
    for i in range(cfg.n_layers):
        if cfg.n_heads:
            layers += [
                mk(f"L{i}/attn_q", d, cfg.n_heads * hd // tp, "attn"),
                mk(f"L{i}/attn_k", d, max(cfg.n_kv_heads * hd // tp, hd), "attn"),
                mk(f"L{i}/attn_v", d, max(cfg.n_kv_heads * hd // tp, hd), "attn"),
                dataclasses.replace(
                    mk(f"L{i}/attn_o", cfg.n_heads * hd // tp, d, "attn"),
                    kv_bytes=kv_by),
            ]
        if cfg.n_experts:
            # routed experts: per token top_k experts touched; per device the
            # expert weights read are min(E/tp, tokens*top_k) experts' worth
            eff = min(cfg.n_experts // tp,
                      max(M * cfg.top_k // max(cfg.n_experts // tp, 1), 1))
            for nm in ("gate", "up"):
                l = mk(f"L{i}/expert_{nm}", d, cfg.d_ff, "expert")
                layers.append(dataclasses.replace(
                    l, M=M * cfg.top_k // max(cfg.n_experts // tp, 1) or 1,
                    name=l.name + f"x{cfg.n_experts // tp}"))
            l = mk(f"L{i}/expert_down", cfg.d_ff, d, "expert")
            layers.append(dataclasses.replace(
                l, M=M * cfg.top_k // max(cfg.n_experts // tp, 1) or 1))
        elif cfg.d_ff:
            f = cfg.d_ff // tp
            if cfg.mlp_gated:
                layers.append(mk(f"L{i}/mlp_gate", d, f, "mlp"))
            layers += [mk(f"L{i}/mlp_up", d, f, "mlp"),
                       mk(f"L{i}/mlp_down", f, d, "mlp")]
        if cfg.ssm_state:
            di = cfg.d_inner // tp
            layers += [mk(f"L{i}/ssm_in", d, 2 * di, "mlp"),
                       mk(f"L{i}/ssm_out", di, d, "mlp")]
    return layers


@dataclasses.dataclass
class ModelTiming:
    layers: list
    timings: list
    total_s: float
    bounds: dict
    wasted_s: float = 0.0        # II seconds attributable to padding rows
                                 # (total_s minus the same step at valid M)

    @property
    def step_efficiency(self) -> float:
        """1 - wasted/total in (0, 1]: how much of the modeled step was real
        work (each layer's waste is bounded by its own II)."""
        return 1.0 - (self.wasted_s / self.total_s if self.total_s else 0.0)

    def bound_of(self, name: str) -> BoundClass:
        for l, t in zip(self.layers, self.timings):
            if l.name == name:
                return t.bound
        raise KeyError(name)


def model_timing(layers: list[GemmLayer], hw: HW = V5E) -> ModelTiming:
    ts = [layer_timing(l, hw) for l in layers]
    bounds: dict = {}
    for l, t in zip(layers, ts):
        bounds[l.name] = t.bound
    return ModelTiming(layers, ts, sum(t.ii for t in ts), bounds,
                       wasted_s=sum(t.t_wasted for t in ts))


def serve_step_timing(cfg, *, valid_tokens: int, batch_tokens: int,
                      hw: HW = V5E, n_devices: int = 1, tp: int = 1,
                      kv_len: int = 0) -> ModelTiming:
    """Model one serving step that batches ``batch_tokens`` rows of which
    ``valid_tokens`` are real work — the padded (B, W) window step vs its
    token-packed replacement, priced on the same analytical model the
    mapper uses. ``ShapeConfig`` is decode-kind with the batch-token count
    as the per-step row dimension. ``kv_len`` adds the KV-cache read bytes
    each row streams against its cached context."""
    from repro.configs.base import ShapeConfig
    shape = ShapeConfig("serve_step", 1, batch_tokens, "decode")
    layers = model_layers(cfg, shape, n_devices=n_devices, tp=tp,
                          m_valid=valid_tokens, kv_len=kv_len)
    return model_timing(layers, hw)


def throughput(layers: list[GemmLayer], hw: HW = V5E,
               tokens_per_step: float = 1.0) -> float:
    """Steps (or inferences) per second under the II pipeline model."""
    mt = model_timing(layers, hw)
    return tokens_per_step / mt.total_s if mt.total_s > 0 else float("inf")
