"""Paper §5 performance model + §6.2 autotuning + §4.3 balancer tests."""
import collections
import dataclasses

import pytest

from repro.configs import SHAPES, get_config
from repro.hwmodel import autotune, dse, perf_model as pm, tile_balance as tb


def test_bound_classification_regimes():
    # tiny M (decode) with big dense weights -> weight-read (IFM) bound
    small = pm.GemmLayer("dec", M=8, d_in=4096, d_out=4096)
    assert pm.layer_timing(small).bound == "IFM"
    # huge M -> compute bound
    big = pm.GemmLayer("train", M=2 ** 18, d_in=4096, d_out=4096)
    assert pm.layer_timing(big).bound == "C"


def test_ovsf_cuts_weight_bytes():
    dense = pm.GemmLayer("l", M=8, d_in=4096, d_out=4096)
    o = dataclasses.replace(dense, ovsf=True, rho=0.25, exec_path="spectral")
    td, to = pm.layer_timing(dense), pm.layer_timing(o)
    assert to.t_mem_w < 0.3 * td.t_mem_w
    assert to.ii < td.ii          # decode layer gets faster


def test_materialize_pays_hbm_roundtrip_at_decode():
    """Honest adaptation note: materialising dense W per step round-trips
    HBM, so at decode it is WORSE than dense; fused/spectral are the decode
    answers (segmented generation itself is cheap: rho*L0 MACs/weight)."""
    mk = lambda path, ov: pm.GemmLayer("l", M=8, d_in=4096, d_out=4096,
                                       ovsf=ov, rho=0.5, exec_path=path,
                                       seg=16)
    t_dense = pm.layer_timing(mk("materialize", False)).ii
    t_mat = pm.layer_timing(mk("materialize", True)).ii
    t_fused = pm.layer_timing(mk("fused", True)).ii
    t_spec = pm.layer_timing(mk("spectral", True)).ii
    assert t_mat > t_dense            # round-trip costs more than it saves
    assert t_fused < 0.7 * t_dense    # TiWGen: ~rho x weight bytes
    assert t_spec < 0.7 * t_dense


def test_bandwidth_scaling_shifts_bounds():
    """Paper Table 1: lower bandwidth pushes layers to memory-bound."""
    l = pm.GemmLayer("l", M=2048, d_in=2048, d_out=2048)
    fast = pm.layer_timing(l, pm.V5E.scaled_bw(8.0))
    slow = pm.layer_timing(l, pm.V5E.scaled_bw(1 / 8))
    assert fast.bound == "C"
    assert slow.bound in ("IFM", "OFM")


def test_autotune_rhos_only_increase_and_timing_not_worse():
    cfg = get_config("qwen2_5_14b")
    cfg = cfg.replace(ovsf=dataclasses.replace(cfg.ovsf, rho=0.25))
    layers = pm.model_layers(cfg, SHAPES["train_4k"], n_devices=256, tp=16)[:20]
    res = autotune.autotune_rhos(layers)
    for l in layers:
        if l.ovsf:
            assert res.rhos[l.name] >= l.rho - 1e-9
    assert res.tuned_total_s <= res.baseline_total_s * (1 + 1e-6)


def test_autotune_never_creates_wgen_bound():
    cfg = get_config("qwen2_5_14b")
    cfg = cfg.replace(ovsf=dataclasses.replace(cfg.ovsf, rho=0.125))
    layers = pm.model_layers(cfg, SHAPES["train_4k"], n_devices=256, tp=16)[:12]
    res = autotune.autotune_rhos(layers, pm.V5E.scaled_bw(0.25))
    for name, rho in res.rhos.items():
        if rho < 1.0:
            assert res.bounds[name] != "W", (name, rho, res.bounds[name])


def test_model_layers_counts():
    cfg = get_config("tinyllama_1_1b")
    layers = pm.model_layers(cfg, SHAPES["train_4k"], n_devices=256, tp=16)
    # 4 attn + 3 mlp per layer
    assert len(layers) == cfg.n_layers * 7


def test_kv_read_bytes_grow_modeled_ii():
    # the serving memory wall: decode II must grow with cached context
    cfg = get_config("tinyllama_1_1b")
    ts = [pm.serve_step_timing(cfg, valid_tokens=8, batch_tokens=8,
                               kv_len=L).total_s for L in (0, 512, 8192)]
    assert ts[0] < ts[1] < ts[2]
    # the KV traffic lands on the attention block, not the MLP
    layers = pm.model_layers(cfg, SHAPES["decode_32k"], n_devices=1, tp=1,
                             kv_len=4096)
    kv = {l.name: l.kv_bytes for l in layers}
    assert all(b > 0 for n, b in kv.items() if n.endswith("attn_o"))
    assert all(b == 0 for n, b in kv.items() if "attn_o" not in n)
    # per-token traffic: wasted-row accounting scales it with valid rows
    t_pad = pm.layer_timing(dataclasses.replace(
        layers[3], M=8, m_valid=2), pm.V5E)
    assert t_pad.t_wasted > 0


def test_tile_balancer_improves_ragged_gemm():
    # C=192 on 128-blocks wastes 25% of the N dim; menu should recover it
    ch = tb.balance_blocks(M=1024, K=4096, N=192)
    assert ch.util_balanced >= ch.util_naive
    assert ch.util_balanced > 0.99
    assert ch.bn in (64, 192)


def test_input_selective_model_bounds():
    # paper reports up to ~1.2x; model should stay in a sane band
    g = tb.input_selective_speedup(T_R=64, T_C=128, C=64, P=1024, T_P=64)
    assert 1.0 <= g <= 2.1
    assert tb.input_selective_speedup(64, 128, 128, 1024, 64) == 1.0


def test_hw_preset_follows_device_kind():
    Dev = collections.namedtuple("Dev", "platform device_kind")
    v5e = Dev("tpu", "TPU v5 lite")
    assert pm.hw_for_device(v5e) is pm.V5E
    with pytest.raises(KeyError, match="TPU v9"):
        pm.hw_for_device(Dev("tpu", "TPU v9"))   # unknown: never a default
    pm.check_hw_for_device("v5e", v5e)
    with pytest.raises(ValueError, match="does not match"):
        pm.check_hw_for_device("v6e", v5e)
    pm.check_hw_for_device("v5e", Dev("cpu", "cpu"))   # plans for any target


def test_dse_prunes_infeasible():
    cfg = get_config("qwen1_5_32b")
    pts = dse.explore(cfg, SHAPES["decode_32k"], n_devices=4, tps=(4,))
    assert pts, "DSE returned nothing"
    assert any(not p.feasible for p in pts) or all(p.feasible for p in pts)
    # ranking: feasible first, then by time
    feas = [p.feasible for p in pts]
    assert feas == sorted(feas, reverse=True)
