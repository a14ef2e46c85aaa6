"""Quantised alpha pipeline: int8 / int4-packed storage of the OVSF alpha
buffers with per-segment symmetric scales and a fused dequant epilogue.

Covers the ISSUE-4 satellites: round-trip error bounds vs alpha magnitude
(property tests), 3-path (fused/materialize/spectral) agreement under int8,
the Pallas generator streaming quantised bytes (interpret-mode vs dequant
oracle), dtype-keyed decompress caching, perf-model/mapper accounting,
checkpoint round-trip, config validation, and a fused-int8 serving decode
determinism regression.
"""
import dataclasses

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, OVSFConfig
from repro.configs import get_smoke_config
from repro.core import ovsf
from repro.hwmodel import perf_model as pm
from repro.kernels import ops, ref as kref
from repro.kernels.ovsf_gemm import ovsf_gemm, ovsf_decompress
from repro.runtime import mapper

# hypothesis drives the randomised property sweeps; the rest of the module
# (fixed-seed kernel/cache/serving coverage) runs without it
try:
    import hypothesis
    import hypothesis.strategies as st
    hypothesis.settings.register_profile(
        "ci", deadline=None, max_examples=10,
        suppress_health_check=[hypothesis.HealthCheck.too_slow])
    hypothesis.settings.load_profile("ci")
    HAVE_HYPOTHESIS = True
except ImportError:                              # pragma: no cover - CI has it
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Config / spec validation (satellite: reject unknown dtypes up front)
# ---------------------------------------------------------------------------

def test_ovsf_config_rejects_unknown_alpha_dtype():
    with pytest.raises(ValueError, match="alpha_dtype"):
        OVSFConfig(alpha_dtype="int7")
    for ok in ("", "int8", "int4"):
        OVSFConfig(alpha_dtype=ok)


def test_ovsf_spec_rejects_unknown_alpha_dtype():
    with pytest.raises(ValueError, match="alpha_dtype"):
        ovsf.OVSFSpec(64, 64, rho=0.5, alpha_dtype="fp16")


def test_ovsf_config_rejects_unknown_exec_path():
    with pytest.raises(ValueError, match="exec_path"):
        OVSFConfig(exec_path="telepathy")


def test_int4_requires_even_d_out():
    al = jnp.ones((8, 3))
    with pytest.raises(ValueError, match="even d_out"):
        ovsf.quantize_alphas(al, 1, "int4")


# ---------------------------------------------------------------------------
# Quantise / dequantise round trip (property: error bounded by segment max)
# ---------------------------------------------------------------------------

def _check_roundtrip_bound(n_seg, n_keep, d_out, scale_exp, seed, dt):
    """Per-segment symmetric round-to-nearest: per-element error <= scale/2
    with scale = max|alpha_seg| / qmax — the error tracks alpha magnitude."""
    J = n_seg * n_keep
    qmax = 127.0 if dt == "int8" else 7.0
    al = jax.random.normal(jax.random.PRNGKey(seed), (J, d_out))
    al = al * (10.0 ** scale_exp)
    q, s = ovsf.quantize_alphas(al, n_seg, dt)
    assert q.dtype == jnp.int8
    assert q.shape == (J, d_out // 2 if dt == "int4" else d_out)
    assert s.shape == (n_seg, 1)
    deq = ovsf.dequantize_alphas(q, s, dt)
    err = np.abs(np.asarray(deq - al)).reshape(n_seg, -1).max(axis=1)
    amax = np.abs(np.asarray(al)).reshape(n_seg, -1).max(axis=1)
    bound = 0.5 * amax / qmax
    assert (err <= bound * (1 + 1e-5) + 1e-12).all(), (err, bound)


@pytest.mark.parametrize("dt", ["int8", "int4"])
@pytest.mark.parametrize("n_seg,n_keep,d_out,scale_exp,seed", [
    (1, 8, 16, 0.0, 0), (4, 8, 32, -3.0, 1), (8, 3, 2, 2.0, 2),
    (2, 1, 24, -1.0, 3),
])
def test_roundtrip_error_bounded(dt, n_seg, n_keep, d_out, scale_exp, seed):
    _check_roundtrip_bound(n_seg, n_keep, d_out, scale_exp, seed, dt)


if HAVE_HYPOTHESIS:
    @hypothesis.given(
        dt=st.sampled_from(["int8", "int4"]),
        n_seg=st.sampled_from([1, 2, 4, 8]),
        n_keep=st.integers(1, 8),
        d_half=st.integers(1, 12),
        scale_exp=st.floats(-3.0, 2.0),
        seed=st.integers(0, 10_000))
    def test_roundtrip_error_bounded_hypothesis(dt, n_seg, n_keep, d_half,
                                                scale_exp, seed):
        _check_roundtrip_bound(n_seg, n_keep, 2 * d_half, scale_exp, seed, dt)


def test_int4_pack_unpack_exact():
    # every representable nibble value survives the pack/unpack round trip
    vals = jnp.arange(-7, 8, dtype=jnp.float32)
    al = jnp.stack([vals, vals[::-1]], axis=0)          # (2, 15) -> pad even
    al = jnp.concatenate([al, jnp.zeros((2, 1))], axis=1)  # (2, 16)
    q, s = ovsf.quantize_alphas(al, 1, "int4")
    deq = ovsf.dequantize_alphas(q, s, "int4")
    np.testing.assert_allclose(np.asarray(deq), np.asarray(al),
                               rtol=1e-6, atol=1e-6)


def test_quantize_params_key_carries_dtype():
    spec = ovsf.OVSFSpec(64, 32, rho=0.5, seg=16)
    p = ovsf.compress_matrix(
        jax.random.normal(jax.random.PRNGKey(0), (64, 32)), spec)
    p8 = ovsf.quantize_params(p, "int8")
    p4 = ovsf.quantize_params(p, "int4")
    assert "alphas" not in p8 and "alphas_q8" in p8 and "alpha_scale" in p8
    assert "alphas" not in p4 and "alphas_q4" in p4
    assert ovsf.alpha_params(p8)[2] == "int8"
    assert ovsf.alpha_params(p4)[2] == "int4"
    assert ovsf.alpha_params(p)[2] == ""
    # compress_matrix emits the quantised form directly when the spec asks
    spec_q = dataclasses.replace(spec, alpha_dtype="int8")
    pq = ovsf.compress_matrix(
        jax.random.normal(jax.random.PRNGKey(0), (64, 32)), spec_q)
    assert "alphas_q8" in pq
    np.testing.assert_array_equal(np.asarray(pq["alphas_q8"]),
                                  np.asarray(p8["alphas_q8"]))
    # and decompress_matrix accepts it
    W = ovsf.decompress_matrix(pq, spec_q)
    assert W.shape == (64, 32) and np.isfinite(np.asarray(W)).all()


def test_alpha_hbm_bytes_accounting():
    # HBM byte accounting lives in ONE place: the perf model's GemmLayer
    mk = lambda dt: pm.GemmLayer("g", M=8, d_in=4096, d_out=4096, rho=0.5,
                                 ovsf=True, seg=16, alpha_dtype=dt)
    b_fp, b8, b4 = (mk(dt).alpha_hbm_bytes for dt in ("", "int8", "int4"))
    assert b8 < b_fp / 2 + mk("int8").j_total // mk("int8").n_keep * 4 + 1
    assert b4 < b8


# ---------------------------------------------------------------------------
# Pallas generator: quantised bytes stream, dequant fused into the tile loop
# ---------------------------------------------------------------------------

def _quant_case(seed, M, d_in, d_out, dt, seg=16):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    W = jax.random.normal(k1, (d_in, d_out)) * 0.1
    x = jax.random.normal(k2, (M, d_in))
    spec = ovsf.OVSFSpec(d_in, d_out, rho=0.5, seg=seg, alpha_dtype=dt)
    p = ovsf.compress_matrix(W, spec)
    al, sc, adt = ovsf.alpha_params(p)
    assert adt == dt
    return x, al, sc, p["idx"]


@pytest.mark.parametrize("dt", ["int8", "int4"])
@pytest.mark.parametrize("seg", [16, 0])
def test_ovsf_gemm_quantised_matches_dequant_oracle(dt, seg):
    x, al, sc, idx = _quant_case(3, 7, 128, 64, dt, seg=seg)
    y = ovsf_gemm(x, al, idx, alpha_scale=sc, alpha_dtype=dt, interpret=True,
                  block_m=8, block_n=32, block_k=32, block_j=8)
    deq = ovsf.dequantize_alphas(al, sc, dt)
    yr = kref.ovsf_matmul_ref(x, deq, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-3)
    # the operand that entered the kernel really is the quantised storage
    assert al.dtype == jnp.int8


@pytest.mark.parametrize("dt", ["int8", "int4"])
@pytest.mark.parametrize("bk,bj", [(16, 8), (48, 16)])
def test_ovsf_gemm_quantised_bounded_loop_reads_its_own_scales(dt, bk, bj):
    """Segmented codes: each k-block's generator loop starts at the chunk
    of its own alpha rows (the k-th of 8 rows, or rows 24k.. across two
    chunks of 16), so it reads scale chunks past the first, and the
    per-segment scales differ."""
    x, al, sc, idx = _quant_case(13, 5, 128, 64, dt, seg=16)
    assert len(np.unique(np.asarray(sc))) > 1
    y = ovsf_gemm(x, al, idx, alpha_scale=sc, alpha_dtype=dt, interpret=True,
                  block_m=8, block_n=32, block_k=bk, block_j=bj)
    yr = kref.ovsf_matmul_ref(x, ovsf.dequantize_alphas(al, sc, dt), idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dt", ["int8", "int4"])
def test_ovsf_decompress_quantised_matches_dequant_oracle(dt):
    _, al, sc, idx = _quant_case(5, 1, 128, 64, dt, seg=0)
    W = ovsf_decompress(al, idx, d_in=128, alpha_scale=sc, alpha_dtype=dt,
                        interpret=True, block_n=32, block_k=32, block_j=8)
    Wr = kref.ovsf_decompress_ref(al, idx, 128, alpha_scale=sc,
                                  alpha_dtype=dt)
    np.testing.assert_allclose(np.asarray(W), np.asarray(Wr),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dt", ["int8", "int4"])
def test_three_path_agreement_quantised(dt):
    """fused / materialize / spectral agree on the SAME quantised params."""
    x, al, sc, idx = _quant_case(11, 9, 192, 48, dt, seg=16)
    deq = ovsf.dequantize_alphas(al, sc, dt)
    y_ref = kref.ovsf_matmul_ref(x, deq, idx)
    for path in ("materialize", "spectral", "fused"):
        y = ops.ovsf_matmul(x, al, idx, path=path, use_pallas=False,
                            alpha_scale=sc, alpha_dtype=dt)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=3e-3, atol=3e-3, err_msg=path)
    # and the interpret-mode Pallas fused kernel agrees with all of them
    y_pl = ops.ovsf_matmul(x, al, idx, path="fused", use_pallas=True,
                           interpret=True, alpha_scale=sc, alpha_dtype=dt,
                           block_m=8, block_n=16, block_k=32, block_j=8)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=3e-3, atol=3e-3)


def test_quantised_output_close_to_fp(seed=17):
    """int8 stays within ~2% relative error of the fp path on N(0,.) data;
    int4 within ~25% (3-bit mantissa): the traffic/accuracy trade-off."""
    x, al8, sc8, idx = _quant_case(seed, 16, 256, 128, "int8")
    spec = ovsf.OVSFSpec(256, 128, rho=0.5, seg=16)
    W = jax.random.normal(jax.random.PRNGKey(seed), (256, 128)) * 0.1
    p = ovsf.compress_matrix(W, spec)
    xx = jax.random.normal(jax.random.PRNGKey(seed + 1), (16, 256))
    y_fp = kref.ovsf_matmul_ref(xx, p["alphas"], p["idx"])
    for dt, tol in (("int8", 0.02), ("int4", 0.25)):
        pq = ovsf.quantize_params(p, dt)
        al, sc, _ = ovsf.alpha_params(pq)
        y = ops.ovsf_matmul(xx, al, pq["idx"], path="fused", use_pallas=False,
                            alpha_scale=sc, alpha_dtype=dt)
        rel = float(jnp.linalg.norm(y - y_fp) / jnp.linalg.norm(y_fp))
        assert rel < tol, (dt, rel)


# ---------------------------------------------------------------------------
# Perf model + mapper account the shrunken alpha stream
# ---------------------------------------------------------------------------

def test_modeled_fused_ii_strictly_drops_with_quantisation():
    def ii(dt):
        l = pm.GemmLayer("g", M=8, d_in=4096, d_out=4096, rho=0.5, ovsf=True,
                         exec_path="fused", seg=16, alpha_dtype=dt)
        return pm.layer_timing(l).ii
    assert ii("int4") < ii("int8") < ii("")
    # the standard bench shape is IFM-bound at fp: int8 halves t_mem_w
    l8 = pm.GemmLayer("g", M=8, d_in=4096, d_out=4096, rho=0.5, ovsf=True,
                      exec_path="fused", seg=16, alpha_dtype="int8")
    lf = dataclasses.replace(l8, alpha_dtype="")
    t8, tf = pm.layer_timing(l8), pm.layer_timing(lf)
    assert tf.bound == "IFM"
    assert t8.t_mem_w < 0.51 * tf.t_mem_w + 1e-9


def test_mapper_threads_alpha_dtype():
    p_fp = mapper.classify_gemm(8, 4096, 4096, 0.5, seg=16)
    p_q = mapper.classify_gemm(8, 4096, 4096, 0.5, seg=16,
                               alpha_dtype="int8")
    assert p_q.path == "fused" and p_q.alpha_dtype == "int8"
    assert p_q.ii_s < p_fp.ii_s          # quantising raises the roofline
    # plan_model picks the dtype up from the config
    from repro.configs.base import ShapeConfig
    cfg = get_smoke_config("tinyllama_1_1b").replace(d_model=1024, d_ff=2048)
    cfg = cfg.replace(ovsf=dataclasses.replace(cfg.ovsf, alpha_dtype="int4",
                                               min_dim=512))
    plan = mapper.plan_model(cfg, ShapeConfig("d", 1, 8, "decode"))
    assert plan.entries and all(lp.alpha_dtype == "int4"
                                for _n, lp in plan.entries)


# ---------------------------------------------------------------------------
# Checkpoint round trip (satellite)
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrips_quantised_params(tmp_path):
    from repro.checkpoint import ckpt
    spec = ovsf.OVSFSpec(64, 32, rho=0.5, seg=16, alpha_dtype="int8")
    p = ovsf.compress_matrix(
        jax.random.normal(jax.random.PRNGKey(2), (64, 32)), spec)
    tree = {"layer": p}
    ckpt.save(tree, str(tmp_path), step=1)
    restored, step = ckpt.restore(str(tmp_path), template=tree)
    assert step == 1
    assert restored["layer"]["alphas_q8"].dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(restored["layer"]["alphas_q8"]),
                                  np.asarray(p["alphas_q8"]))
    np.testing.assert_array_equal(np.asarray(restored["layer"]["alpha_scale"]),
                                  np.asarray(p["alpha_scale"]))


def test_checkpoint_refuses_float_to_int_cast(tmp_path):
    from repro.checkpoint import ckpt
    tree_fp = {"w": jnp.ones((4, 4), jnp.float32)}
    ckpt.save(tree_fp, str(tmp_path), step=1)
    tmpl = {"w": jnp.ones((4, 4), jnp.int8)}
    with pytest.raises(TypeError, match="float<->int"):
        ckpt.restore(str(tmp_path), template=tmpl)


# ---------------------------------------------------------------------------
# End to end: fused-int8 serving decode is deterministic (regression)
# ---------------------------------------------------------------------------

def _quantised_smoke_cfg(dt) -> ModelConfig:
    cfg = get_smoke_config("tinyllama_1_1b")
    return cfg.replace(ovsf=dataclasses.replace(cfg.ovsf, alpha_dtype=dt))


def test_linear_init_emits_quantised_storage():
    from repro.models import layers as L
    cfg = _quantised_smoke_cfg("int8")
    p = L.linear_init(jax.random.PRNGKey(0), cfg, "mlp_up", 128, 256)
    assert "alphas_q8" in p and p["alphas_q8"].dtype == jnp.int8
    assert "alpha_scale" in p and "alphas" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 128))
    y = L.linear_apply(p, x, cfg, "mlp_up")
    assert y.shape == (3, 256) and np.isfinite(np.asarray(y)).all()


def test_fused_int8_serving_decode_deterministic():
    from repro.models import registry as R
    from repro.serving import LLMEngine, Request, SamplingParams
    cfg = _quantised_smoke_cfg("int8")
    params = R.model_init(jax.random.PRNGKey(0), cfg)

    def decode_tokens():
        eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=64)
        for rid in range(2):
            eng.submit(Request(rid, np.arange(4, dtype=np.int32) + rid,
                               max_new_tokens=4,
                               sampling=SamplingParams()))
        eng.run_until_drained()
        outs = sorted(eng.outputs(), key=lambda o: o.rid)
        return [tuple(o.tokens) for o in outs], eng.stats

    t1, st1 = decode_tokens()
    t2, st2 = decode_tokens()
    assert t1 == t2, "fused-int8 decode must be seed-deterministic"
    assert all(len(t) == 4 for t in t1)
    assert st1.completed == 2
