"""Hardware-aware layer mapper: decision rules, purity, plan-driven numerics.

The mapper (runtime.mapper) must be a pure function of (layer shape, rho, HW)
and reproduce the paper's §5 regime split: memory-bound decode GEMMs run the
fused on-the-fly generator, compute-bound train/prefill GEMMs pre-generate
dense W once and reuse it (weight-stationary + decompress cache).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, OVSFConfig, ShapeConfig
from repro.configs import get_smoke_config
from repro.core import ovsf
from repro.hwmodel import perf_model as pm
from repro.kernels import ops
from repro.runtime import mapper


# ---------------------------------------------------------------------------
# Decision rules
# ---------------------------------------------------------------------------

def test_decode_shaped_layer_maps_to_fused():
    # B=8 decode GEMV-block: memory-bound on weight bytes -> generate in-tile
    plan = mapper.classify_gemm(8, 4096, 4096, 0.5, seg=16, weight_reuse=256)
    assert plan.path == "fused"
    assert not plan.cache_weights


def test_train_shaped_layer_maps_to_materialize_with_cache():
    # 8k tokens: compute-bound -> pre-generate dense W, weight-stationary
    plan = mapper.classify_gemm(8192, 4096, 4096, 0.5, seg=16, weight_reuse=1)
    assert plan.path == "materialize"
    assert plan.cache_weights


def test_prefill_shaped_layer_maps_to_materialize():
    plan = mapper.classify_gemm(2048, 2048, 2048, 0.5, seg=16,
                                weight_reuse=256)
    assert plan.path == "materialize"
    assert plan.cache_weights


def test_mapper_is_pure_in_shape_rho_hw():
    a = mapper.classify_gemm(8, 2048, 2048, 0.5, seg=16, weight_reuse=64)
    b = mapper.classify_gemm(8, 2048, 2048, 0.5, seg=16, weight_reuse=64)
    assert a == b                       # same inputs -> identical plan
    # and the decision flips with the workload shape, not hidden state
    c = mapper.classify_gemm(8192, 2048, 2048, 0.5, seg=16, weight_reuse=64)
    assert c.path != a.path


def test_bandwidth_starved_hw_pushes_toward_generation():
    # On a device with 10x less HBM bandwidth the decode case must still
    # prefer generation; on an infinite-bandwidth device the distinction
    # collapses to compute and materialize's single GEMM wins ties.
    slow = pm.V5E.scaled_bw(0.1)
    p_slow = mapper.classify_gemm(8, 4096, 4096, 0.5, seg=16, hw=slow,
                                  weight_reuse=256)
    assert p_slow.path == "fused"


def test_blocks_are_legal_and_hashable():
    plan = mapper.classify_gemm(8, 2048, 2048, 0.5, seg=16)
    for b in (plan.block_m, plan.block_n, plan.block_k, plan.block_j):
        assert b >= 8
    assert plan.block_k % 16 == 0       # segmented codes: bk multiple of L0
    hash(plan)                          # frozen dataclass


def test_plan_model_covers_ovsf_weight_types():
    cfg = get_smoke_config("tinyllama_1_1b")
    assert cfg.ovsf.enable
    shape = ShapeConfig("d", 1, 8, "decode")
    ep = mapper.plan_model(cfg, shape)
    names = ep.names()
    for w in ("attn_q", "mlp_up", "mlp_down"):
        assert w in names
    assert ep.plan_for("L3/mlp_up") is ep.plan_for("mlp_up")
    hash(ep)                            # rides inside frozen ModelConfig
    # decode-shaped plans for a smoke stack are generation-side
    assert ep.plan_for("mlp_up").path == "fused"


def test_plan_model_aliases_ssm_projection_names():
    # perf_model names SSM workloads ssm_in/ssm_out, but ssm.py dispatches
    # them as mlp_in/mlp_out — plans must land on the dispatch names.
    cfg = get_smoke_config("falcon_mamba_7b")
    assert cfg.ovsf.enable
    ep = mapper.plan_model(cfg, ShapeConfig("d", 1, 8, "decode"))
    assert ep.plan_for("mlp_in") is not None
    assert ep.plan_for("mlp_out") is not None
    assert ep.plan_for("ssm_in") is None or "ssm_in" not in ep.names()


@pytest.mark.parametrize("slots", [32, 64])
def test_plan_model_plans_the_expert_banks_under_their_names(slots):
    """perf_model names the expert GEMMs with a multiplicity suffix
    ("expert_gatex64"); the plan keys them by the names models.moe passes,
    and weighs only the paths it runs for them (no fused per-expert
    kernel): the activation-domain contraction at OLMoE's decode rows."""
    from repro.configs import get_config
    cfg = get_config("olmoe_1b_7b")
    ep = mapper.plan_model(cfg, ShapeConfig("serve_decode", 1, slots,
                                            "decode"),
                           hw="v5e", weight_reuse=1)
    assert "e" not in ep.names()
    for name in ("expert_gate", "expert_up", "expert_down"):
        assert name in ep.names()
        assert ep.plan_for(name).path == "spectral"
    assert ep.names() == ("attn_q", "attn_k", "attn_v", "attn_o",
                          "expert_gate", "expert_up", "expert_down")


def test_plan_model_keeps_the_trunk_plans_of_a_dense_model():
    """No trunk weight type carries a multiplicity suffix: starcoder2's
    decode plan (names, paths and blocks) is what it was."""
    from repro.configs import get_config
    ep = mapper.plan_model(get_config("starcoder2_15b"),
                           ShapeConfig("serve_decode", 1, 128, "decode"),
                           hw="v5e", weight_reuse=1)
    assert [(n, lp.path, lp.block_m, lp.block_n, lp.block_k, lp.block_j)
            for n, lp in ep.entries] == [
        ("attn_q", "fused", 128, 128, 128, 128),
        ("attn_k", "fused", 128, 128, 128, 128),
        ("attn_v", "fused", 128, 128, 128, 128),
        ("attn_o", "fused", 128, 128, 128, 128),
        ("mlp_up", "fused", 128, 128, 128, 128),
        ("mlp_down", "fused", 128, 128, 128, 128),
    ]


def test_plan_model_train_shape_prefers_materialize():
    cfg = get_smoke_config("tinyllama_1_1b")
    shape = ShapeConfig("t", 512, 8, "train")
    ep = mapper.plan_model(cfg, shape)
    assert ep.plan_for("mlp_up").path == "materialize"
    assert ep.plan_for("mlp_up").cache_weights


def test_plan_cnn_emits_plans_for_compressed_convs():
    from repro.models.cnn import CNNConfig
    cfg = CNNConfig("r18", "resnet18", ovsf_enable=True,
                    block_rhos=(1.0, 0.5, 0.5, 0.5))
    ep = mapper.plan_cnn(cfg, batch=1)
    assert len(ep.entries) > 0
    for name, lp in ep.entries:
        assert lp.path in ("fused", "materialize")


# ---------------------------------------------------------------------------
# Numeric equivalence of the three paths under mapper-emitted plans
# ---------------------------------------------------------------------------

def _integer_ovsf_case(key, d_in, d_out, rho, seg):
    """Integer-valued params/activations: every path is exact in f32, so the
    three execution paths must agree BIT-IDENTICALLY, not just approximately."""
    spec = ovsf.OVSFSpec(d_in, d_out, rho=rho, seg=seg)
    p = ovsf.init_ovsf(key, spec, dtype=jnp.float32)
    ks = jax.random.split(key, 2)
    alphas = jnp.round(jax.random.uniform(ks[0], p["alphas"].shape,
                                          minval=-4, maxval=4))
    x = jnp.round(jax.random.uniform(ks[1], (16, d_in), minval=-4, maxval=4))
    return x, alphas, p["idx"]


@pytest.mark.parametrize("seg", [0, 16])
def test_paths_bit_identical_under_plans(seg):
    key = jax.random.PRNGKey(0)
    x, alphas, idx = _integer_ovsf_case(key, 256, 128, 0.5, seg)
    base = mapper.classify_gemm(16, 256, 128, 0.5, seg=seg,
                                paths=mapper.ALL_PATHS)
    outs = {}
    for path in ("materialize", "fused", "spectral"):
        plan = dataclasses.replace(base, path=path)
        outs[path] = np.asarray(ops.ovsf_matmul(x, alphas, idx, plan=plan))
    np.testing.assert_array_equal(outs["materialize"], outs["fused"])
    np.testing.assert_array_equal(outs["materialize"], outs["spectral"])


def test_fused_pallas_interpret_matches_plan_output():
    key = jax.random.PRNGKey(1)
    x, alphas, idx = _integer_ovsf_case(key, 128, 64, 0.5, 16)
    plan = mapper.classify_gemm(16, 128, 64, 0.5, seg=16)
    y_ref = np.asarray(ops.ovsf_matmul(x, alphas, idx, plan=plan))
    y_pal = np.asarray(ops.ovsf_matmul(
        x, alphas, idx, path="fused", use_pallas=True, interpret=True,
        block_m=plan.block_m, block_n=plan.block_n,
        block_k=plan.block_k, block_j=plan.block_j))
    np.testing.assert_allclose(y_pal, y_ref, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# Decompress cache policy
# ---------------------------------------------------------------------------

def test_weight_cache_hits_and_invalidates():
    ops.clear_weight_cache()
    key = jax.random.PRNGKey(2)
    x, alphas, idx = _integer_ovsf_case(key, 256, 128, 0.5, 16)
    plan = mapper.LayerPlan("materialize", cache_weights=True,
                            cache_key="test_layer")
    y1 = ops.ovsf_matmul(x, alphas, idx, plan=plan)
    assert ops.weight_cache_stats()["entries"] == 1
    # slots are keyed (cache_key | alpha dtype) so a dtype switch re-keys
    w_cached = ops._WEIGHT_CACHE[""]["test_layer|fp"][2]
    y2 = ops.ovsf_matmul(x, alphas, idx, plan=plan)
    assert ops._WEIGHT_CACHE[""]["test_layer|fp"][2] is w_cached   # reused
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    # new parameter version -> regenerated
    alphas2 = alphas + 1.0
    ops.ovsf_matmul(x, alphas2, idx, plan=plan)
    assert ops._WEIGHT_CACHE[""]["test_layer|fp"][2] is not w_cached
    ops.clear_weight_cache()


def test_weight_cache_skips_tracers():
    ops.clear_weight_cache()
    key = jax.random.PRNGKey(3)
    x, alphas, idx = _integer_ovsf_case(key, 256, 128, 0.5, 16)
    plan = mapper.LayerPlan("materialize", cache_weights=True,
                            cache_key="traced_layer")
    y = jax.jit(lambda a: ops.ovsf_matmul(x, a, idx, plan=plan))(alphas)
    jax.block_until_ready(y)
    assert "traced_layer" not in ops._WEIGHT_CACHE.get("", {})  # no tracer leaks
    ops.clear_weight_cache()


# ---------------------------------------------------------------------------
# Engine integration: one jit'd batched call per decode step
# ---------------------------------------------------------------------------

def test_engine_issues_one_batched_decode_call_per_step():
    from repro.models import registry as R
    from repro.serving.engine import LLMEngine, Request
    cfg = get_smoke_config("tinyllama_1_1b")
    params = R.model_init(jax.random.PRNGKey(0), cfg)
    eng = LLMEngine(params, cfg, batch_slots=4, buffer_len=32)
    calls = {"n": 0}
    inner = eng._step_fn

    def counting_step(*a):
        calls["n"] += 1
        return inner(*a)

    eng._step_fn = counting_step
    rng = np.random.default_rng(0)
    for rid in range(6):
        eng.submit(Request(rid, rng.integers(0, cfg.vocab, 4, dtype=np.int32),
                           max_new_tokens=3))
    stats = eng.run_until_drained()
    assert stats.completed == 6
    assert calls["n"] == stats.steps        # ONE batched decode call per step
    assert stats.tokens_out == 6 * 3
    # the engine auto-applied a decode-shaped mapper plan
    assert eng.cfg.exec_plan is not None
    assert eng.cfg.exec_plan.plan_for("mlp_up").path == "fused"
