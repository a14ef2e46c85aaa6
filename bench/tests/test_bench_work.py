"""Least work (bench/work.py): the OVSF flops counted are the spectral
path's GEMM, experts count top-k only, and the metrics that divide by it
cannot pass 100% of a peak."""
import os
import sys
import types

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import work  # noqa: E402
from bench.run import load_json, metric_reader, model_of  # noqa: E402
from pathlib import Path  # noqa: E402

SMOKE = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 32, "d_ff": 256, "vocab": 512, "mlp": "gelu_tanh",
         "n_experts": 0, "top_k": 0,
         "ovsf": {"rho": 0.5, "seg_len": 16, "min_dim": 32}}


def _dot_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _rc), _ = eqn.params["dimension_numbers"]
            a = eqn.invars[0].aval.shape
            out = eqn.outvars[0].aval.shape
            k = int(np.prod([a[i] for i in lc]))
            total += 2.0 * np.prod(out) * k
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                total += _dot_flops(sub)
    return total


@pytest.mark.parametrize("M", [1, 7, 64])
def test_ovsf_flops_equal_the_spectral_gemm(M):
    from repro.core import ovsf
    from repro.kernels import ops
    m = SMOKE
    for li in work.trunk_linears(m):
        spec = ovsf.OVSFSpec(li.d_in, li.d_out, rho=0.5, seg=16)
        p = ovsf.init_ovsf(jax.random.PRNGKey(0), spec)
        x = jnp.ones((M, li.d_in), jnp.float32)
        jx = jax.make_jaxpr(lambda x, a, i: ops.spectral_matmul(
            x, a, i, use_pallas=False))(x, p["alphas"], p["idx"])
        assert work.kept_codes(li.d_in, m["ovsf"]) == spec.j_total
        assert work.ovsf_linear(li, M, m["ovsf"]).flops == \
            _dot_flops(jx.jaxpr), li


def test_experts_count_top_k_not_capacity():
    m = dict(SMOKE, mlp="swiglu", n_experts=8, top_k=2, d_ff=64)
    st = work.StepTokens(n_tokens=10, ctx_sum=0, n_emit=0)
    w = work.step_work(m, st)
    d, f, J_d, J_f = 128, 64, 64, 32
    attn = 2 * 10 * J_d * (4 * 32) * 2 + 2 * 10 * J_d * (2 * 32) * 2
    experts = 10 * 2 * (2 * J_d * f * 2 + 2 * J_f * d)
    router = 2 * 10 * d * 8
    assert w.flops == pytest.approx(2 * (attn + experts + router))


def test_real_configs_read_as_published():
    root = Path(ROOT)
    bench = load_json(root / "BENCHMARK.json")
    for c in bench["configs"]:
        m = model_of(load_json(root / c["file"]))
        for li in work.trunk_linears(m) + (work.expert_linears(m)
                                           if m["n_experts"] else []):
            assert work.kept_codes(li.d_in, m["ovsf"]) * 2 == li.d_in


def _ctx(m, steps, window_s, kernel_s=None):
    from bench import trace
    tr = None
    if kernel_s is not None:
        tr = trace.Trace({0: [trace.Event("ovsf_gemm.3", 0.0, kernel_s)]},
                         [])
    return types.SimpleNamespace(
        model=m, peaks={"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
        window_s=window_s, steps=len(steps), step_tokens=steps,
        valid_tokens=0, batch_tokens=0, trace=tr,
        trace_window=(0.0, max(window_s, kernel_s or 0.0)))


def test_mfu_and_roofline_reach_100_only_at_least_time():
    root = Path(ROOT)
    m = SMOKE
    steps = [work.StepTokens(32, 32 * 100, 32)] * 3
    least = sum(work.step_work(m, s).flops for s in steps) / 197e12
    mfu = metric_reader(root, "mfu")
    assert mfu.read(_ctx(m, steps, least)) == pytest.approx(100.0)
    assert mfu.read(_ctx(m, steps, 2 * least)) == pytest.approx(50.0)
    roof = metric_reader(root, "ovsf_gemm_roofline")
    k_least = sum(
        m["n_layers"] * work.ovsf_linear(li, s.n_tokens, m["ovsf"])
        .least_seconds(197e12, 819e9)
        for s in steps for li in work.trunk_linears(m))
    assert roof.read(_ctx(m, steps, 1.0, kernel_s=k_least)) == \
        pytest.approx(100.0)
    assert roof.read(_ctx(m, steps, 1.0, kernel_s=4 * k_least)) == \
        pytest.approx(25.0)
    assert roof.read(_ctx(m, steps, 1.0)) is None       # no trace: silent


def test_counter_and_clock_readers():
    from bench import trace, window
    root = Path(ROOT)
    ctx = types.SimpleNamespace(
        valid_tokens=150, batch_tokens=200, window_s=10.0, steps=8,
        window=window.WindowCounts(10.0, 0, [], [1.0, 2.0, 3.0, 4.0, 5.0]),
        trace=trace.Trace({0: trace.mark_leaves(
            [trace.Event("a", 0.0, 3.0), trace.Event("b", 2.0, 4.0)])}, []),
        trace_window=(0.0, 5.0))
    read = lambda name: metric_reader(root, name).read(ctx)
    assert read("padding_eff") == pytest.approx(75.0)
    assert read("step_ms") == pytest.approx(1250.0)
    assert read("ttft_p90_s") == pytest.approx(4.6)
    assert read("idle_share") == pytest.approx(20.0)
    ctx.trace, ctx.steps, ctx.batch_tokens = None, 0, 0
    ctx.window = window.WindowCounts(10.0, 0, [], [])
    assert all(read(n) is None for n in ("padding_eff", "step_ms",
                                         "ttft_p90_s", "idle_share"))
