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
         "n_experts": 0, "top_k": 0, "router_experts": 0,
         "n_shared_experts": 0, "moe_d_ff": 256, "first_dense": 0, "mla": None,
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
    m = dict(SMOKE, mlp="swiglu", n_experts=8, top_k=2, d_ff=64,
             router_experts=8, moe_d_ff=64)
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


# step_work of the two real configurations, recorded from the harness
# before it read latent attention, shared experts or leading dense layers:
# (flops, bytes) on each StepTokens below
STEPS = {"decode": work.StepTokens(128, 128 * 600, 128),
         "mixed": work.StepTokens(256, 32 * 700 + 224 * (200 + 113), 33),
         "one": work.StepTokens(1, 1, 1)}
PARENT_WORK = {
    ("olmoe_1b_7b", "decode"): (174415937536.0, 7312703488.0),
    ("olmoe_1b_7b", "mixed"): (294876872704.0, 7680506112.0),
    ("olmoe_1b_7b", "one"): (1284112384.0, 1287463168.0),
    ("starcoder2_15b_pp4", "decode"): (587420663808.0, 4711309312.0),
    ("starcoder2_15b_pp4", "mixed"): (1025140850688.0, 4955082752.0),
    ("starcoder2_15b_pp4", "one"): (4442013696.0, 4444971008.0),
}
# the ovsf_gemm_roofline and mfu readers on all three steps (a 0.5-s kernel
# in a 1-s traced window, a 2-s window), recorded alike
PARENT_READS = {"olmoe_1b_7b": (0.24614009279609275, 0.11943576716345178),
                "starcoder2_15b_pp4": (2.9991472136752138,
                                       0.41040698685076143)}


def _real(name):
    return model_of(load_json(Path(ROOT) / "bench" / "configs"
                              / f"{name}.json"))


@pytest.mark.parametrize("name,step", sorted(PARENT_WORK))
def test_step_work_of_the_real_configs_is_bit_identical(name, step):
    w = work.step_work(_real(name), STEPS[step])
    assert (w.flops, w.bytes) == PARENT_WORK[(name, step)]


@pytest.mark.parametrize("name", sorted(PARENT_READS))
def test_work_readers_of_the_real_configs_are_bit_identical(name):
    from bench import trace
    m = _real(name)
    ctx = types.SimpleNamespace(
        model=m, peaks={"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
        window_s=2.0, steps=3, step_tokens=list(STEPS.values()),
        valid_tokens=0, batch_tokens=0,
        trace=trace.Trace({0: [trace.Event("ovsf_gemm.3", 0.0, 0.5)]}, []),
        trace_window=(0.0, 1.0))
    root = Path(ROOT)
    got = (metric_reader(root, "ovsf_gemm_roofline").read(ctx),
           metric_reader(root, "mfu").read(ctx))
    assert got == PARENT_READS[name]


def _kimi():
    return model_of(load_json(Path(__file__).parent / "data"
                              / "kimi_k2_pp5_ep48.json"))


def _ovsf(d_in, d_out, M):
    """OVSF linear at rho 0.5 with L0 16: J = d_in / 2 kept codes."""
    J = d_in // 2
    return (2 * M * J * d_out, J * d_out * 2 + J * 4 + M * (d_in + d_out) * 2)


def _dense(d_in, d_out, M):
    return (2 * M * d_in * d_out, d_in * d_out * 2 + M * (d_in + d_out) * 2)


def _sum(*parts):
    return tuple(sum(p[i] for p in parts) for i in (0, 1))


def _kimi_by_hand(n, ctx_sum, n_emit, reached):
    """The Kimi-K2 cut's step counted from its published widths: 12 layers,
    the first dense, 8 of 384 experts held, one shared expert. Each token's
    8 picks land here with chance 8/384 each, so n / 6 picks are routed
    here; ``reached`` is the experts whose alphas are read."""
    d, H, f, fe = 7168, 64, 18432, 2048
    attn = _sum(_ovsf(d, 1536, n),                     # q_a
                _ovsf(1536, H * (128 + 64), n),        # q_b
                _ovsf(d, 512 + 64, n),                 # kv_a
                _ovsf(512, H * (128 + 128), n),        # kv_b
                _ovsf(H * 128, d, n))                  # o
    scores = (ctx_sum * (2 * H * (128 + 64) + 2 * H * 128), 0)
    dense_mlp = _sum(_ovsf(d, f, n), _ovsf(d, f, n), _ovsf(f, d, n))
    shared = _sum(_ovsf(d, fe, n), _ovsf(d, fe, n), _ovsf(fe, d, n))
    routed = n / 6
    experts = (0, 0)
    for d_in, d_out in ((d, fe), (d, fe), (fe, d)):
        J = d_in // 2
        experts = _sum(experts, (2 * routed * J * d_out,
                                 reached * J * d_out * 2 + J * 4
                                 + routed * (d_in + d_out) * 2))
    router = _dense(d, 384, n)
    dense_layer = _sum(attn, scores, dense_mlp)
    moe_layer = _sum(attn, scores, shared, experts, router)
    return _sum(dense_layer, *[moe_layer] * 11, _dense(d, 20480, n_emit))


@pytest.mark.parametrize("st,reached", [
    # decode: 64/6 = 10.7 picks here, more than the 8 experts: all read
    (work.StepTokens(64, 64 * 1000, 64), 8),
    # mixed: 42.7 picks here, all 8 read
    (work.StepTokens(256, 32 * 900 + 224 * (512 + 112), 33), 8),
    # one token: 1/6 of a pick here, 1/6 of an expert's alphas expected
    (work.StepTokens(1, 700, 1), 1 / 6)])
def test_step_work_of_the_kimi_cut_is_its_hand_count(st, reached):
    w = work.step_work(_kimi(), st)
    flops, nbytes = _kimi_by_hand(st.n_tokens, st.ctx_sum, st.n_emit,
                                  reached)
    assert w.flops == pytest.approx(flops, rel=1e-12)
    assert w.bytes == pytest.approx(nbytes, rel=1e-12)


@pytest.mark.parametrize("E,R,k", [(8, 384, 8), (64, 64, 8), (16, 128, 2)])
def test_expert_bytes_estimate_the_experts_reached(E, R, k):
    """min(E, routed) experts' weights are read: at or above the expected
    number of distinct held experts that n tokens' top-k picks reach under
    uniform routing, and above it by at most routed**2 / (2E)."""
    m = dict(_kimi(), n_experts=E, router_experts=R, top_k=k)
    li = work.expert_linears(m)
    one = sum(work.kept_codes(x.d_in, m["ovsf"]) * x.d_out * 2 for x in li)
    for n in (0, 1, 3, 10, 100, 1000, 10000):
        router = work.dense_linear(work.Linear("router", m["d_model"], R), n)
        routed = n * k * E / R
        acts = sum(routed * (x.d_in + x.d_out) * 2
                   + work.kept_codes(x.d_in, m["ovsf"]) * 4 for x in li)
        spent = work.expert_work(m, n).bytes - router.bytes
        reached = (spent - acts) / one if n else 0.0
        expected = E * (1 - (1 - k / R) ** n)
        assert expected - 1e-9 <= reached == pytest.approx(min(E, routed))
        assert reached - expected <= routed ** 2 / (2 * E) + 1e-9
    assert work.expert_work(m, 0).bytes == 0


def test_the_kimi_cut_counts_each_linear_in_the_layers_that_hold_it():
    counts = {li.name: n for li, n in work.layer_linears(_kimi())}
    assert counts == {"attn_q_a": 12, "attn_q_b": 12, "attn_kv_a": 12,
                      "attn_kv_b": 12, "attn_o": 12, "mlp_up": 1,
                      "mlp_down": 1, "mlp_gate": 1, "shared_up": 11,
                      "shared_down": 11, "shared_gate": 11}
    assert [li.d_in for li in work.expert_linears(_kimi())] == \
        [7168, 7168, 2048]
