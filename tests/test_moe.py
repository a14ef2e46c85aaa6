"""MoE routing invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import moe


def _cfg(**kw):
    base = dict(name="t", family="moe", n_layers=1, d_model=32, n_heads=2,
                n_kv_heads=2, d_ff=16, vocab=64, head_dim=16, dtype="float32",
                remat=False, n_experts=4, top_k=2)
    base.update(kw)
    return ModelConfig(**base)


def test_moe_output_finite_and_aux():
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    p = moe.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 8, 32))
    y, aux = moe.moe_apply(p, cfg, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    # balanced-ish aux loss is ~1 for uniform routing, bounded by E/k-ish
    assert 0.0 < float(aux) < cfg.n_experts


def test_no_drop_when_capacity_large():
    """With cf >= E/k every token is routed; output == dense-equivalent mix."""
    cfg = _cfg(capacity_factor=2.0)
    key = jax.random.PRNGKey(1)
    p = moe.moe_init(key, cfg)
    x = jax.random.normal(key, (1, 6, 32))

    y, _ = moe.moe_apply(p, cfg, x)

    # dense reference: route every token through its top-k experts manually
    logits = x.reshape(-1, 32) @ p["router"]["w"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    gv, gi = jax.lax.top_k(probs, cfg.top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    W_g, W_u, W_d = p["gate"]["w"], p["up"]["w"], p["down"]["w"]
    ref = []
    for t in range(6):
        acc = jnp.zeros((32,))
        for j in range(cfg.top_k):
            e = int(gi[t, j])
            h = jax.nn.silu(x.reshape(-1, 32)[t] @ W_g[e]) * (
                x.reshape(-1, 32)[t] @ W_u[e])
            acc += gv[t, j] * (h @ W_d[e])
        ref.append(acc)
    ref = jnp.stack(ref).reshape(1, 6, 32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def test_capacity_drops_tokens():
    """With tiny capacity some (token, expert) pairs are dropped, not NaN'd."""
    cfg = _cfg(capacity_factor=0.1)
    key = jax.random.PRNGKey(2)
    p = moe.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 16, 32))
    y, aux = moe.moe_apply(p, cfg, x)
    assert np.isfinite(np.asarray(y)).all()
    # dropped tokens get zero contribution -> output norm smaller than no-drop
    y_full, _ = moe.moe_apply(p, _cfg(capacity_factor=4.0), x)
    assert float(jnp.linalg.norm(y)) <= float(jnp.linalg.norm(y_full)) + 1e-3


def test_shared_expert_added():
    cfg = _cfg(n_shared_experts=1, capacity_factor=2.0)
    key = jax.random.PRNGKey(3)
    p = moe.moe_init(key, cfg)
    assert "shared" in p
    x = jax.random.normal(key, (1, 4, 32))
    y, _ = moe.moe_apply(p, cfg, x)
    assert np.isfinite(np.asarray(y)).all()


def test_moe_ovsf_expert_compression():
    from repro.configs.base import OVSFConfig
    cfg = _cfg(d_ff=64, d_model=64,
               ovsf=OVSFConfig(enable=True, rho=0.5, min_dim=32,
                               exec_path="spectral", targets=("expert",)))
    key = jax.random.PRNGKey(4)
    p = moe.moe_init(key, cfg)
    assert "alphas" in p["gate"], "expert weights should be OVSF params"
    assert p["gate"]["alphas"].shape == (4, 32, 64)   # (E, rho*L, d_ff)
    x = jax.random.normal(key, (1, 8, 64))
    y, _ = moe.moe_apply(p, cfg, x)
    assert np.isfinite(np.asarray(y)).all()


def _spectral_cfg(dtype, capacity_factor, path="spectral"):
    """Compressed expert banks (L0 = 16) under a plan that runs ``path``."""
    from repro.configs.base import OVSFConfig
    from repro.runtime.mapper import ExecutionPlan, LayerPlan
    plan = ExecutionPlan(tuple((f"expert_{nm}", LayerPlan(path))
                               for nm in ("gate", "up", "down")))
    return _cfg(d_model=64, d_ff=32, n_experts=4, top_k=2, dtype=dtype,
                capacity_factor=capacity_factor, exec_plan=plan,
                ovsf=OVSFConfig(enable=True, rho=0.5, min_dim=32,
                                targets=("expert",)))


def _random_codes(p, key):
    """Each segment keeps its own sorted choice of the L0 = 16 codes, as
    the benchmark's weights do (``moe_init`` keeps the same ones in all)."""
    for i, nm in enumerate(("gate", "up", "down")):
        ns, nk = p[nm]["idx"].shape
        u = jax.random.uniform(jax.random.fold_in(key, i), (ns, 16))
        p[nm]["idx"] = jnp.sort(jnp.argsort(u, axis=-1)[:, :nk], axis=-1)
    return p


@pytest.mark.parametrize("capacity_factor", [0.5, 2.0],
                         ids=["dropping", "dropless"])
@pytest.mark.parametrize("bank", ["gate", "up", "down"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_spectral_expert_bank_equals_its_decompressed_weights(
        dtype, bank, capacity_factor):
    """A spectral bank contracts kept-code coefficients against its alphas;
    gate and up take the codes per token, before dispatch, down per expert
    slot. Each expert's rows must equal x @ decompress(alphas[e]), and the
    whole layer must match the layer whose plan decompresses the banks."""
    from repro.kernels import ops as kops
    cfg = _spectral_cfg(dtype, capacity_factor)
    key = jax.random.PRNGKey(5)
    p = _random_codes(moe.moe_init(key, cfg), key)
    dt = cfg.act_dtype
    E, k, d, f = cfg.n_experts, cfg.top_k, cfg.d_model, cfg.d_ff
    G, g = 2, 8
    cap = max(int(np.ceil(capacity_factor * k * g / E)), 1)
    tol = {"float32": 1e-5, "bfloat16": 2e-2}[dtype]
    bp, name = p[bank], f"expert_{bank}"
    d_in = f if bank == "down" else d
    ks = jax.random.split(key, 3)
    if bank == "down":
        rows = jax.random.normal(ks[0], (G, E, cap, f)).astype(dt)
        ex = moe._bank_input(bp, rows, cfg, name)
    else:
        # a one-hot dispatch of tokens to (expert, slot); with capacity
        # below k * g / E some tokens find no slot, some slots stay empty
        tokens = jax.random.normal(ks[0], (G, g, d)).astype(dt)
        slot = jax.random.randint(ks[1], (G, E, cap), 0, g + g // 2)
        disp = jax.nn.one_hot(slot, g, dtype=dt)              # (G,E,cap,g)
        codes = moe._bank_input(bp, tokens, cfg, name)        # (G,g,J)
        assert codes.shape == (G, g, bp["alphas"].shape[1])
        ex = jnp.einsum("gect,gtj->gecj", disp, codes)
        rows = jnp.einsum("gect,gtd->gecd", disp, tokens)
    y = moe._expert_matmul(bp, ex, cfg, name, d_in)
    assert y.shape == (G, E, cap, bp["alphas"].shape[-1]) and y.dtype == dt
    W = jax.vmap(lambda a: kops.decompress(a.astype(jnp.float32), bp["idx"],
                                           d_in))(bp["alphas"])
    ref = jnp.einsum("gecd,edn->gecn", rows.astype(jnp.float32), W)
    scale = float(jnp.max(jnp.abs(ref)))
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ref),
                               rtol=0, atol=tol * scale)

    x = jax.random.normal(ks[2], (G, g, d)).astype(dt)
    y_spec, y_mat = (
        jax.jit(lambda p, x, c=c: moe.moe_apply(p, c, x)[0])(p, x)
        for c in (cfg, _spectral_cfg(dtype, capacity_factor, "materialize")))
    scale = float(jnp.max(jnp.abs(y_mat.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(y_spec, np.float32),
                               np.asarray(y_mat, np.float32),
                               rtol=0, atol=tol * scale)
