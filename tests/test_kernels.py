"""Pallas kernel validation: interpret-mode vs pure-jnp oracles, shape/dtype
sweeps + hypothesis randomised shapes (assignment requirement)."""
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (see requirements.txt)")
import hypothesis.strategies as st  # noqa: E402
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ovsf
from repro.kernels import ops, ref as kref
from repro.kernels.fwht import fwht_pallas
from repro.kernels.ovsf_gemm import ovsf_gemm, ovsf_decompress

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=10,
    suppress_health_check=[hypothesis.HealthCheck.too_slow])
hypothesis.settings.load_profile("ci")


@pytest.mark.parametrize("L", [8, 64, 256, 2048])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_pallas_sweep(L, dtype):
    x = jax.random.normal(jax.random.PRNGKey(L), (6, L)).astype(dtype)
    y = fwht_pallas(x, interpret=True, block_m=4)
    yr = kref.fwht_ref(x.astype(jnp.float32))
    tol = 1e-4 * L if dtype == jnp.float32 else 0.1 * np.sqrt(L)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr),
                               atol=tol, rtol=1e-2)


def _mk_case(seed, M, d_in, J, N):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (M, d_in))
    al = jax.random.normal(k2, (J, N)) * (1.0 / np.sqrt(J))
    L = ovsf.next_pow2(d_in)
    idx = jnp.sort(jax.random.permutation(k1, L)[:J]).astype(jnp.int32)
    return x, al, idx


@pytest.mark.parametrize("M,d_in,J,N", [
    (4, 64, 16, 32), (16, 128, 64, 64), (3, 100, 20, 48), (8, 256, 256, 16),
])
def test_ovsf_gemm_shapes(M, d_in, J, N):
    x, al, idx = _mk_case(M, M, d_in, J, N)
    y = ovsf_gemm(x, al, idx, interpret=True, block_m=8, block_n=16,
                  block_k=32, block_j=16)
    yr = kref.ovsf_matmul_ref(x, al, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ovsf_gemm_dtypes(dtype):
    x, al, idx = _mk_case(7, 8, 128, 32, 64)
    xq = x.astype(dtype)
    alq = al.astype(dtype)
    y = ovsf_gemm(xq, alq, idx, interpret=True,
                  block_m=8, block_n=32, block_k=32, block_j=16)
    # oracle on the SAME rounded inputs (isolates kernel error from input
    # quantisation), f32 accumulation in both
    yr = kref.ovsf_matmul_ref(xq.astype(jnp.float32),
                              alq.astype(jnp.float32), idx)
    tol = 1e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr),
                               rtol=tol, atol=tol * 30)


@hypothesis.given(
    M=st.integers(1, 24), d_in=st.integers(8, 160),
    jfrac=st.floats(0.1, 1.0), N=st.integers(4, 96),
    seed=st.integers(0, 10_000))
def test_ovsf_gemm_hypothesis(M, d_in, jfrac, N, seed):
    L = ovsf.next_pow2(d_in)
    J = max(1, int(jfrac * L))
    x, al, idx = _mk_case(seed, M, d_in, J, N)
    y = ovsf_gemm(x, al, idx, interpret=True, block_m=8, block_n=16,
                  block_k=16, block_j=8)
    yr = kref.ovsf_matmul_ref(x, al, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-3,
                               atol=3e-3)


@pytest.mark.parametrize("d_in,J,N", [(64, 16, 32), (200, 64, 24),
                                      (512, 512, 16)])
def test_ovsf_decompress(d_in, J, N):
    _, al, idx = _mk_case(d_in, 1, d_in, J, N)
    W = ovsf_decompress(al, idx, d_in=d_in, interpret=True, block_n=16,
                        block_k=32, block_j=8)
    Wr = kref.ovsf_decompress_ref(al, idx, d_in)
    np.testing.assert_allclose(np.asarray(W), np.asarray(Wr), rtol=2e-3,
                               atol=2e-3)


def test_spectral_path_equals_ref():
    x, al, idx = _mk_case(11, 9, 200, 100, 40)
    y_spec = ops.ovsf_matmul(x, al, idx, path="spectral", use_pallas=False)
    y_mat = ops.ovsf_matmul(x, al, idx, path="materialize", use_pallas=False)
    y_ref = kref.ovsf_matmul_ref(x, al, idx)
    np.testing.assert_allclose(np.asarray(y_spec), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(y_mat), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-3)


def test_spectral_path_with_pallas_fwht():
    x, al, idx = _mk_case(12, 4, 128, 64, 32)
    y = ops.spectral_matmul(x, al, idx, use_pallas=True, interpret=True)
    yr = kref.ovsf_matmul_ref(x, al, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3,
                               atol=2e-3)


def test_ovsf_matmul_leading_dims():
    x, al, idx = _mk_case(13, 6, 64, 32, 16)
    x3 = x.reshape(2, 3, 64)
    y = ops.ovsf_matmul(x3, al, idx, path="spectral", use_pallas=False)
    assert y.shape == (2, 3, 16)
    yr = kref.ovsf_matmul_ref(x, al, idx).reshape(2, 3, 16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3,
                               atol=2e-3)


def test_gradients_flow_through_all_paths():
    x, al, idx = _mk_case(14, 4, 64, 32, 16)
    for path in ("materialize", "spectral"):
        g = jax.grad(lambda a: jnp.sum(
            ops.ovsf_matmul(x, a, idx, path=path, use_pallas=False) ** 2))(al)
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).max()) > 0


def _seg_case(seed, M, d_in, N, dtype):
    """Segmented (L0 = 16, rho 0.5) codes and alphas, rounded to ``dtype``."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    W = jax.random.normal(k1, (d_in, N)) * 0.1
    x = jax.random.normal(k2, (M, d_in)).astype(dtype)
    p = ovsf.compress_matrix(W, ovsf.OVSFSpec(d_in, N, rho=0.5, seg=16))
    assert p["idx"].shape == (d_in // 16, 8)
    return x, p["alphas"].astype(dtype), p["idx"]


# A k-block of bk inputs meets bk / 16 * 8 alpha rows; J = 128 at d_in 256.
SEG_BLOCKS = {
    "rows_below_chunk": (16, 16),   # 8 rows: two k-blocks share a chunk
    "rows_equal_chunk": (32, 16),   # 16 rows: one chunk, aligned
    "rows_above_chunk": (64, 16),   # 32 rows: two chunks
    "rows_unaligned": (48, 16),     # 24 rows from row 24k: two chunks, the
                                    # last k-block padded past d_in
    "last_chunk_padded": (32, 24),  # J = 128 padded to 144
}


@pytest.mark.parametrize("blocks", SEG_BLOCKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel", ["gemm", "decompress"])
def test_segmented_generator_matches_oracle(kernel, dtype, blocks):
    """The generator loop, bounded to each k-block's own alpha chunks,
    against the oracle on the same rounded inputs."""
    bk, bj = SEG_BLOCKS[blocks]
    x, al, idx = _seg_case(bk + bj, 8, 256, 64, dtype)
    al32 = al.astype(jnp.float32)
    if kernel == "gemm":
        y = ovsf_gemm(x, al, idx, interpret=True, block_m=8, block_n=32,
                      block_k=bk, block_j=bj)
        yr = kref.ovsf_matmul_ref(x.astype(jnp.float32), al32, idx)
    else:
        y = ovsf_decompress(al, idx, d_in=256, interpret=True, block_n=32,
                            block_k=bk, block_j=bj)
        yr = kref.ovsf_decompress_ref(al32, idx, 256)
    tol = 1e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr),
                               rtol=tol, atol=tol * 30)


@pytest.mark.parametrize("d_in,bk,bj", [
    (256, 16, 16), (256, 48, 16), (256, 32, 24), (256, 64, 24),
    (2048, 128, 128), (24576, 128, 128), (6144, 384, 128), (256, 256, 8),
])
def test_generator_chunk_window_covers_each_k_block(d_in, bk, bj):
    """Segmented codes (L0 16, 8 kept): for every k-block, the chunks the
    bounded loop runs hold all of its alpha rows that exist, and none lies
    past the last chunk, which interpret mode would not show."""
    from repro.kernels.ovsf_gemm import _chunk_run, _first_chunk
    seg, keep = 16, 8
    J = d_in // seg * keep
    nc = -(-J // bj)
    n_run = _chunk_run(bk, bj, seg, keep, nc)
    ks = np.arange(-(-d_in // bk))
    c0 = np.asarray(_first_chunk(jnp.asarray(ks, jnp.int32), bk, bj, seg,
                                 keep, nc)) * np.ones_like(ks)
    rows = bk // seg * keep
    first = ks * rows // bj
    last = np.minimum((ks * rows + rows - 1) // bj, nc - 1)
    assert (c0 >= 0).all() and (c0 + n_run <= nc).all()
    assert (c0 <= first).all() and (last < c0 + n_run).all()
