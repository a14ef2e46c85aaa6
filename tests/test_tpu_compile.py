"""The main path's Pallas kernels compile for a TPU v5e, with no chip here.

Interpret mode accepts what the chip's compiler refuses (unaligned blocks,
unsupported in-kernel reshapes, too much VMEM). These tests compile each
kernel at tinyllama_1_1b's widths (the fused one also at starcoder2_15b's
widest linear), with the blocks the mapper plans, for a described v5e chip,
and check the result holds the Pallas custom call. The topology is
described inside a fixture, never at import: only one process at a time may
load the TPU compiler's library.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.kernels.fwht import fwht_pallas
from repro.kernels.ovsf_gemm import ovsf_decompress, ovsf_gemm
from repro.runtime import mapper

ARCH = "tinyllama_1_1b"
ROWS = 8                                    # decode step of 8 slots


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    # libtpu is pinned in requirements.txt: failing to describe the chip is
    # a broken installation, and fails these tests rather than skipping.
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _plan(alpha_dtype: str, name: str, arch: str = ARCH, rows: int = ROWS):
    cfg = get_config(arch)
    cfg = cfg.replace(ovsf=dataclasses.replace(cfg.ovsf,
                                               alpha_dtype=alpha_dtype))
    shape = ShapeConfig("serve_decode", 1, rows, "decode")
    return cfg, mapper.plan_model(cfg, shape, hw="v5e").plan_for(name)


@pytest.mark.parametrize("alpha_dtype,name,arch,rows", [
    pytest.param("", "attn_q", ARCH, ROWS, id="-attn_q"),
    pytest.param("", "mlp_up", ARCH, ROWS, id="-mlp_up"),
    pytest.param("", "mlp_down", ARCH, ROWS, id="-mlp_down"),
    pytest.param("int8", "mlp_down", ARCH, ROWS, id="int8-mlp_down"),
    pytest.param("int4", "mlp_up", ARCH, ROWS, id="int4-mlp_up"),
    # d_in 24576, J 12288: the generator loop runs 1 of 96 alpha chunks
    pytest.param("", "mlp_down", "starcoder2_15b", 128,
                 id="starcoder2_15b-mlp_down"),
])
def test_ovsf_gemm_compiles_at_planned_blocks(one_chip, alpha_dtype, name,
                                              arch, rows):
    cfg, lp = _plan(alpha_dtype, name, arch, rows)
    assert lp.path == "fused"
    d_in, d_out = {"attn_q": (cfg.d_model, cfg.n_heads * cfg.hd),
                   "mlp_up": (cfg.d_model, cfg.d_ff),
                   "mlp_down": (cfg.d_ff, cfg.d_model)}[name]
    seg = cfg.ovsf.seg_len
    n_seg, keep = d_in // seg, int(round(cfg.ovsf.rho * seg))
    J = n_seg * keep
    stored = (J, d_out // 2 if alpha_dtype == "int4" else d_out)
    shapes = [((rows, d_in), jnp.bfloat16),
              (stored, jnp.int8 if alpha_dtype else jnp.bfloat16),
              ((n_seg, keep), jnp.int32)]
    if alpha_dtype:
        shapes.append(((n_seg,), jnp.float32))

    def fn(x, al, idx, *scale):
        return ovsf_gemm(x, al, idx, alpha_scale=scale[0] if scale else None,
                         alpha_dtype=alpha_dtype, block_m=lp.block_m,
                         block_n=lp.block_n, block_k=lp.block_k,
                         block_j=lp.block_j)

    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def test_ovsf_decompress_compiles(one_chip):
    d_in, d_out = get_config(ARCH).d_ff, get_config(ARCH).d_model
    J = d_in // 2
    text = _compiled_text(lambda al, idx: ovsf_decompress(al, idx, d_in=d_in),
                          one_chip, ((J, d_out), jnp.bfloat16),
                          ((J,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("L", [2048, 8192])
def test_fwht_pallas_compiles(one_chip, L):
    text = _compiled_text(fwht_pallas, one_chip, ((512, L), jnp.bfloat16))
    assert "tpu_custom_call" in text
