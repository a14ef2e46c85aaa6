"""Sharding rules: every param/cache leaf gets a legal PartitionSpec."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.models import registry as R
from repro.sharding.rules import ShardingRules


def _mesh():
    n = len(jax.devices())
    from repro.launch.mesh import make_mesh
    return make_mesh((n, 1), ("data", "model"))


@pytest.mark.parametrize("arch", ["tinyllama_1_1b", "olmoe_1b_7b",
                                  "falcon_mamba_7b", "whisper_tiny"])
def test_param_specs_cover_every_leaf(arch):
    cfg = get_smoke_config(arch)
    specs = R.model_init_specs(cfg)
    rules = ShardingRules(_mesh())
    pspecs = rules.params_specs(specs)
    flat_s = jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P))
    flat_p = jax.tree_util.tree_leaves(specs)
    assert len(flat_s) == len(flat_p)
    for spec, leaf in zip(flat_s, flat_p):
        assert isinstance(spec, P)
        assert len(spec) <= len(leaf.shape)
        # every sharded dim must be divisible by its mesh axes
        for ax, name in enumerate(spec):
            if name is None:
                continue
            names = name if isinstance(name, tuple) else (name,)
            size = 1
            for nm in names:
                size *= dict(zip(rules.mesh.axis_names,
                                 rules.mesh.devices.shape))[nm]
            assert leaf.shape[ax] % size == 0, (spec, leaf.shape)


def test_idx_buffers_replicated():
    cfg = get_smoke_config("tinyllama_1_1b")
    specs = R.model_init_specs(cfg)
    rules = ShardingRules(_mesh())
    flat, _ = jax.tree_util.tree_flatten_with_path(rules.params_specs(specs))
    for path, spec in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        if name.endswith("idx"):
            assert spec == P(), name


def test_cache_specs_decode_seq_sharding():
    cfg = get_smoke_config("qwen2_5_14b")
    rules = ShardingRules(_mesh(), flash_decode_seq_shard=True)
    cspec = R.cache_spec(cfg, 4, 64)
    tree = rules.cache_spec_tree(cspec)
    # with model=1 mesh there is nothing to shard seq over; spec stays legal
    assert isinstance(tree["k"], P)
    assert tree["pos"] == P()


def test_no_fsdp_replicates_weights():
    cfg = get_smoke_config("tinyllama_1_1b")
    specs = R.model_init_specs(cfg)
    rules = ShardingRules(_mesh(), fsdp=False)
    flat = jax.tree_util.tree_leaves(rules.params_specs(specs),
                                     is_leaf=lambda x: isinstance(x, P))
    daxes = ("data", "pod")
    for spec in flat:
        for entry in spec:
            names = entry if isinstance(entry, tuple) else (entry,)
            assert not any(n in daxes for n in names if n), spec


def test_local_mesh_refuses_more_devices_than_exist():
    from repro.launch.mesh import make_local_mesh
    n = len(jax.devices())
    mesh = make_local_mesh(n, 1)
    assert mesh.devices.size == n
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(n + 1, 1)
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(1, 2 * n)
