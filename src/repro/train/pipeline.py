"""GPipe-style pipeline parallelism over the 'pod' mesh axis.

Multi-pod meshes make the pod axis the slow communication domain, so the
natural layout is one pipeline stage per pod: layer-stacked params are
sharded over 'pod' on the layer dim, microbatches flow stage-to-stage via
``ppermute`` inside a ``shard_map``. The schedule is the classic GPipe fill/
drain: T = n_micro + n_stages - 1 rotation slots, bubble slots compute on
masked (zero) activations and are discarded.

This is the optional PP mode from DESIGN.md §5: off by default (the dry-run
uses FSDP over ('pod','data')); enabled here as a first-class building block
with a correctness test (pipeline == sequential stack) and usable on any
mesh with a 'pod' axis.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import transformer as T


def _apply_local_layers(blocks_local, cfg: ModelConfig, x, positions):
    """Run this stage's slice of the layer stack (scan, like _scan_stack)."""
    def body(carry, pp):
        h, _, _ = T.block_apply(pp, cfg, T._layer_kind(cfg), carry,
                                positions=positions)
        return h, None
    y, _ = jax.lax.scan(body, x, blocks_local)
    return y


def gpipe_apply(mesh: Mesh, cfg: ModelConfig, stacked_blocks, x,
                *, n_micro: int, axis: str = "pod"):
    """Pipeline the trunk over the pod axis.

    stacked_blocks: params pytree with leading n_layers dim (divisible by the
    pod-axis size). x: (B, S, d) embedded activations (B divisible by
    n_micro). Returns trunk output (B, S, d), identical (up to fp error) to
    the sequential stack.
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    B, S, d = x.shape
    assert B % n_micro == 0 and cfg.n_layers % n_stages == 0
    mb = B // n_micro
    positions = jnp.arange(S)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_fn(blocks_local, xm):
        # blocks_local: this pod's (L/S, ...) layer slice; xm: (n_micro, mb, S, d)
        stage = jax.lax.axis_index(axis)
        carry = jnp.zeros((mb, S, d), x.dtype)
        outs = jnp.zeros((n_micro, mb, S, d), x.dtype)
        T_slots = n_micro + n_stages - 1
        for t in range(T_slots):
            inject = xm[min(t, n_micro - 1)]
            h = jnp.where(stage == 0, inject, carry)
            h = _apply_local_layers(blocks_local, cfg, h, positions)
            # last stage banks microbatch t-(n_stages-1) when valid
            out_idx = t - (n_stages - 1)
            if 0 <= out_idx < n_micro:
                keep = (stage == n_stages - 1)
                outs = outs.at[out_idx].set(jnp.where(keep, h, outs[out_idx]))
            carry = jax.lax.ppermute(h, axis, perm)
        # broadcast the last stage's outputs to every pod member
        outs = jax.lax.psum(
            jnp.where(jax.lax.axis_index(axis) == n_stages - 1, outs, 0.0),
            axis)
        return outs

    other = tuple(a for a in mesh.axis_names if a != axis)
    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stacked_blocks),
        P(),
    )
    fn = jax.shard_map(stage_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    xm = x.reshape(n_micro, mb, S, d)
    outs = fn(stacked_blocks, xm)
    return outs.reshape(B, S, d)
