"""Weights from the seed, made on the device in one jitted call, in the layout
and dtype the program serves.

The layout (which leaves exist, their shapes and dtypes) is read from the
program with ``jax.eval_shape``; the values are the benchmark's own:

* ``idx``: per length-L0 segment, a sorted random choice of round(rho * L0)
  of the L0 codes;
* ``alphas``: N(0, 1 / (d_in * n_keep)), so that each generated weight has
  variance 1 / d_in, as a dense fan-in initialisation would;
* ``w`` (dense linears): N(0, 1 / d_in); ``table`` (embedding): N(0, 1);
* ``scale`` (norms): 1 + N(0, 0.1^2);
* ``router/bias`` (a router's per-expert correction bias, ``topk_method``
  ``noaux_tc``): N(0, 0.1^2). With x at unit RMS and the router's ``w`` at
  N(0, 1/d), the logits are about N(0, 1) and the sigmoid scores spread by
  about 0.2, so a bias of 0.1 moves some top-k picks: a program that picks
  by score alone, or weights its picks by score plus bias, departs from the
  reference.

A leaf of any other name, or a ``bias`` outside a ``router``, is an error: a
layout this file has no rule for.

Stacked leaves (a leading layer or expert axis) are drawn one slice at a
time, so the call's temporaries stay the size of one layer.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _sliced(fn, key, shape, n_lead: int):
    """fn(key, shape) drawn one leading slice at a time."""
    if n_lead == 0 or len(shape) <= 2:
        return fn(key, shape)
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(lambda k: _sliced(fn, k, shape[1:], n_lead - 1), keys)


def _codes(key, shape, seg: int):
    """(..., n_seg, n_keep) sorted random code ids in [0, seg)."""
    nk = shape[-1]
    u = jax.random.uniform(key, tuple(shape[:-1]) + (seg,))
    return jnp.sort(jnp.argsort(u, axis=-1)[..., :nk], axis=-1).astype(
        jnp.int32)


def _make(key, spec, seg: int, path: str = ""):
    if isinstance(spec, dict):
        if "alphas" in spec:
            idx_s, al_s = spec["idx"], spec["alphas"]
            ns, nk = idx_s.shape[-2:]
            d_in = ns * seg
            std = (1.0 / (d_in * nk)) ** 0.5
            out = {k: _make(key, v, seg, f"{path}/{k}")
                   for k, v in spec.items() if k not in ("alphas", "idx")}
            out["idx"] = _codes(_key(key, path + "/idx"), idx_s.shape, seg)
            lead = len(al_s.shape) - 2
            out["alphas"] = _sliced(
                lambda k, s: (jax.random.normal(k, s, jnp.float32) * std
                              ).astype(al_s.dtype),
                _key(key, path + "/alphas"), al_s.shape, lead)
            return out
        return {k: _make(key, v, seg, f"{path}/{k}") for k, v in spec.items()}
    name = path.rsplit("/", 1)[-1]
    shape, dtype = spec.shape, spec.dtype
    k = _key(key, path)
    if name == "w":
        std = (1.0 / shape[-2]) ** 0.5
        return _sliced(lambda k, s: (jax.random.normal(k, s, jnp.float32)
                                     * std).astype(dtype),
                       k, shape, len(shape) - 2)
    if name == "table":
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)
    if name == "scale":
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)
    if path.endswith("/router/bias"):
        return _sliced(lambda k, s: (jax.random.normal(k, s, jnp.float32)
                                     * 0.1).astype(dtype),
                       k, shape, len(shape) - 1)
    raise ValueError(f"no rule to make weight leaf {path!r}")


def make(layout, seed_key, seg: int):
    """The weights for ``layout`` (a pytree of ShapeDtypeStruct, as
    ``jax.eval_shape`` of the program's initialiser gives it), from
    ``seed_key``, in one jitted call on the default device."""
    return jax.jit(lambda k: _make(k, layout, seg))(seed_key)
