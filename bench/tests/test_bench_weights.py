"""The weights' rules: a router's per-expert correction bias is drawn as
N(0, 0.1^2) from the seed and the leaf's path, and a leaf with no rule is
refused."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import weights  # noqa: E402


def _layout(name, shape=(64, 384), dtype=jnp.float32, parent="router"):
    return {"blocks": {"moe": {parent: {
        name: jax.ShapeDtypeStruct(shape, dtype)}}}}


def _bias(seed, **kw):
    out = weights.make(_layout("bias", **kw), jax.random.PRNGKey(seed), 16)
    return out["blocks"]["moe"]["router"]["bias"]


def test_the_router_bias_is_drawn_at_a_tenth():
    b = np.asarray(_bias(2**31 + 3), np.float64)
    assert b.shape == (64, 384)
    assert b.std() == pytest.approx(0.1, rel=0.05)
    assert abs(b.mean()) < 0.005
    assert not np.array_equal(b[0], b[1])     # each layer its own bias


def test_the_same_seed_and_path_give_the_same_bias():
    a, b = _bias(7, dtype=jnp.bfloat16), _bias(7, dtype=jnp.bfloat16)
    assert a.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
    assert not np.array_equal(np.asarray(_bias(8)), np.asarray(_bias(7)))


@pytest.mark.parametrize("parent,name", [("router", "gain"),
                                         ("ln", "bias")])
def test_a_leaf_with_no_rule_is_refused(parent, name):
    """An unknown leaf name, and a LayerNorm's bias (a ``bias`` outside a
    router), are refused."""
    with pytest.raises(ValueError, match=f"no rule.*{parent}/{name}"):
        weights.make(_layout(name, parent=parent), jax.random.PRNGKey(0),
                     16)
