"""The load generator: one seed gives one sequence of requests, another seed
another order of the same sizes."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import traffic  # noqa: E402
from bench.run import load_json  # noqa: E402

HERE = os.path.dirname(__file__)


def _spec(name):
    return load_json(os.path.join(HERE, "..", "traffic", f"{name}.json"))


def _draw(spec, seed, n):
    loop = traffic.ClosedLoop(spec, seed, vocab=50304)
    return [loop.next() for _ in range(n)]


@pytest.mark.parametrize("name", ["chat", "codegen"])
def test_same_seed_same_requests(name):
    spec = _spec(name)
    a, b = _draw(spec, 2**31 + 5, 70), _draw(spec, 2**31 + 5, 70)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["chat", "codegen"])
def test_other_seed_other_order_same_mix(name):
    spec = _spec(name)
    a, b = _draw(spec, 1, 64), _draw(spec, 2, 64)
    assert [r.max_new for r in a] != [r.max_new for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # any 32 consecutive requests carry nearly the same work, whatever the
    # seed: mean lengths within 10% of the distribution's own
    grid = [(i + 0.5) / 4096 for i in range(4096)]
    for key, rs in (("prompt_tokens", lambda r: len(r.prompt)),
                    ("output_tokens", lambda r: r.max_new)):
        want = np.mean([traffic.quantile(spec[key], q) for q in grid])
        for reqs in (a[:32], a[32:], b[:32], b[16:48]):
            assert np.mean([rs(r) for r in reqs]) == pytest.approx(
                want, rel=0.1), key


def test_lengths_stay_in_bounds_and_fit_the_buffer():
    for name in ("chat", "codegen"):
        spec = _spec(name)
        for r in _draw(spec, 9, 3 * spec["clients"]):
            p, o = spec["prompt_tokens"], spec["output_tokens"]
            assert p["min"] <= len(r.prompt) <= p["max"]
            assert o["min"] <= r.max_new <= o["max"]
            assert len(r.prompt) + r.max_new <= spec["engine"]["buffer"]


def test_lognormal_median():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 32,
         "max": 768}
    assert traffic.quantile(d, 0.5) == 256
    assert traffic.quantile(d, 1e-9) == 32 and traffic.quantile(d, 1 - 1e-9) \
        == 768
