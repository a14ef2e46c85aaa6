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


@pytest.mark.parametrize("name", ["chat", "codegen", "batch"])
def test_same_seed_same_requests(name):
    spec = _spec(name)
    a, b = _draw(spec, 2**31 + 5, 70), _draw(spec, 2**31 + 5, 70)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


def _first(spec):
    """How many requests open the loop cut to a steady start."""
    return spec["clients"] if spec.get("start") == "steady" else 0


@pytest.mark.parametrize("name", ["chat", "codegen", "batch"])
def test_other_seed_other_order_same_mix(name):
    spec = _spec(name)
    n0 = _first(spec)
    a, b = _draw(spec, 1, n0 + 64)[n0:], _draw(spec, 2, n0 + 64)[n0:]
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
    for name in ("chat", "codegen", "batch"):
        spec = _spec(name)
        for r in _draw(spec, 9, 3 * spec["clients"]):
            p, o = spec["prompt_tokens"], spec["output_tokens"]
            assert p["min"] <= len(r.prompt) <= p["max"]
            least = 1 if r.index < _first(spec) else o["min"]
            assert least <= r.max_new <= o["max"]
            assert len(r.prompt) + r.max_new <= spec["engine"]["buffer"]


def test_lognormal_median():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 32,
         "max": 768}
    assert traffic.quantile(d, 0.5) == 256
    assert traffic.quantile(d, 1e-9) == 32 and traffic.quantile(d, 1 - 1e-9) \
        == 768


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_a_steady_start_leaves_what_a_steady_state_has_in_flight(seed):
    """The first ``clients`` requests are cut to what is left of requests in
    flight at steady state: on average half a size-biased output length,
    E[L^2] / (2 E[L]), so that as many end in the first steps as at steady
    state, 1 / E[L] a slot a step, and another seed leaves the same set of
    lengths. Later requests are drawn as without it."""
    spec = _spec("batch")
    n = spec["clients"]
    grid = [(i + 0.5) / 4096 for i in range(4096)]
    lengths = np.array([traffic.quantile(spec["output_tokens"], q)
                        for q in grid], np.float64)
    left = [r.max_new for r in _draw(spec, seed, n)]
    assert min(left) >= 1 and max(left) <= spec["output_tokens"]["max"]
    residual = (lengths ** 2).mean() / (2 * lengths.mean())
    assert np.mean(left) == pytest.approx(residual, rel=0.1)
    ends = sum(m <= 80 for m in left)       # in 80 decode steps
    assert ends == pytest.approx(80 * n / lengths.mean(), abs=2)
    other = sorted(r.max_new for r in _draw(spec, seed + 1, n))
    assert max(abs(a - b) for a, b in zip(sorted(left), other)) <= \
        0.05 * spec["output_tokens"]["max"]
    plain = {k: v for k, v in spec.items() if k != "start"}
    a, b = _draw(spec, seed, 2 * n), _draw(plain, seed, 2 * n)
    assert [r.max_new for r in a[n:]] == [r.max_new for r in b[n:]]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_an_unknown_start_is_refused():
    with pytest.raises(ValueError, match="unknown start"):
        traffic.ClosedLoop(dict(_spec("batch"), start="warm"), 1, 100)
