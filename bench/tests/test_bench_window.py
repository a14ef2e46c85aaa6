"""Window arithmetic: what the measured window counts, and that a stall
inside it moves the inter-token tail."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pytest  # noqa: E402

from bench import window as win  # noqa: E402


def test_percentile_interpolates_like_numpy():
    assert win.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert win.percentile([0, 10], 95) == pytest.approx(9.5)
    assert win.percentile([], 95) is None


def test_counts_only_what_falls_inside():
    recs = [win.Record(send=0.0, token_times=[0.5, 1.5, 2.0, 3.5]),
            win.Record(send=1.2, token_times=[2.5, 2.7]),
            win.Record(send=3.0, token_times=[])]
    c = win.count(recs, 1.0, 3.0)
    assert c.tokens == 4                      # 1.5, 2.0, 2.5, 2.7
    assert sorted(c.itls) == pytest.approx([0.2, 0.5])
    assert c.ttfts == pytest.approx([1.3])    # first token 0.5 is outside
    e = win.end_to_end(c)
    assert e["output_tok_s"] == pytest.approx(2.0)


def _steady(n_req=20, n_tok=150, dt=0.1, stall_at=(), stall=2.0):
    recs = []
    for r in range(n_req):
        t, ts = 0.0, []
        for k in range(n_tok):
            t += dt + (stall if k in stall_at else 0.0)
            ts.append(t)
        recs.append(win.Record(0.0, ts))
    return recs


def test_a_stall_inside_the_window_moves_itl_p95():
    base = win.end_to_end(win.count(_steady(), 0.0, 10.0))
    # three long steps: 3 of each client's ~30 gaps in the window are 20x longer
    stalled = win.end_to_end(win.count(_steady(stall_at=(10, 20, 30)),
                                       0.0, 10.0))
    assert base["itl_p95_ms"] == pytest.approx(100.0)
    assert stalled["itl_p95_ms"] > 1.5 * base["itl_p95_ms"]
    assert stalled["output_tok_s"] < base["output_tok_s"]


def test_a_stall_outside_the_window_does_not():
    base = win.end_to_end(win.count(_steady(), 0.0, 1.95))
    late = win.end_to_end(win.count(_steady(stall_at=(30,)), 0.0, 1.95))
    assert late == base
