"""Window arithmetic: what the measured window counts, from host time stamps.

A request is recorded by the benchmark as its send time and the times at
which each of its tokens was committed. The window is [t0, t1]:

* output tokens: tokens committed inside the window;
* inter-token gaps: between consecutive tokens of one request, both inside;
* time to first token: for requests whose first token falls inside, from
  the client's send to that token (send may precede the window).

Percentiles interpolate linearly between order statistics, as numpy's
default ``percentile`` does.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Record:
    send: float
    token_times: list


def percentile(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else None


@dataclasses.dataclass
class WindowCounts:
    seconds: float
    tokens: int
    itls: list
    ttfts: list


def count(records, t0: float, t1: float) -> WindowCounts:
    tokens, itls, ttfts = 0, [], []
    for r in records:
        ts = r.token_times
        inside = [t for t in ts if t0 <= t <= t1]
        tokens += len(inside)
        itls.extend(b - a for a, b in zip(inside, inside[1:]))
        if ts and t0 <= ts[0] <= t1:
            ttfts.append(ts[0] - r.send)
    return WindowCounts(t1 - t0, tokens, itls, ttfts)


def end_to_end(c: WindowCounts) -> dict:
    """The end-to-end numbers of one window (None where it has no sample)."""
    itl = percentile(c.itls, 95)
    return {"output_tok_s": c.tokens / c.seconds if c.seconds > 0 else None,
            "itl_p95_ms": None if itl is None else itl * 1e3,
            "ttft_p90_s": percentile(c.ttfts, 90)}
