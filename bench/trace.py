"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time as the union of the intervals in
which an operation ran, time by operation name, and the idle gaps between
operations labelled by the host span the benchmark had open.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO instruction (the event's name
is the instruction's text, ``%name = shape op(...)``; the name is what comes
before `` = ``). Operations that hold others, such as the ``while`` of a
scan over layers, count towards busy time but are left out of time by name.
Host spans are the benchmark's own ``TraceAnnotation`` events (names starting
with ``bench.``) on host planes. All times are seconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Callable, Optional


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    leaf: bool = True

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict              # device id -> [Event], sorted by start
    spans: list            # benchmark host spans, sorted by start

    def window(self, name: str = "bench.window") -> Optional[tuple]:
        ws = [s for s in self.spans if s.name == name]
        return (ws[0].start, ws[-1].end) if ws else None


_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OP = re.compile(r"^%?([^\s=]+)")


def op_name(text: str) -> str:
    """``%ovsf_gemm.46 = bf16[256,2048] custom-call(...)`` -> ``ovsf_gemm.46``."""
    m = _OP.match(text)
    return m.group(1) if m else text


def mark_leaves(events: list) -> list:
    """Sorted by start; an event that another event starts inside is not a
    leaf."""
    events = sorted(events, key=lambda e: (e.start, -e.end))
    stack: list = []
    holds = set()
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            holds.add(stack[-1])
        stack.append(i)
    return [dataclasses.replace(e, leaf=i not in holds)
            for i, e in enumerate(events)]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb*"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or one compressed as ``.xplane.pb.gz``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    ops: dict = {}
    spans: list = []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                ops.setdefault(int(m.group(1)), []).extend(
                    Event(op_name(e.name), e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                spans.extend(Event(e.name, e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events
                             if e.name.startswith("bench."))
    ops = {d: mark_leaves(evs) for d, evs in ops.items()}
    spans.sort(key=lambda e: e.start)
    return Trace(ops, spans)


def clip(events, t0: float, t1: float) -> list:
    out = []
    for e in events:
        s, t = max(e.start, t0), min(e.end, t1)
        if t > s:
            out.append(Event(e.name, s, t, e.leaf))
    return out


def merge(events) -> list:
    """Union of intervals, as sorted disjoint (start, end) pairs."""
    out: list = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [tuple(p) for p in out]


def busy_seconds(events, t0: float, t1: float) -> float:
    return sum(b - a for a, b in merge(clip(events, t0, t1)))


def seconds_by_name(events, t0: float, t1: float,
                    match: Callable[[str], bool] = lambda n: True) -> dict:
    """Device seconds of the leaf operations in the window, by name."""
    out: dict = defaultdict(float)
    for e in clip(events, t0, t1):
        if e.leaf and match(e.name):
            out[e.name] += e.seconds
    return dict(out)


def top_ops(events, t0: float, t1: float, n: int = 10) -> list:
    by = seconds_by_name(events, t0, t1)
    return sorted(([k, v] for k, v in by.items()), key=lambda kv: -kv[1])[:n]


def idle_gaps(events, spans, t0: float, t1: float, n: int = 10) -> list:
    """The ``n`` longest gaps in which no operation ran, each labelled by the
    innermost benchmark span that covers most of it (``host:none`` where the
    benchmark had none open)."""
    busy = merge(clip(events, t0, t1))
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for a, b in gaps[:n]:
        best, cover = "host:none", 0.0
        for s in spans:
            if s.name == "bench.window":
                continue
            c = min(b, s.end) - max(a, s.start)
            # innermost: among equal cover, the later-starting span wins
            if c > 0 and c >= cover:
                best, cover = "host:" + s.name[len("bench."):], c
        out.append([best, b - a])
    return out
