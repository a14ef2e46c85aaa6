"""Reduction of a ``--trace 1`` run's profile to the serving program's own
layers: device time by named scope, the engine's host spans, and the queue
wait of the requests admitted in the window.

The profile names each device operation by its HLO instruction only. The
scope of an instruction is the innermost known scope in its ``op_name``
metadata (``embed``, ``attention``, ``linear.<weight type>``, ``moe`` and
``moe.<part>``, ``unembed``, ``sample``), read from the compiled text of the
step programs the run called (``repro.serving.trace.step_program_texts``);
an instruction with none of its own takes the one its users, else its
operands, share. Each leaf operation of the window counts once: in its
scope when it ran inside a run of a step program (a
``jit__paged(<fingerprint>)`` event of the ``XLA Modules`` line, matched to
the compiled text whose instructions it ran); in ``other`` when its
instruction has no scope; in ``outside_step`` when it ran in no step
program (the uploads of the step's host arrays, for example). A fusion
counts in the scope of its root.

JAX's persistent compile cache leaves metadata out of its key, so a loaded
executable carries the metadata of the run that wrote its entry. For these
step programs that run had the same scopes: the key of a program holding a
Pallas kernel covers the kernel's source locations (file, line and function
of each frame that called it), so a checkout in another directory, or an
edit that moves a line on that path, writes an entry of its own.

Host spans are the benchmark's ``bench.*`` and the engine's ``engine.*``
``TraceAnnotation`` events; ``engine.admit`` carries a request's queue wait
as an argument. A program without these (one older than them) gives no
scope map and no engine spans, and every number here reads ``None``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from collections import defaultdict
from pathlib import Path
from typing import Callable, Optional

from bench import trace as T

KNOWN = {"embed", "attention", "moe", "moe.router", "moe.dispatch",
         "moe.experts", "moe.combine", "unembed", "sample"}
OTHER, OUTSIDE = "other", "outside_step"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+)")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_WRAP = re.compile(r"^[\w.\-]+\((.*)\)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"(calls|to_apply|body|condition|branch_computations)="
                     r"(\{[^}]*\}|%[\w.\-]+)")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    args: tuple = ()

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def arg(self, key: str):
        return dict(self.args).get(key)


@dataclasses.dataclass
class Profile:
    ops: list              # leaf-marked trace.Event of device 0, named by
                           # their instruction's text
    modules: list          # Event per program run on device 0
    spans: list            # Span: bench.*, sorted by start
    program_spans: list    # Span: engine.*, sorted by start

    def window(self) -> Optional[tuple]:
        ws = [s for s in self.spans if s.name == "bench.window"]
        return (ws[0].start, ws[-1].end) if ws else None


def load(path: str) -> Profile:
    """Read an ``.xplane.pb`` file, or one compressed as ``.xplane.pb.gz``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [T.Event(e.name, e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                    (ops if line.name == "XLA Ops" else modules).extend(evs)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend(
                    Span(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         tuple(e.stats))
                    for e in line.events
                    if e.name.startswith(("bench.", "engine.")))
    spans.sort(key=lambda s: s.start)
    return Profile(T.mark_leaves(ops), sorted(modules, key=lambda e: e.start),
                   [s for s in spans if s.name.startswith("bench.")],
                   [s for s in spans if s.name.startswith("engine.")])


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

def scope_of(op_name: str) -> Optional[str]:
    """The innermost known scope of an ``op_name``, unwrapping transform
    names such as ``vmap(attention)``; None where it has none."""
    for part in reversed(op_name.split("/")):
        while (m := _WRAP.match(part)) is not None:
            part = m.group(1)
        if part in KNOWN or part.startswith("linear."):
            return part
    return None


def instr_key(text: str) -> Optional[str]:
    """The instruction's name and the first word of its shape:
    ``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion.3 bf16[8]{0}``."""
    m = _INSTR.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else None


@dataclasses.dataclass
class ScopeMap:
    module: str            # the HloModule's name, e.g. jit__paged
    scopes: dict           # instr_key -> scope, or None

    @property
    def known(self) -> bool:
        return any(s is not None for s in self.scopes.values())


def _instructions(text: str) -> dict:
    """instr_key -> (the line without its metadata, op_name)."""
    out = {}
    for line in text.splitlines():
        key = instr_key(line)
        if key is not None:
            m = _OP_NAME.search(line)
            out[key] = (_METADATA.sub("", line).strip(),
                        m.group(1) if m else "")
    return out


def _inherit(ins: dict, scopes: dict) -> dict:
    """Instructions with no known scope of their own (the compiler's
    converts, broadcasts and copies, which carry no ``op_name``, and the
    layer scan's slices of stacked weights) take the scope their users
    share, else the one their operands share, until nothing changes."""
    key_of = {k.split(" ")[0]: k for k in ins}
    operands = {}
    for k, (body, _) in ins.items():
        rhs = _CALLED.sub("", body.split(" = ", 1)[1])
        operands[k] = [key_of[n] for n in _OPERAND.findall(rhs)
                       if n in key_of and key_of[n] != k]
    users = defaultdict(list)
    for k, ops in operands.items():
        for o in ops:
            users[o].append(k)
    out = dict(scopes)
    changed = True
    while changed:
        changed = False
        for k, s in out.items():
            if s is not None:
                continue
            for near in (users[k], operands[k]):
                shared = {out[n] for n in near} - {None}
                if len(shared) == 1:
                    out[k] = shared.pop()
                    changed = True
                    break
    return out


def scope_map(text: str) -> ScopeMap:
    """The scope of each instruction of a compiled program's text: that of
    its ``op_name``, else the one it inherits (``_inherit``)."""
    m = _MODULE.search(text)
    ins = _instructions(text)
    return ScopeMap(m.group(1) if m else "", _inherit(
        ins, {k: scope_of(op) for k, (_, op) in ins.items()}))


def program_maps() -> list:
    """Scope maps of the step programs this process ran; none for a program
    older than ``repro.serving.trace``."""
    try:
        from repro.serving import trace as program
    except ImportError:
        return []
    return [scope_map(t) for t in program.step_program_texts()]


class _Runs:
    """The runs of the step programs (those with a map) on the device, and
    which run each operation ran in."""

    def __init__(self, modules, maps):
        names = {m.module for m in maps}
        self.runs = [r for r in modules if r.name.split("(")[0] in names]
        self.starts = [r.start for r in self.runs]

    def of(self, e):
        i = bisect.bisect_right(self.starts, e.start) - 1
        run = self.runs[i] if i >= 0 else None
        return run if run is not None and e.start < run.end else None


def match_programs(ops, modules, maps) -> dict:
    """Program (its module event's name, with fingerprint) -> index of the
    map that knows most of the instructions its runs ran."""
    runs = _Runs(modules, maps)
    ran = defaultdict(set)
    for e in ops:
        r = runs.of(e)
        if e.leaf and r is not None:
            ran[r.name].add(instr_key(e.name))
    out = {}
    for prog, keys in ran.items():
        base = prog.split("(")[0]
        out[prog] = max((i for i, m in enumerate(maps) if m.module == base),
                        key=lambda i: len(keys & maps[i].scopes.keys()))
    return out


def device_seconds_by_scope(ops, modules, maps, t0: float,
                            t1: float) -> tuple:
    """Device seconds of the window's leaf operations by scope, with
    ``other`` and ``outside_step``, and the number of step-program runs
    that start in the window. The seconds are None where no map holds a
    known scope."""
    runs = _Runs(modules, maps)
    steps = sum(1 for r in runs.runs if t0 <= r.start < t1)
    if not any(m.known for m in maps):
        return None, steps
    window_ops = [e for e in T.clip(ops, t0, t1) if e.leaf]
    best = match_programs(window_ops, modules, maps)
    out: dict = defaultdict(float, {OTHER: 0.0, OUTSIDE: 0.0})
    for e in window_ops:
        r = runs.of(e)
        scope = OUTSIDE if r is None else \
            maps[best[r.name]].scopes.get(instr_key(e.name)) or OTHER
        out[scope] += e.seconds
    return dict(out), steps


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def _children(spans) -> dict:
    """index -> indices of the spans directly inside it (spans of one
    thread nest)."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].start, -spans[i].end))
    kids: dict = defaultdict(list)
    stack: list = []
    for i in order:
        while stack and spans[stack[-1]].end <= spans[i].start:
            stack.pop()
        if stack:
            kids[stack[-1]].append(i)
        stack.append(i)
    return kids


def label(name: str) -> str:
    return "host:" + (name[len("bench."):] if name.startswith("bench.")
                      else name)


def idle_gaps(events, spans, t0: float, t1: float, n: int = 10) -> list:
    """The ``n`` longest gaps in which no operation ran, each labelled by
    the span with the most self time in it (its time in the gap less its
    children's), ``host:none`` where none was open. Among equal self times
    the later-starting span wins."""
    busy = T.merge(T.clip(events, t0, t1))
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    spans = [s for s in spans if s.name != "bench.window"]
    kids = _children(spans)
    out = []
    for a, b in gaps[:n]:
        def cover(i):
            return max(0.0, min(b, spans[i].end) - max(a, spans[i].start))
        best, most = "host:none", 0.0
        for i, s in enumerate(spans):
            c = cover(i)
            if c <= 0:
                continue
            own = c - sum(cover(k) for k in kids.get(i, ()))
            if own > 0 and own >= most:
                best, most = label(s.name), own
        out.append([best, b - a])
    return out


def host_step_seconds(program_spans, t0: float, t1: float) -> list:
    """Per ``engine.step`` that starts in the window: its time less that of
    its ``engine.wait`` children, the host's own work in the step."""
    steps = [s for s in program_spans if s.name == "engine.step"]
    waits = [s for s in program_spans if s.name == "engine.wait"]
    out = []
    for s in steps:
        if t0 <= s.start < t1:
            waited = sum(w.seconds for w in waits
                         if s.start <= w.start and w.end <= s.end)
            out.append(s.seconds - waited)
    return out


def queue_waits(program_spans, t0: float, t1: float) -> list:
    """``t_admit - t_submit`` of each request admitted in the window."""
    return [float(s.arg("queue_wait_s")) for s in program_spans
            if s.name == "engine.admit" and t0 <= s.start < t1
            and s.arg("queue_wait_s") is not None]


# ---------------------------------------------------------------------------
# one run's reduction, shared by the metric readers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Reduction:
    scope_s: Optional[dict]   # scope -> device seconds in the window
    steps: int                # step-program runs starting in the window
    host_step_s: list
    queue_waits: list
    gaps: list

    def scope_ms(self, match: Callable[[str], bool]) -> Optional[float]:
        if self.scope_s is None or not self.steps:
            return None
        return 1e3 * sum(v for k, v in self.scope_s.items()
                         if match(k)) / self.steps


def reduce(prof: Profile, maps: list, t0: float, t1: float) -> Reduction:
    scope_s, steps = device_seconds_by_scope(prof.ops, prof.modules, maps,
                                             t0, t1)
    return Reduction(scope_s, steps,
                     host_step_seconds(prof.program_spans, t0, t1),
                     queue_waits(prof.program_spans, t0, t1),
                     idle_gaps(prof.ops, prof.spans + prof.program_spans,
                               t0, t1))


def _profile_of(root: Path, window: tuple) -> Optional[Profile]:
    """This process's trace whose window is ``window`` (``bench/run.py``
    writes it under ``.bench_out/trace/<cell>-<seed>-<pid>``)."""
    pattern = str(root / ".bench_out" / "trace" / f"*-{os.getpid()}" / "**"
                  / "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        prof = load(path)
        if prof.window() == tuple(window):
            return prof
    return None


_DONE: dict = {}


def of(ctx, root: Path) -> Optional[Reduction]:
    """The reduction of the traced window a metric reader's ``ctx`` holds,
    made once per run; it prints one ``[bench] scopes:`` line (ms per step
    by scope, None where the program has no scopes) and one
    ``[bench] program_gaps:`` line."""
    if ctx.trace is None or ctx.trace_window is None:
        return None
    key = (str(root), tuple(ctx.trace_window))
    if key not in _DONE:
        prof = _profile_of(root, ctx.trace_window)
        red = None
        if prof is not None:
            red = reduce(prof, program_maps(), *ctx.trace_window)
            ms = None if red.scope_s is None or not red.steps else {
                k: 1e3 * v / red.steps for k, v in sorted(red.scope_s.items())}
            print("[bench] scopes: " + json.dumps(ms), flush=True)
            print("[bench] program_gaps: " + json.dumps(red.gaps), flush=True)
        _DONE[key] = red
    return _DONE[key]

