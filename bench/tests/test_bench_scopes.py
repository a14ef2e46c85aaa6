"""The scope reduction (bench/scopes.py) on hand-made events and compiled
texts, the self-time labels of idle gaps, the per-layer readers that use it,
and the scope maps of a tiny engine's compiled step on the CPU."""
import gzip
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pytest  # noqa: E402

from bench import run as R  # noqa: E402
from bench import scopes as S  # noqa: E402
from bench import trace as T  # noqa: E402

E = T.Event
ROOT = Path(__file__).resolve().parents[2]
OLD = os.path.join(os.path.dirname(__file__), "data",
                   "olmoe_chat_3s.xplane.pb.gz")


def _text(rows: int, scoped: bool = True) -> str:
    def md(name):
        where = f"{name}/" if scoped else ""
        return f'metadata={{op_name="jit(_paged)/while/body/{where}op"}}'
    return f"""HloModule jit__paged, entry_computation_layout={{()}}

%fused_computation.1 (param_0: f32[{rows}]) -> f32[{rows}] {{
  %param_0 = f32[{rows}]{{0}} parameter(0)
  ROOT %exp.1 = f32[{rows}]{{0}} exponential(%param_0), {md("attention")}
}}

ENTRY %main.9 (p: f32[{rows}]) -> f32[{rows}] {{
  %p = f32[{rows}]{{0}} parameter(0)
  %convert.6 = f32[{rows}]{{0}} convert(%p)
  %fusion.1 = f32[{rows}]{{0}} fusion(%convert.6), kind=kLoop, calls=%fused_computation.1, {md("attention")}
  %ovsf_gemm.2 = bf16[{rows},8]{{1,0}} custom-call(%p), custom_call_target="tpu_custom_call", backend_config={{"metadata={{}}"}}, {md("linear.attn_q/jit(ovsf_gemm)")}
  %fusion.3 = f32[{rows}]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.1, {md("moe/moe.experts")}
  %copy.4 = f32[{rows}]{{0}} copy(%p)
  ROOT %fusion.5 = f32[{rows}]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.1, {md("vmap(sample)")}
}}
"""


def _op(name: str, rows: int, start: float, end: float) -> E:
    shape = {"ovsf_gemm.2": f"bf16[{rows},8]{{1,0}}"}.get(name,
                                                        f"f32[{rows}]{{0}}")
    return E(f"%{name} = {shape} op(%p)", start, end)


def _runs(rows: int = 32):
    """Two runs of a step program (32 rows) with an upload between, and a
    while op holding the second run's ops."""
    ops = [_op("fusion.1", rows, 0.0, 1.0), _op("ovsf_gemm.2", rows, 1.0, 3.0),
           _op("fusion.3", rows, 3.0, 3.5), _op("copy.4", rows, 3.5, 3.6),
           _op("fusion.5", rows, 3.6, 3.8),
           E("%copy.7 = s32[32]{0} copy(%args_0_.1)", 4.0, 4.5),
           E("%while.2 = (f32[32]) while(%t)", 5.0, 8.0),
           _op("fusion.1", rows, 5.0, 6.0), _op("ovsf_gemm.2", rows, 6.0, 7.5),
           _op("fusion.3", rows, 7.5, 8.0)]
    modules = [E("jit__paged(11)", 0.0, 3.9), E("jit_copy(5)", 4.0, 4.5),
               E("jit__paged(11)", 5.0, 8.2)]
    return T.mark_leaves(ops), modules


def test_scope_of_takes_the_innermost_known_scope():
    assert S.scope_of("jit(_paged)/while/body/attention/linear.attn_q/"
                      "jit(ovsf_gemm)/pallas_call") == "linear.attn_q"
    assert S.scope_of("jit(_paged)/moe/moe.experts/dot_general") \
        == "moe.experts"
    assert S.scope_of("jit(_b)/vmap(attention)/dot_general") == "attention"
    assert S.scope_of("jit(_paged)/while/body/add") is None


def test_scope_map_reads_each_instruction():
    m = S.scope_map(_text(32))
    assert m.module == "jit__paged" and m.known
    assert m.scopes["ovsf_gemm.2 bf16[32,8]{1,0}"] == "linear.attn_q"
    assert m.scopes["fusion.3 f32[32]{0}"] == "moe.experts"
    assert m.scopes["fusion.5 f32[32]{0}"] == "sample"
    assert m.scopes["copy.4 f32[32]{0}"] is None
    # no op name of its own: the scope its only user has
    assert m.scopes["convert.6 f32[32]{0}"] == "attention"
    assert not S.scope_map(_text(32, scoped=False)).known


def test_every_leaf_op_falls_in_one_scope():
    ops, modules = _runs()
    # the other bucket's program has the same module name: the runs are
    # matched to the text whose instructions they ran
    maps = [S.scope_map(_text(64)), S.scope_map(_text(32))]
    by, steps = S.device_seconds_by_scope(ops, modules, maps, 0.0, 10.0)
    assert steps == 2
    assert by == pytest.approx({"attention": 2.0, "linear.attn_q": 3.5,
                                "moe.experts": 1.0, "other": 0.1,
                                "sample": 0.2, "outside_step": 0.5})
    leaf = sum(e.seconds for e in ops if e.leaf)
    assert sum(by.values()) == pytest.approx(leaf)
    # a window that cuts the first run clips its ops
    by, steps = S.device_seconds_by_scope(ops, modules, maps, 2.0, 10.0)
    assert steps == 1 and by["linear.attn_q"] == pytest.approx(2.5)


def test_a_program_with_no_known_scope_reads_none():
    ops, modules = _runs()
    maps = [S.scope_map(_text(32, scoped=False))]
    by, steps = S.device_seconds_by_scope(ops, modules, maps, 0.0, 10.0)
    assert by is None and steps == 2
    red = S.Reduction(by, steps, [], [], [])
    assert red.scope_ms(lambda s: s == "attention") is None
    assert S.device_seconds_by_scope(ops, modules, [], 0.0, 10.0)[0] is None


def _nested_spans():
    Sp = S.Span
    return [Sp("bench.window", 0.0, 10.0), Sp("bench.step", 0.0, 9.0),
            Sp("engine.step", 0.1, 8.9), Sp("engine.schedule", 0.1, 0.5),
            Sp("engine.launch", 0.5, 0.6), Sp("engine.wait", 0.6, 8.5),
            Sp("engine.commit", 8.5, 8.9), Sp("bench.finish", 9.0, 9.5)]


def test_idle_gaps_are_labelled_by_self_time():
    ops = [E("a.1", 0.6, 8.4)]
    gaps = S.idle_gaps(ops, _nested_spans(), 0.0, 10.0)
    # the outermost span covers each gap; the one with most time of its
    # own there names it
    assert gaps == [["host:finish", pytest.approx(1.6)],
                    ["host:engine.schedule", pytest.approx(0.6)]]


def test_bench_spans_alone_label_gaps_as_before():
    ops = T.mark_leaves([E("while.1", 0.0, 4.0), E("a.1", 0.5, 1.0),
                         E("b.2", 1.0, 2.5), E("c", 6.0, 7.0)])
    spans = [E("bench.window", 0.0, 10.0), E("bench.step", 0.0, 7.0),
             E("bench.finish", 7.0, 9.0)]
    assert S.idle_gaps(ops, spans, 0.0, 10.0) == \
        T.idle_gaps(ops, spans, 0.0, 10.0)
    tr = T.load(OLD)
    w = tr.window()
    assert S.idle_gaps(tr.ops[0], tr.spans, *w) == \
        T.idle_gaps(tr.ops[0], tr.spans, *w)


def test_host_time_and_queue_waits_from_program_spans():
    Sp = S.Span
    spans = _nested_spans() + [
        Sp("engine.step", 10.0, 12.0), Sp("engine.wait", 10.5, 11.9),
        Sp("engine.admit", 0.2, 0.2, (("rid", 4), ("queue_wait_s", 1.5))),
        Sp("engine.admit", 10.1, 10.1, (("rid", 5), ("queue_wait_s", 3.0)))]
    host = S.host_step_seconds(spans, 0.0, 10.0)
    assert host == [pytest.approx(8.8 - 7.9)]
    assert S.queue_waits(spans, 0.0, 10.0) == [1.5]
    assert S.queue_waits(spans, 0.0, 11.0) == [1.5, 3.0]


class _Ctx:
    trace = object()
    trace_window = (0.0, 10.0)
    model = {"d_model": 128, "n_heads": 4, "n_kv_heads": 4, "head_dim": 32,
             "n_experts": 8, "d_ff": 64, "mlp": "swiglu", "n_layers": 2,
             "router_experts": 8, "n_shared_experts": 0, "moe_d_ff": 64,
             "first_dense": 0, "mla": None,
             "ovsf": {"rho": 0.5, "seg_len": 16, "min_dim": 32}}


def test_the_readers_of_the_reduction(monkeypatch, capsys):
    ops, modules = _runs()
    spans = _nested_spans()
    prof = S.Profile(ops, modules, [s for s in spans
                                    if s.name.startswith("bench.")],
                     [s for s in spans if s.name.startswith("engine.")]
                     + [S.Span("engine.admit", 0.2, 0.2,
                               (("queue_wait_s", 2.0),))])
    monkeypatch.setattr(S, "_profile_of", lambda root, w: prof)
    monkeypatch.setattr(S, "program_maps", lambda: [S.scope_map(_text(32))])
    monkeypatch.setattr(S, "_DONE", {})
    ctx = _Ctx()
    read = {n: R.metric_reader(ROOT, n).read(ctx)
            for n in ("moe_ms", "attn_ms", "ovsf_linear_ms", "host_step_ms",
                      "queue_wait_p90_s")}
    assert read == pytest.approx({"moe_ms": 500.0, "attn_ms": 1000.0,
                                  "ovsf_linear_ms": 1750.0,
                                  "host_step_ms": 900.0,
                                  "queue_wait_p90_s": 2.0})
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("[bench] scopes: {") and "outside_step" in out[0]
    assert out[1].startswith("[bench] program_gaps: ")
    assert len(out) == 2                            # reduced once per run
    # a program older than the scopes: every scope metric reads None
    monkeypatch.setattr(S, "program_maps", lambda: [])
    monkeypatch.setattr(S, "_DONE", {})
    assert R.metric_reader(ROOT, "attn_ms").read(ctx) is None
    assert R.metric_reader(ROOT, "host_step_ms").read(ctx) == \
        pytest.approx(900.0)


def test_a_tiny_engine_gives_scope_maps():
    import jax
    from repro.configs import get_smoke_config
    from repro.models import registry as MR
    from repro.serving import LLMEngine, Request
    import numpy as np
    cfg = get_smoke_config("olmoe_1b_7b")
    eng = LLMEngine(MR.model_init(jax.random.PRNGKey(0), cfg), cfg,
                    batch_slots=2, buffer_len=64, chunk_size=8, packed=True,
                    paged=True, page_size=8)
    eng.submit(Request(0, np.arange(12, dtype=np.int32), max_new_tokens=3))
    eng.run_until_drained()
    maps = [m for m in S.program_maps() if m.module == "jit__paged"]
    assert maps and all(m.known for m in maps)
    scopes = {s for m in maps for s in m.scopes.values()}
    assert scopes >= {"attention", "moe.experts", "linear.attn_q", "unembed",
                      "sample", "embed"}


SCOPED = os.path.join(os.path.dirname(__file__), "data",
                      "olmoe_chat_scoped_3s")


@pytest.fixture(scope="module")
def recorded():
    """Three mixed steps of olmoe.chat on a TPU v5e, with the scopes and
    the engine's spans, and the compiled text of the step program that ran
    (``bench/tests/record_trace.py``)."""
    prof = S.load(SCOPED + ".xplane.pb.gz")
    with gzip.open(SCOPED + ".hlo.json.gz", "rt") as f:
        maps = [S.scope_map(t) for t in json.load(f)]
    return prof, maps, prof.window()


def test_recorded_leaf_ops_each_fall_in_one_scope(recorded):
    prof, maps, w = recorded
    by, steps = S.device_seconds_by_scope(prof.ops, prof.modules, maps, *w)
    assert steps == 3
    leaf = sum(e.seconds for e in T.clip(prof.ops, *w) if e.leaf)
    assert sum(by.values()) == pytest.approx(leaf)
    in_step = leaf - by.get(S.OUTSIDE, 0.0)
    assert by.get(S.OTHER, 0.0) < 0.1 * in_step
    assert set(by) >= {"attention", "linear.attn_q", "linear.attn_o",
                       "moe.router", "moe.dispatch", "moe.experts",
                       "moe.combine", "embed", "unembed", "sample"}


def test_recorded_engine_spans_label_the_idle_gaps(recorded):
    prof, _, w = recorded
    names = {s.name for s in prof.program_spans}
    assert names >= {"engine.step", "engine.schedule", "engine.page_gate",
                     "engine.pack", "engine.launch", "engine.wait",
                     "engine.commit"}
    gaps = S.idle_gaps(prof.ops, prof.spans + prof.program_spans, *w)
    assert len(gaps) == 10
    assert all(lbl.startswith("host:engine.") for lbl, _ in gaps)
    host = S.host_step_seconds(prof.program_spans, *w)
    assert len(host) == 3 and all(0 < h < 0.1 for h in host)
