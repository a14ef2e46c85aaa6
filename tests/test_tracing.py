"""The serving program's own spans, scopes and stamps: named scopes per layer
in the compiled step's HLO metadata, the engine's host spans under the
profiler, the queue-wait stamp ``Request.t_admit``, and the counters of the
kernels and the expert banks (``trace.kernel_notes``, ``trace.expert_notes``)."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.kernels.ovsf_gemm import ovsf_gemm
from repro.models import registry as R
from repro.serving import LLMEngine, Request, trace
from repro.runtime import mapper
from repro.runtime.faults import FaultPlan


@pytest.fixture(scope="module")
def moe():
    cfg = get_smoke_config("olmoe_1b_7b")
    return cfg, R.model_init(jax.random.PRNGKey(0), cfg)


def _req(rid, plen, max_new=4, vocab=512, **kw):
    rng = np.random.default_rng(rid)
    return Request(rid, rng.integers(0, vocab, plen, dtype=np.int32),
                   max_new_tokens=max_new, **kw)


def _paged(cfg, params, **kw):
    return LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=8,
                     packed=True, paged=True, page_size=8, **kw)


def test_compiled_paged_step_names_each_layer_in_its_metadata(moe):
    cfg, params = moe
    eng = _paged(cfg, params)
    for rid in range(2):
        eng.submit(_req(rid, 12, vocab=cfg.vocab))
    eng.run_until_drained()
    texts = trace.step_program_texts()
    names = {part for t in texts
             for op in re.findall(r'op_name="([^"]*)"', t)
             for part in op.split("/")}
    assert names >= {"embed", "attention", "linear.attn_q", "linear.attn_o",
                     "moe", "moe.router", "moe.dispatch", "moe.experts",
                     "moe.combine", "unembed", "sample"}


def test_engine_spans_nest_inside_the_step(moe, tmp_path):
    from jax.profiler import ProfileData
    cfg, params = moe
    eng = _paged(cfg, params, faults=FaultPlan.parse(["fail:step=3"]))
    for rid in range(3):
        eng.submit(_req(rid, 12, vocab=cfg.vocab))
    eng.step()                                      # compile outside
    with jax.profiler.trace(str(tmp_path)):
        eng.run_until_drained()
    assert eng.stats.recoveries == 1
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host") for line in p.lines
             for e in line.events if e.name.startswith("engine.")]
    names = {s[0] for s in spans}
    assert names == {"engine.step", "engine.schedule", "engine.page_gate",
                     "engine.admit", "engine.pack", "engine.launch",
                     "engine.wait", "engine.commit", "engine.recover"}
    steps = [s for s in spans if s[0] == "engine.step"]
    for name, a, b, _ in spans:
        if name != "engine.step":
            assert any(s <= a and b <= e for _, s, e, _ in steps), name
    # the request admitted under the profiler carries its queue wait
    (admit,) = [s for s in spans if s[0] == "engine.admit"]
    assert admit[3]["rid"] == 2 and admit[3]["queue_wait_s"] > 0


def test_t_admit_is_stamped_once_and_kept_across_preemption(moe):
    cfg, params = moe
    eng = LLMEngine(params, cfg, batch_slots=2, buffer_len=64, chunk_size=8,
                    admission="preempt", packed=True, paged=True,
                    page_size=8)
    reqs = [_req(rid, 10, max_new=6, vocab=cfg.vocab) for rid in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(4):                              # both slots mid-decode
        eng.step()
    first = [r.t_admit for r in reqs]
    urgent = _req(9, 10, vocab=cfg.vocab, priority=5)
    eng.submit(urgent)
    eng.run_until_drained()
    assert sum(r.preemptions for r in reqs) >= 1
    assert [r.t_admit for r in reqs] == first
    for r in reqs + [urgent]:
        assert r.t_submit <= r.t_admit <= r.token_times[0]


def test_expert_notes_show_the_served_step_materialises_no_expert_w(moe):
    """The engine plans the expert banks spectral: a traced MoE step notes
    each bank's call with no dense W, and the kernels' counter keeps to
    the ovsf_gemm calls."""
    cfg, params = moe
    eng = _paged(cfg, params)
    for rid in range(2):
        eng.submit(_req(rid, 12, vocab=cfg.vocab))
    eng.run_until_drained()
    banks = {"expert_gate": (cfg.d_model, cfg.d_ff),
             "expert_up": (cfg.d_model, cfg.d_ff),
             "expert_down": (cfg.d_ff, cfg.d_model)}
    served = {n["name"]: n for n in trace.expert_notes()
              if n["path"] == "spectral" and n["experts"] == cfg.n_experts
              and banks.get(n["name"]) == (n["d_in"], n["d_out"])}
    assert set(served) == set(banks)
    for name, n in served.items():
        assert eng.cfg.exec_plan.plan_for(name).path == "spectral"
        assert n["dense_w_bytes"] == 0 and n["rows"] > 0
        assert n["j"] == n["d_in"] // 2                  # rho 0.5
    gemm_keys = {"d_in", "d_out", "seg", "bk", "bj", "nc", "n_run"}
    assert all(set(n) == gemm_keys for n in trace.kernel_notes())


def _note_of(d_in, d_out, bk, bj, seg):
    notes = [n for n in trace.kernel_notes()
             if (n["d_in"], n["d_out"], n["bk"], n["bj"], n["seg"])
             == (d_in, d_out, bk, bj, seg)]
    assert len(notes) == 1, notes
    return notes[0]


@pytest.mark.parametrize("arch,rows,name,nc", [
    ("starcoder2_15b", 128, "attn_q", 24),
    ("starcoder2_15b", 128, "mlp_up", 24),
    ("starcoder2_15b", 128, "mlp_down", 96),
    ("tinyllama_1_1b", 32, "attn_q", 8),
    ("tinyllama_1_1b", 32, "mlp_down", 22),
])
def test_kernel_notes_count_the_generator_chunks_per_k_block(arch, rows,
                                                             name, nc):
    """At the blocks the mapper plans, a segmented (L0 = 16) linear's
    k-block meets 64 alpha rows, inside one chunk of 128: the generator
    runs 1 of its nc chunks. Noted at trace time, at full width."""
    cfg = get_config(arch)
    lp = mapper.plan_model(cfg, ShapeConfig("serve_decode", 1, rows, "decode"),
                           hw="v5e").plan_for(name)
    d_in, d_out = {"attn_q": (cfg.d_model, cfg.n_heads * cfg.hd),
                   "mlp_up": (cfg.d_model, cfg.d_ff),
                   "mlp_down": (cfg.d_ff, cfg.d_model)}[name]
    seg = cfg.ovsf.seg_len
    keep = int(round(cfg.ovsf.rho * seg))
    assert (lp.path, seg, keep) == ("fused", 16, 8)
    jax.eval_shape(
        lambda x, al, idx: ovsf_gemm(x, al, idx, block_m=lp.block_m,
                                     block_n=lp.block_n, block_k=lp.block_k,
                                     block_j=lp.block_j),
        jax.ShapeDtypeStruct((rows, d_in), jnp.bfloat16),
        jax.ShapeDtypeStruct((d_in // seg * keep, d_out), jnp.bfloat16),
        jax.ShapeDtypeStruct((d_in // seg, keep), jnp.int32))
    note = _note_of(d_in, d_out, lp.block_k, lp.block_j, seg)
    assert (note["nc"], note["n_run"]) == (nc, 1)


def test_kernel_notes_keep_the_full_loop_for_monolithic_codes():
    jax.eval_shape(
        lambda x, al, idx: ovsf_gemm(x, al, idx, block_k=128, block_j=128),
        jax.ShapeDtypeStruct((8, 2048), jnp.bfloat16),
        jax.ShapeDtypeStruct((1024, 384), jnp.bfloat16),
        jax.ShapeDtypeStruct((1024,), jnp.int32))
    note = _note_of(2048, 384, 128, 128, 0)
    assert (note["nc"], note["n_run"]) == (8, 8)
