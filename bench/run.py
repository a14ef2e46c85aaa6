#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result as one JSON line.

    python bench/run.py --workload olmoe.chat --seed 7 --seconds 51 --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); each per-layer metric is read by
``bench/metrics/<metric>.py``. Nothing here names a cell, a configuration or
a metric: a new one is a new file and a new entry.

One run, all in this process (it holds the chip):
  1. set-up: weights made on the device from ``--seed``; the engine built as
     ``repro.launch.serve`` builds it (chunked, packed, paged, greedy); every
     step shape the traffic uses run once; the closed loop of clients run
     until every client's first request has reached decode;
  2. the window: ``LLMEngine.step()`` for ``--seconds`` seconds, each client
     sending its next request when the last one finishes; with
     ``--trace 1`` under the profiler, with the benchmark's spans around each
     step and each batch of finished requests;
  3. the check: a sample of the served requests, drawn from the seed with the
     longest among them, scored against the float32 reference
     (``bench/reference.py``) teacher-forced on prompt and served tokens.

The last line of standard output is the result; the last lines of standard
error are the numbers compared, each beside its limit. With no TPU, with
fewer chips than the cell asks for, or on a device kind that has no peaks,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent          # bench/
ROOT = HERE.parent                              # the checkout
if __name__ == "__main__":
    # run as a script: import the harness as the ``bench`` package, not its
    # modules as top-level names
    sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import traffic as traffic_gen      # noqa: E402
from bench import window as win               # noqa: E402
from bench import work as W                   # noqa: E402

NATURAL = ("length", "eos")


class BenchError(Exception):
    pass


def process_seconds() -> float:
    """Seconds since this process started (falls back to interpreter
    import time where /proc is absent)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


_T_IMPORT = time.perf_counter()


# ---------------------------------------------------------------------------
# finding the cell's files by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    root: Path
    bench: dict
    workload: dict
    config: dict
    traffic: dict
    model: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"] if m["moves"] in e2e
                and self.name in m.get("workloads", [self.name])]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(root: Path, workload: str) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / cfgs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(root, bench, w, config, traffic, model_of(config))


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise BenchError(f"no module file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, name: str):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}")


# ---------------------------------------------------------------------------
# the configuration: published keys -> the sizes the reference and the
# work counts read, and the program's ModelConfig
# ---------------------------------------------------------------------------

_MLP = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu_tanh"}
MLA_KEYS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim")
_SCORING = ("softmax", "sigmoid")
# topk_method -> router_bias: plain top-k, or top-k over scores plus a
# correction bias that picks experts but does not weight them
_TOPK_METHOD = {None: False, "noaux_tc": True}


def _refuse(c: dict, key: str, why: str):
    raise BenchError(f"{c['name']}: {key} {c.get(key)!r}: {why}")


def _expert_count(c: dict) -> int:
    """The experts held here; with an ``expert_share`` they are this chip's
    share of the published ``expert_share.n_routed_experts`` over
    ``expert_share.chips``, and their key is listed in ``reduced``."""
    key = "n_routed_experts" if "n_routed_experts" in c else "num_experts"
    E = c.get(key) or 0
    share = c.get("expert_share")
    if share is not None:
        if E * share["chips"] != share["n_routed_experts"]:
            raise BenchError(
                f"{c['name']}: {key} {E} x expert_share.chips "
                f"{share['chips']} is not expert_share.n_routed_experts "
                f"{share['n_routed_experts']}")
        if key not in c.get("reduced", []):
            raise BenchError(f"{c['name']}: {key} is this chip's share of "
                             "the experts and must be listed in reduced")
    return E


def _rope_scaling(c: dict):
    rs = c.get("rope_scaling")
    if rs is None:
        return None
    kind = rs.get("type", rs.get("rope_type"))
    if kind != "yarn":
        raise BenchError(f"{c['name']}: rope_scaling type {kind!r} is not "
                         "described (yarn only)")
    return dict(rs)


def model_of(c: dict) -> dict:
    """The sizes of a configuration file, under the names the reference and
    ``bench/work.py`` use. A shape that these names cannot describe is
    refused, naming its key."""
    if c.get("norm_type", "rms_norm") != "rms_norm":
        raise BenchError(f"{c['name']}: norm_type {c['norm_type']!r} has no "
                         "reference")
    if c.get("moe_layer_freq", 1) != 1:
        _refuse(c, "moe_layer_freq", "only every layer after the leading "
                "dense ones is described as an expert layer")
    for key in ("n_group", "topk_group"):
        if (c.get(key) or 1) > 1:
            _refuse(c, key, "grouped expert choice is not described")
    if (c.get("num_nextn_predict_layers") or 0) > 0:
        _refuse(c, "num_nextn_predict_layers", "multi-token prediction "
                "layers are not described")
    scoring = c.get("scoring_func", "softmax")
    if scoring not in _SCORING:
        _refuse(c, "scoring_func", f"not one of {_SCORING}")
    if c.get("topk_method") not in _TOPK_METHOD:
        _refuse(c, "topk_method", f"not one of {list(_TOPK_METHOD)}")
    if "quantization_config" in c:
        _refuse(c, "quantization_config", "weights are made as OVSF alphas "
                "in torch_dtype; a quantised weight format is not described")
    d, H = c["hidden_size"], c["num_attention_heads"]
    d_ff = c["intermediate_size"]
    E = _expert_count(c)
    mla = None
    if c.get("kv_lora_rank") is not None:
        mla = {k: c.get(k) for k in MLA_KEYS}
        if not mla["q_lora_rank"]:
            _refuse(c, "q_lora_rank", "latent attention without a query "
                    "latent is not described")
    return {
        "n_layers": c["num_hidden_layers"], "d_model": d, "n_heads": H,
        "n_kv_heads": c.get("num_key_value_heads", H),
        # latent attention has no single head width: its five widths
        # are under "mla"
        "head_dim": None if mla else c.get("head_dim") or d // H,
        "d_ff": d_ff, "vocab": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c.get("rms_norm_eps", c.get("norm_epsilon"))),
        "n_experts": E,
        "top_k": c.get("num_experts_per_tok", 0),
        "norm_topk_prob": c.get("norm_topk_prob", False),
        "mlp": _MLP[c["hidden_act"]],
        "ovsf": c["ovsf"],
        "router_experts": (c["expert_share"]["n_routed_experts"]
                           if "expert_share" in c else E),
        "n_shared_experts": c.get("n_shared_experts") or 0,
        "moe_d_ff": c.get("moe_intermediate_size") or d_ff,
        "first_dense": c.get("first_k_dense_replace") or 0,
        "mla": mla,
        "router_scoring": scoring,
        "router_bias": _TOPK_METHOD[c.get("topk_method")],
        "routed_scaling": float(c.get("routed_scaling_factor") or 1.0),
        "rope_scaling": _rope_scaling(c),
    }


def _program_fields(m: dict) -> dict:
    """The fields beyond the dense and softmax-routed block that a program's
    ModelConfig must carry to run a configuration: field -> (the file's
    value, its default, the file's key). A program without a field runs the
    default."""
    rs = m["rope_scaling"]
    mla = m["mla"] or {}
    out = {
        "router_experts": (m["router_experts"], m["n_experts"],
                           "expert_share.n_routed_experts"),
        "n_shared_experts": (m["n_shared_experts"], 0, "n_shared_experts"),
        "moe_d_ff": (m["moe_d_ff"], m["d_ff"], "moe_intermediate_size"),
        "first_dense_layers": (m["first_dense"], 0, "first_k_dense_replace"),
        "router_scoring": (m["router_scoring"], "softmax", "scoring_func"),
        "router_bias": (m["router_bias"], False, "topk_method"),
        "routed_scaling": (m["routed_scaling"], 1.0,
                           "routed_scaling_factor"),
        "rope_scaling": (None if rs is None else tuple(sorted(rs.items())),
                         None, "rope_scaling"),
    }
    out.update({k: (mla.get(k), None, k) for k in MLA_KEYS})
    return out


def program_config(c: dict, m: dict):
    """The program's ModelConfig for configuration file ``c``: its
    ``program.arch`` with ``program.overrides``, checked against the file's
    sizes so the program runs what the file states."""
    from repro.configs import get_config
    prog = c["program"]
    cfg = get_config(prog["arch"])
    over = dict(prog.get("overrides", {}))
    if "ovsf" in over:
        cfg = cfg.replace(ovsf=dataclasses.replace(cfg.ovsf,
                                                   **over.pop("ovsf")))
    cfg = cfg.replace(**over)
    fields = _program_fields(m)
    lacks = {f: key for f, (v, default, key) in fields.items()
             if not hasattr(cfg, f) and v != default}
    if lacks:
        raise BenchError(f"{c['name']}: the program's config has no field "
                         f"for what the file states (field: file key): "
                         f"{lacks}")
    want = {"n_layers": m["n_layers"], "d_model": m["d_model"],
            "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
            "d_ff": m["d_ff"], "vocab": m["vocab"],
            "rope_theta": m["rope_theta"], "norm_eps": m["norm_eps"],
            "n_experts": m["n_experts"], "top_k": m["top_k"],
            "mlp_gated": m["mlp"] == "swiglu", "dtype": c["torch_dtype"],
            "qkv_bias": bool(c.get("use_bias", c.get("attention_bias"))),
            "tie_embeddings": bool(c.get("tie_word_embeddings"))}
    if m["mla"] is None:
        want["hd"] = m["head_dim"]
    want.update({f: v for f, (v, _d, _k) in fields.items()
                 if hasattr(cfg, f)})
    got = {k: getattr(cfg, k) for k in want}
    ov = cfg.ovsf
    want.update(ovsf_rho=m["ovsf"]["rho"], ovsf_seg=m["ovsf"]["seg_len"],
                ovsf_min_dim=m["ovsf"]["min_dim"], ovsf_on=True,
                alpha_dtype=m["ovsf"].get("alpha_dtype", ""))
    got.update(ovsf_rho=ov.rho, ovsf_seg=ov.seg_len, ovsf_min_dim=ov.min_dim,
               ovsf_on=ov.enable, alpha_dtype=ov.alpha_dtype)
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise BenchError(f"{c['name']}: the program's config departs from "
                         f"the file (program, file): {bad}")
    if m["n_experts"] and cfg.capacity_factor * m["top_k"] < m["n_experts"]:
        raise BenchError(f"{c['name']}: capacity_factor "
                         f"{cfg.capacity_factor} drops tokens")
    return cfg


# ---------------------------------------------------------------------------
# set-up accounting
# ---------------------------------------------------------------------------

class CompileClock:
    """Backend compile seconds and count, and persistent-cache hits, from
    JAX's own monitoring events."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


_CLOCK: Optional[CompileClock] = None


def compile_clock():
    global _CLOCK
    if _CLOCK is None:
        import jax.monitoring
        _CLOCK = CompileClock(jax.monitoring)
    return _CLOCK


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory inside the checkout. Every
    program is kept, however short its compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".bench_cache" / "jax")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# the served run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """Everything a run leaves for the metrics and the check."""
    requests: dict          # rid -> Request
    sends: dict             # rid -> send time
    t0: float
    t1: float
    steps: int              # eng.step() calls in the window
    step_tokens: list       # work.StepTokens of each device step in it
    valid_tokens: int
    batch_tokens: int
    failed: int
    recoveries: int
    setup: dict
    memory_peak: int


def span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


def build_engine(cell: Cell, params, pcfg, hw: str):
    from repro.serving import LLMEngine
    t = cell.traffic
    if t.get("sampling", "greedy") != "greedy" or \
            t.get("eos", "ignored") != "ignored":
        raise BenchError("the harness drives greedy sampling with eos "
                         "ignored only")
    e = t["engine"]
    return LLMEngine(params, pcfg, batch_slots=e["slots"],
                     buffer_len=e["buffer"], hw=hw,
                     chunk_size=e["chunk"], packed=True, paged=True,
                     page_size=e["page_size"], kv_pages=e.get("kv_pages"),
                     max_step_tokens=e.get("max_step_tokens"))


def record_steps(eng, out: list) -> None:
    """Record what each device step of ``eng`` processed (its tokens, their
    contexts, the rows it emits), around the core's own step."""
    core = eng.core
    inner = core.step

    def step(so, last_tokens=None):
        pos = core._host_pos
        n = len(so.decode_slots) + sum(c.length for c in so.chunks)
        ctx = (sum(int(pos[i]) + 1 for i in so.decode_slots)
               + sum(c.length * c.start + c.length * (c.length + 1) // 2
                     for c in so.chunks))
        emit = len(so.decode_slots) + sum(1 for c in so.chunks if c.last)
        res = inner(so, last_tokens)
        out.append(W.StepTokens(n, ctx, emit))
        return res

    core.step = step


def warm_shapes(eng) -> list:
    """Run the packed paged step once at every token bucket the traffic can
    use (pure decode, and a mixed step under the token budget), on an input
    of padding only: no slot's state changes."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import core as C
    from repro.serving.scheduler import pack_bucket
    core = eng.core
    B = core.B
    buckets = sorted({pack_bucket(1, B, eng.chunk, False),
                      pack_bucket(eng.max_step_tokens or 1, B, eng.chunk,
                                  True)})
    for Tb in buckets:
        pad = np.full(Tb, B, np.int32)
        zeros = np.zeros(Tb, np.int32)
        fn = C._paged_step_fn(eng.cfg, Tb)
        res = fn(eng.params, core.caches, jnp.asarray(core.pager.page_table),
                 jnp.asarray(zeros), jnp.asarray(pad), jnp.asarray(zeros),
                 jnp.asarray(core._host_pos, dtype=jnp.int32),
                 jnp.asarray(np.zeros(B, np.int64), dtype=jnp.int32),
                 jnp.asarray(core._zero_poison), jnp.asarray(core.temps),
                 jnp.asarray(core.topks), jnp.asarray(core.greedy),
                 jnp.asarray(core.keys))
        np.asarray(res[0])
        del res
    return buckets


def serve(cell: Cell, params, pcfg, hw: str, seed: int, seconds: float,
          trace_dir: Optional[str], t_setup0: float, setup: dict,
          alter=None) -> Served:
    """Set up the engine, warm it, and run the measured window."""
    import jax
    from repro.serving import Request
    clock = compile_clock()
    eng = build_engine(cell, params, pcfg, hw)
    if alter is not None:
        alter(eng)
    steps_rec: list = []
    record_steps(eng, steps_rec)
    tw = time.perf_counter()
    setup["buckets"] = warm_shapes(eng)
    loop = traffic_gen.ClosedLoop(cell.traffic, seed, cell.model["vocab"])
    reqs, sends, done = {}, {}, []
    failed = 0

    def send():
        r = loop.next()
        req = Request(r.index, r.prompt, max_new_tokens=r.max_new,
                      on_finish=lambda out: done.append(out))
        reqs[r.index] = req
        sends[r.index] = time.perf_counter()
        eng.submit(req)

    def settle(on: bool) -> None:
        """Book finished requests; each client sends its next at once."""
        nonlocal failed
        if not done:
            return
        with span("bench.finish", on):
            finished = list(done)
            done.clear()
            for out in finished:
                if out.finish_reason not in NATURAL:
                    failed += 1
            with span("bench.submit", on):
                for _ in finished:
                    send()

    clients = int(cell.traffic["clients"])
    for _ in range(clients):
        send()
    first = list(range(clients))
    # a loop that opens at steady state also waits out the queue that the
    # first prompts' prefill left behind
    steady = cell.traffic.get("start") == "steady"
    warm_steps = 0
    while not all(reqs[i].out_tokens or reqs[i].done for i in first) \
            or (steady and len(eng.scheduler)):
        eng.step()
        settle(False)
        warm_steps += 1
        if warm_steps > 20 * clients:
            raise BenchError("the warm-up's queue does not drain")
    setup["warmup_s"] = time.perf_counter() - tw
    st = eng.stats
    valid0, batch0, rec0 = st.packed_tokens, st.padded_tokens, st.recoveries
    pre0, pages0 = st.preemptions, eng.core.pager.used_pages
    c0, n0, h0 = clock.seconds, clock.compiles, clock.hits
    setup["compile_s"] = clock.seconds
    setup["compiles"] = clock.compiles
    setup["cache_hits"] = clock.hits
    n_steps0 = len(steps_rec)
    tracing = trace_dir is not None
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # the benchmark's spans only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    setup["setup_s"] = t0 - (_T_IMPORT if t_setup0 is None else t_setup0)
    steps = 0
    longest = (0.0, -1)
    with span("bench.window", tracing):
        while (ts := time.perf_counter()) - t0 < seconds:
            with span("bench.step", tracing):
                eng.step()
            settle(tracing)
            longest = max(longest, (time.perf_counter() - ts, steps))
            steps += 1
    t1 = time.perf_counter()
    setup["window_s"] = t1 - t0
    setup["window_steps"] = steps
    setup["longest_step_s"], setup["longest_step_at"] = longest
    if tracing:
        jax.profiler.stop_trace()
    setup["compiles_in_window"] = clock.compiles - n0
    setup["compile_s_in_window"] = clock.seconds - c0
    setup["cache_hits_in_window"] = clock.hits - h0
    st = eng.stats
    setup["kv_pages"] = {"pool": eng.core.pager.P, "at_start": pages0,
                         "at_end": eng.core.pager.used_pages,
                         "peak": st.kv_pages_used}
    setup["preemptions_in_window"] = st.preemptions - pre0
    mem = 0
    for d in jax.local_devices()[:int(cell.workload["chips"])]:
        ms = d.memory_stats() or {}
        mem = max(mem, int(ms.get("peak_bytes_in_use", 0)))
    served = Served(reqs, sends, t0, t1, steps, steps_rec[n_steps0:],
                    st.packed_tokens - valid0, st.padded_tokens - batch0,
                    failed, st.recoveries - rec0 + st.errors, setup, mem)
    # free the program's state before the reference runs
    eng.core.caches = None
    del eng
    gc.collect()
    return served


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def sample_for_check(served: Served, spec: dict, seed: int) -> list:
    """Request ids to score: the finished request with the most served
    tokens, then the other finished ones, then those still in flight, each
    group in an order drawn from the seed, until ``spec['tokens']`` served
    tokens or ``spec['requests']`` requests."""
    rng = traffic_gen.rng_for(seed, 2)
    have = sorted((r for r in served.requests.values() if r.out_tokens),
                  key=lambda r: r.rid)
    fin = [r for r in have if r.done]
    live = [r for r in have if not r.done]
    order = []
    if fin:
        longest = max(fin, key=lambda r: (len(r.out_tokens), -r.rid))
        rest = [r for r in fin if r is not longest]
        order = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    order += [live[i] for i in rng.permutation(len(live))]
    out, n = [], 0
    for r in order:
        if n >= spec["tokens"] or len(out) >= spec["requests"]:
            break
        out.append(r.rid)
        n += len(r.out_tokens)
    return out


def gaps(rows, tokens) -> list:
    """Per served token: how far its reference logit lies below the
    reference's best, in units of the row's standard deviation."""
    return [float((row.max() - row[t]) / row.std())
            for row, t in zip(rows, tokens)]


def reference_of(cell: Cell):
    """The configuration's plain reference (``modules.reference``), which
    refuses a model it does not compute before any weight is made."""
    ref = load_module(cell.root / "bench" /
                      f"{cell.config['modules']['reference']}.py",
                      "bench_reference")
    try:
        ref.check_model(cell.model)
    except ValueError as e:
        raise BenchError(f"{cell.config['name']}: {e}") from None
    return ref


def check(cell: Cell, ref, params, served: Served, seed: int,
          control: bool = False) -> dict:
    """Score the sampled served tokens against the reference ``ref``. With
    ``control``, also read the control: the reference at the precision
    below the configuration's, at the same positions, scored under
    ``res['control']`` as the program is."""
    import numpy as np
    rids = sample_for_check(served, cell.traffic["check"], seed)
    if not rids:
        return {"tokens": 0}
    seqs, rows, toks = [], [], []
    for rid in rids:
        r = served.requests[rid]
        out = list(r.out_tokens)
        plen = len(r.prompt) if r.prompt_len_orig is None \
            else r.prompt_len_orig
        prompt = np.asarray(r.prompt[:plen], np.int32)
        seqs.append(np.concatenate([prompt, np.asarray(out, np.int32)]))
        rows.append(list(range(plen - 1, plen - 1 + len(out))))
        toks.extend(out)
    t = time.perf_counter()
    ref_rows = np.concatenate(ref.logits(params, cell.model, seqs, rows))
    res = {"tokens": len(toks), "requests": len(rids)}
    g = gaps(ref_rows, toks)
    res.update(gap_stats(g))
    res["check_s"] = time.perf_counter() - t
    if control:
        c_rows = np.concatenate(ref.logits(params, cell.model, seqs, rows,
                                           prec="fp8"))
        cg = gaps(ref_rows, [int(r.argmax()) for r in c_rows])
        res["control"] = dict(gap_stats(cg), tokens=len(cg),
                              requests=len(rids))
        res["gaps"] = [round(x, 6) for x in g]
        res["control_gaps"] = [round(x, 6) for x in cg]
    return res


def gap_stats(g: list) -> dict:
    """The numbers a check can compare, from the per-token gaps."""
    import numpy as np
    g = np.asarray(g, np.float64)
    return {"max_token_gap_std": float(g.max()),
            "mean_token_gap_std": float(g.mean()),
            "p99_token_gap_std": float(np.percentile(g, 99)),
            "not_argmax_share": float(np.mean(g > 0))}


def checks_of(cell: Cell, served: Served, scored: dict) -> dict:
    """Each number compared, beside its limit: the gap statistics the
    configuration names (``correct.compare``), at most their limit; the
    tokens scored, at least theirs; failed requests and step recoveries, 0."""
    lim = cell.config["correct"]
    out = {name: {"value": scored.get(name), "limit": limit}
           for name, limit in lim["compare"].items()}
    out["scored_tokens"] = {"value": scored.get("tokens", 0),
                            "limit": lim["min_scored_tokens"]}
    out["failed_requests"] = {"value": served.failed, "limit": 0}
    out["step_recoveries"] = {"value": served.recoveries, "limit": 0}
    return out


def is_correct(checks: dict) -> bool:
    ok = True
    for name, c in checks.items():
        v = c["value"]
        if name == "scored_tokens":
            ok &= v >= c["limit"]
        else:
            ok &= v is not None and v <= c["limit"]
    return bool(ok)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    model: dict
    peaks: dict
    window_s: float             # host clock
    steps: int                  # eng.step() calls in the window
    step_tokens: list           # work.StepTokens per device step
    valid_tokens: int
    batch_tokens: int
    window: win.WindowCounts    # host-clock counts of the window
    trace: object = None        # trace.Trace, or None
    trace_window: Optional[tuple] = None


def window_counts(served: Served) -> win.WindowCounts:
    recs = [win.Record(served.sends[rid], list(r.token_times))
            for rid, r in served.requests.items()]
    return win.count(recs, served.t0, served.t1)


def end_to_end_metrics(cell: Cell, served: Served) -> dict:
    vals = win.end_to_end(window_counts(served))
    vals["setup_s"] = served.setup["setup_s"]
    out = {}
    for m in cell.end_to_end():
        v = vals.get(m["name"])
        if v is None:
            raise BenchError(f"{m['name']}: no sample in the window")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def per_layer_metrics(cell: Cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer():
        v = metric_reader(cell.root, m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown_of(tr, t0: float, t1: float) -> dict:
    from bench import trace as T
    ops = tr.ops[min(tr.ops)]
    return {"device_ops": T.top_ops(ops, t0, t1, 10),
            "idle_gaps": T.idle_gaps(ops, tr.spans, t0, t1, 10)}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(chips: int, need_tpu: bool) -> dict:
    import jax
    from bench.peaks import UnknownDevice, peaks_for
    devs = jax.devices()
    dev = devs[0]
    if need_tpu:
        if dev.platform != "tpu":
            raise BenchError(f"no TPU: JAX reports platform "
                             f"{dev.platform!r}")
        if len(devs) < chips:
            raise BenchError(f"the cell asks for {chips} chips, "
                             f"{len(devs)} found")
        try:
            peaks = peaks_for(dev.device_kind)
        except UnknownDevice as e:
            raise BenchError(str(e)) from None
    else:
        peaks = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips, "peaks": peaks}


def hw_for(dev: dict) -> str:
    if dev["platform"] != "tpu":
        return "cpu"
    from repro.hwmodel.perf_model import DEVICE_KINDS
    return DEVICE_KINDS[dev["kind"]]


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, need_tpu: bool = True, alter=None, t_setup0=None,
        control: bool = False, cache: bool = True) -> tuple:
    """One run of one cell. Returns (result dict, checks dict, extra dict).
    ``need_tpu=False`` skips the look for a chip and ``cache=False`` the
    persistent compile cache (tests); ``alter(eng)`` may break the engine
    underneath (tests)."""
    cell = find_cell(root, workload)
    if cache:
        use_compile_cache()
    import jax
    compile_clock()
    dev = device_info(int(cell.workload["chips"]), need_tpu)
    pcfg = program_config(cell.config, cell.model)
    ref = reference_of(cell)
    from repro.models import registry as R
    wmod = load_module(root / "bench" /
                       f"{cell.config['modules']['weights']}.py",
                       "bench_weights")
    setup: dict = {}
    t = time.perf_counter()
    key = jax.random.PRNGKey(int(traffic_gen.rng_for(seed, 3).integers(
        0, 2**31 - 1)))
    layout = jax.eval_shape(lambda k: R.model_init(k, pcfg), key)
    params = wmod.make(layout, key, cell.model["ovsf"]["seg_len"])
    jax.block_until_ready(params)
    setup["weights_s"] = time.perf_counter() - t
    trace_dir = None
    if trace:
        trace_dir = str(root / ".bench_out" / "trace" /
                        f"{workload}-{seed}-{os.getpid()}")
    served = serve(cell, params, pcfg, hw_for(dev), seed, seconds, trace_dir,
                   t_setup0, setup, alter=alter)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": served.memory_peak}
    result = {"correct": False, "attempted": len(served.requests),
              "failed": served.failed}
    extra = {"setup": setup}
    if trace:
        from bench import trace as T
        tr = T.load(T.find_xplane(trace_dir))
        tw = tr.window()
        if not tr.ops or tw is None:
            raise BenchError("the trace holds no device operation or no "
                             "window span")
        ctx = Context(cell.model, dev["peaks"], served.t1 - served.t0,
                      served.steps, served.step_tokens, served.valid_tokens,
                      served.batch_tokens, window_counts(served), tr, tw)
        used = sorted(tr.ops)[:dev["count"]]
        busy = sum(T.busy_seconds(tr.ops[d], *tw) for d in used) / len(used)
        device.update(busy_s=busy, window_s=tw[1] - tw[0])
        result["metrics"] = per_layer_metrics(cell, ctx)
        result["breakdown"] = breakdown_of(tr, *tw)
    else:
        result["metrics"] = end_to_end_metrics(cell, served)
    result["device"] = device
    scored = check(cell, ref, params, served, seed, control=control)
    extra["scored"] = scored
    checks = checks_of(cell, served, scored)
    result["correct"] = is_correct(checks)
    result["checks"] = checks
    if control:
        # the control in the program's place, judged at the cell's limits;
        # a control with no reading has failed
        extra["control_checks"] = checks_of(cell, served,
                                            scored.get("control", {}))
        extra["control_correct"] = is_correct(extra["control_checks"])
    return result, checks, extra


def main(argv=None) -> int:
    t_start = time.perf_counter() - process_seconds()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (the reference at the "
                         "precision below the configuration's) after the "
                         "check; for setting limits, not for measuring")
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "BENCHMARK.json").is_file() or \
                not (ROOT / "src" / "repro").is_dir():
            raise BenchError(f"{ROOT} is not a checkout of the repository "
                             "(BENCHMARK.json or src/repro missing)")
        result, checks, extra = run(ROOT, args.workload, args.seed,
                                    args.seconds, bool(args.trace),
                                    t_setup0=t_start,
                                    control=bool(args.control))
    except BenchError as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr)
        return 1
    s = extra["setup"]
    print("[bench] setup: " + json.dumps(
        {k: s[k] for k in ("setup_s", "weights_s", "compile_s", "compiles",
                           "cache_hits", "warmup_s", "compiles_in_window",
                           "compile_s_in_window", "cache_hits_in_window",
                           "buckets", "window_s", "window_steps",
                           "longest_step_s", "longest_step_at",
                           "kv_pages", "preemptions_in_window")}),
          flush=True)
    print("[bench] check: " + json.dumps(extra["scored"]), flush=True)
    if "control_checks" in extra:
        print(f"[bench] control correct: "
              f"{json.dumps(extra['control_correct'])}", file=sys.stderr)
        for name, c in extra["control_checks"].items():
            print(f"control {name} {c['value']} limit {c['limit']}",
                  file=sys.stderr, flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    if extra.get("control_correct"):
        print("[bench] FAILED: the control passes the cell's limits",
              file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
