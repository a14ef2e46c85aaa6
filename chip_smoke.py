#!/usr/bin/env python3
"""Prove that the serving path runs on a TPU chip at published widths.

    python chip_smoke.py [--seed N]        # one chip: device, kernels, serve
    python chip_smoke.py --chips 4         # four chips: the sharded trainer

Everything runs in this one process, which holds the chip; no phase starts a
child. All data and weights are made from ``--seed``. With no TPU, or outside
a checkout of the repository, it exits non-zero and prints no result.

Phases (one chip):
  1. device   -- a TPU is required; the mapper's HW preset is looked up by
                 the device kind (an unknown kind is an error).
  2. kernels  -- ``ovsf_gemm`` (bf16, int8, int4), ``ovsf_decompress`` and
                 ``fwht_pallas`` compiled for the chip at tinyllama_1_1b's
                 weight shapes and the mapper's blocks, each against its
                 ``kernels/ref.py`` oracle.
  3. serve    -- ``repro.launch.serve`` at full width (22 layers, d_model
                 2048), phase-based and then chunked + packed + paged; every
                 request must finish with no recovery and no error, the
                 served step must hold Pallas kernels (``tpu_custom_call``),
                 and a float32 reference that runs no Pallas kernel,
                 teacher-forced on each request's prompt and served tokens,
                 must agree with the served model's logits and rank every
                 served token within ``TOKEN_TOL`` of its best.
With ``--chips 4`` only the trainer runs: ``repro.launch.train`` at
tinyllama widths over data=4 and over data=2 x model=2, whose losses must
agree.

The last line of standard output is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "tinyllama_1_1b"
REQUESTS, BUFFER = 8, 512    # served requests; KV buffer tokens per slot
KERNEL_TOL = 1e-2        # max |y - ref| / max |ref|: a few bf16 roundings
MODEL_TOL = 5e-2         # ||logits - ref|| / ||ref|| after 22 bf16 layers
# A served token's reference logit below the reference's best, in units of
# the row's std: bf16 near-ties sit within a few MODEL_TOL of 0, a token
# from a wrong step (mask, page table, packing) several std below.
TOKEN_TOL = 0.5
LOSS_TOL = 1e-2          # relative loss gap between the two meshes


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Backend compile seconds (cache reads included) and persistent-cache
    hits, from JAX's own monitoring events."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ---------------------------------------------------------------------------
# 1. device
# ---------------------------------------------------------------------------

def device_phase(jax, chips: int):
    from repro.hwmodel.perf_model import hw_for_device
    from repro.launch.compile_cache import enable_compile_cache
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu",
          f"no TPU found: JAX reports platform {dev.platform!r}")
    check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    hw = hw_for_device(dev)
    log(f"device: {dev.device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"hw preset {hw.name}")
    log(f"compile cache: {enable_compile_cache()}")
    return dev, hw


# ---------------------------------------------------------------------------
# 2. kernels
# ---------------------------------------------------------------------------

def _rel_err(y, ref) -> float:
    import numpy as np
    y = np.asarray(y, np.float32)
    ref = np.asarray(ref, np.float32)
    check(y.shape == ref.shape, f"shape {y.shape} != oracle {ref.shape}")
    check(bool(np.isfinite(y).all()), "non-finite kernel output")
    return float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-30))


def kernel_phase(jax, hw, seed: int, rows: int = 8) -> None:
    import dataclasses
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core import ovsf
    from repro.kernels import ref as kref
    from repro.kernels.fwht import fwht_pallas
    from repro.kernels.ovsf_gemm import ovsf_decompress, ovsf_gemm
    from repro.runtime import mapper

    base = get_config(ARCH)
    shapes = {"attn_q": (base.d_model, base.n_heads * base.hd),
              "attn_o": (base.n_heads * base.hd, base.d_model),
              "mlp_gate": (base.d_model, base.d_ff),
              "mlp_up": (base.d_model, base.d_ff),
              "mlp_down": (base.d_ff, base.d_model)}
    shape = ShapeConfig("serve_decode", 1, rows, "decode")
    key = jax.random.PRNGKey(seed)
    results = []

    def record(name, y, ref):
        err = _rel_err(y, ref)
        results.append((name, err))
        log(f"kernel {name}: max rel err {err:.2e}")

    with jax.default_matmul_precision("highest"):   # fp32 oracles
        for adt in ("", "int8", "int4"):
            cfg = base.replace(ovsf=dataclasses.replace(base.ovsf,
                                                        alpha_dtype=adt))
            plan = mapper.plan_model(cfg, shape, hw=hw, weight_reuse=1)
            for name, (d_in, d_out) in shapes.items():
                lp = plan.plan_for(name)
                key, kx, ka = jax.random.split(key, 3)
                spec = ovsf.OVSFSpec(d_in, d_out, rho=cfg.ovsf.rho,
                                     seg=cfg.ovsf.seg_len)
                p = ovsf.init_ovsf(ka, spec, dtype=jnp.bfloat16)
                al, sc, _ = ovsf.alpha_params(ovsf.quantize_params(p, adt))
                x = jax.random.normal(kx, (rows, d_in), jnp.bfloat16)
                y = ovsf_gemm(x, al, p["idx"], alpha_scale=sc,
                              alpha_dtype=adt, block_m=lp.block_m,
                              block_n=lp.block_n, block_k=lp.block_k,
                              block_j=lp.block_j)
                ref = kref.ovsf_matmul_ref(x, al, p["idx"], alpha_scale=sc,
                                           alpha_dtype=adt)
                record(f"ovsf_gemm {adt or 'bf16'} {name} {d_in}x{d_out} "
                       f"blocks=({lp.block_m},{lp.block_n},{lp.block_k},"
                       f"{lp.block_j})", y, ref)
        for adt in ("", "int8"):
            for d_in, d_out in sorted(set(shapes.values())):
                key, ka = jax.random.split(key)
                spec = ovsf.OVSFSpec(d_in, d_out, rho=0.5, seg=0)
                p = ovsf.quantize_params(
                    ovsf.init_ovsf(ka, spec, dtype=jnp.bfloat16), adt)
                al, sc, _ = ovsf.alpha_params(p)
                W = ovsf_decompress(al, p["idx"], d_in=d_in, alpha_scale=sc,
                                    alpha_dtype=adt)
                ref = kref.ovsf_decompress_ref(al, p["idx"], d_in,
                                               alpha_scale=sc,
                                               alpha_dtype=adt)
                record(f"ovsf_decompress {adt or 'bf16'} {d_in}x{d_out}",
                       W, ref)
        for L in (ovsf.next_pow2(base.d_model), ovsf.next_pow2(base.d_ff)):
            for m in (rows, 512):
                key, kx = jax.random.split(key)
                x = jax.random.normal(kx, (m, L), jnp.bfloat16)
                record(f"fwht_pallas M={m} L={L}", fwht_pallas(x),
                       kref.fwht_ref(x.astype(jnp.float32)))
    worst = max(results, key=lambda r: r[1])
    check(worst[1] <= KERNEL_TOL,
          f"{worst[0]}: max rel err {worst[1]:.2e} > {KERNEL_TOL}")
    log(f"kernels: {len(results)} compiled kernels within {KERNEL_TOL} of "
        f"their oracles (worst {worst[1]:.2e})")


# ---------------------------------------------------------------------------
# 3. serve
# ---------------------------------------------------------------------------

def _served_step_hlo(jax, eng) -> str:
    """Compiled HLO of the step function this engine ran last."""
    import jax.numpy as jnp
    from repro.serving import core as C
    core = eng.core
    B = core.B
    i32 = lambda n: jnp.zeros((n,), jnp.int32)
    sample = (jnp.asarray(core._zero_poison), jnp.asarray(core.temps),
              jnp.asarray(core.topks), jnp.asarray(core.greedy),
              jnp.asarray(core.keys))
    if core.paged:
        Tb = max(n for kind, n in core.step_shapes if kind == "packed")
        fn = C._paged_step_fn(eng.cfg, Tb)
        args = (eng.params, core.caches, jnp.asarray(core.pager.page_table),
                i32(Tb), i32(Tb), i32(Tb), i32(B), i32(B)) + sample
    else:
        fn = C._decode_step_fn(eng.cfg)
        args = (eng.params, core.caches, i32(B)) + sample
    return fn.lower(*args).compile().as_text()


def _reference_check(jax, eng, prompts: list) -> tuple:
    """Score what the engine served against a float32 forward of the same
    weights that runs the plain jnp materialize path and no Pallas kernel,
    teacher-forced on each request's prompt + served tokens.

    Returns the relative L2 gap between the served model's forward logits
    (bf16, the plan's Pallas paths) and the reference's; the largest gap,
    in units of the reference row's standard deviation, between the
    reference's best logit and its logit for the token the engine served;
    and the share of served tokens that are the reference's argmax."""
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from repro.models import registry as R
    cfg = eng.cfg
    outs = sorted(eng.outputs(), key=lambda o: o.rid)
    seqs = [np.concatenate([prompts[o.rid], np.asarray(o.tokens, np.int32)])
            for o in outs]
    T = -(-max(len(s) for s in seqs) // 128) * 128
    toks = np.zeros((len(seqs), T), np.int32)    # right padding: causal
    for b, s in enumerate(seqs):
        toks[b, :len(s)] = s
    toks = jnp.asarray(toks)
    served = jax.jit(lambda p, t: R.forward(p, cfg, {"tokens": t})[0])
    check("tpu_custom_call" in served.lower(eng.params, toks).as_text(),
          "the served forward runs no Pallas kernel")
    ref_cfg = cfg.replace(dtype="float32", exec_plan=None,
                          ovsf=dataclasses.replace(cfg.ovsf,
                                                   exec_path="materialize"))
    ref_params = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, eng.params)
    ref_fn = jax.jit(lambda p, t: R.forward(p, ref_cfg, {"tokens": t})[0])
    check("tpu_custom_call" not in ref_fn.lower(ref_params, toks).as_text(),
          "the float32 reference ran a Pallas kernel")
    y = np.asarray(served(eng.params, toks), np.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(ref_fn(ref_params, toks), np.float32)
    del ref_params
    check(bool(np.isfinite(y).all()), "non-finite served logits")
    gap = float(np.linalg.norm(y - ref) / np.linalg.norm(ref))
    margins, hits = [], 0
    for b, (o, s) in enumerate(zip(outs, seqs)):
        p0 = len(s) - len(o.tokens) - 1     # logits here predict token 0
        for j, t in enumerate(o.tokens):
            row = ref[b, p0 + j]
            margins.append((row.max() - row[t]) / row.std())
            hits += int(row.argmax() == t)
    return gap, float(max(margins)), hits / len(margins)


def serve_phase(jax, hw, seed: int, clock: CompileClock, extra: list,
                label: str) -> dict:
    """One served run; returns its token streams by request id."""
    from repro.launch import serve
    from repro.models import registry as R
    argv = ["--arch", ARCH, "--slots", "8", "--buffer", str(BUFFER),
            "--requests", str(REQUESTS), "--max-new", "32", "--hw", hw.name,
            "--seed", str(seed)] + extra
    log(f"serve {label}: {' '.join(argv)}")
    c0, h0, t0 = clock.seconds, clock.hits, time.perf_counter()
    eng = serve.main(argv)
    wall = time.perf_counter() - t0
    st, cfg = eng.stats, eng.cfg
    outs = eng.outputs()
    reasons = sorted({o.finish_reason for o in outs})
    log(f"serve {label}: params {R.param_count(eng.params) / 1e6:.1f}M, "
        f"layers {cfg.n_layers}, d_model {cfg.d_model}")
    for name, lp in cfg.exec_plan.entries:
        log(f"serve {label}: {name} -> {lp.path} blocks=({lp.block_m},"
            f"{lp.block_n},{lp.block_k},{lp.block_j})")
    log(f"serve {label}: compile {clock.seconds - c0:.1f}s "
        f"(cache hits {clock.hits - h0}), wall {wall:.1f}s, "
        f"{st.tokens_out / wall:.1f} tok/s (information only), "
        f"finish {reasons}, recoveries {st.recoveries}, errors {st.errors}")
    check(cfg.n_layers == 22 and cfg.d_model == 2048,
          f"served {cfg.n_layers} layers at d_model {cfg.d_model}")
    check(len(outs) == REQUESTS and set(reasons) <= {"length", "eos"},
          f"{len(outs)} requests finished, reasons {reasons}")
    check(st.recoveries == 0 and st.errors == 0,
          f"recoveries {st.recoveries}, errors {st.errors}")
    n_calls = _served_step_hlo(jax, eng).count("tpu_custom_call")
    log(f"serve {label}: served step holds {n_calls} tpu_custom_call(s)")
    check(n_calls > 0, "the served step runs no Pallas kernel")
    prompts = serve.synthetic_prompts(REQUESTS, BUFFER, cfg.vocab, seed)
    gap, margin, agree = _reference_check(jax, eng, prompts)
    log(f"serve {label}: logits vs float32 reference: rel L2 {gap:.2e}; "
        f"served tokens: {agree:.1%} the reference's argmax, worst "
        f"{margin:.3f} std below its best logit")
    check(gap <= MODEL_TOL, f"logits gap {gap:.2e} > {MODEL_TOL}")
    check(margin <= TOKEN_TOL,
          f"a served token is {margin:.3f} std below the reference's best "
          f"logit (> {TOKEN_TOL})")
    return {o.rid: tuple(o.tokens) for o in outs}


# ---------------------------------------------------------------------------
# 4. four chips: the sharded trainer
# ---------------------------------------------------------------------------

def train_phase(jax, seed: int) -> None:
    from repro.launch import train
    ckpt_root = ROOT / ".smoke_ckpt"
    losses = {}
    try:
        for label, mesh in (("data=4", ["--data-par", "4"]),
                            ("data=2 x model=2",
                             ["--data-par", "2", "--model-par", "2"])):
            ckpt = ckpt_root / label.replace(" ", "").replace("=", "")
            shutil.rmtree(ckpt, ignore_errors=True)
            argv = ["--arch", ARCH, "--steps", "3", "--batch", "8",
                    "--seq", "128", "--seed", str(seed), "--save-every",
                    "1000", "--ckpt", str(ckpt)] + mesh
            log(f"train {label}: {' '.join(argv)}")
            report = train.main(argv)
            check(report.steps_run == 3 and report.failures == 0,
                  f"{label}: {report.steps_run} steps, "
                  f"{report.failures} failures")
            losses[label] = report.losses
            for d in jax.devices():
                ms = d.memory_stats() or {}
                log(f"train {label}: device {d.id} in use "
                    f"{ms.get('bytes_in_use', 0) / 2**30:.2f} GiB, peak "
                    f"{ms.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")
    finally:
        shutil.rmtree(ckpt_root, ignore_errors=True)
    a, b = losses.values()
    gap = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    log(f"train: losses {a} vs {b}, max relative gap {gap:.2e}")
    check(gap <= LOSS_TOL, f"loss gap {gap:.2e} > {LOSS_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded-trainer phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("[smoke] FAILED: src/repro not found next to chip_smoke.py; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.monitoring
    try:
        dev, hw = device_phase(jax, args.chips)
        if args.chips == 4:
            check(len(jax.devices()) == 4,
                  f"--chips 4 needs 4 devices, {len(jax.devices())} found")
            train_phase(jax, args.seed)
        else:
            clock = CompileClock(jax.monitoring)
            kernel_phase(jax, hw, args.seed)
            a = serve_phase(jax, hw, args.seed, clock, [], "phase-based")
            b = serve_phase(jax, hw, args.seed, clock,
                            ["--chunk-size", "64", "--packed", "--paged"],
                            "chunked packed paged")
            same = sum(a[r] == b[r] for r in a)
            log(f"serve: {same} of {len(a)} token streams identical across "
                f"the two runs (bf16 near-ties may differ; information only)")
            log(f"total backend compile {clock.seconds:.1f}s, persistent "
                f"cache hits {clock.hits}")
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
