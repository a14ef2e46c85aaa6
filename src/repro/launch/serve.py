"""Serving launcher: batched requests through the request-level API.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1_1b --smoke \
      --requests 8 --max-new 12 --hw v5e --temperature 0.8 --top-k 40

``--hw`` picks the hardware target the mapper plans against (any registered
preset: v5e/v5p/v6e/cpu); ``--no-bucketing`` reverts to per-prompt-length
prefill (the pre-bucketing behaviour) for A/B comparison. ``--chunk-size N``
switches to step-based serving: queued prompts feed through the decode-shaped
path in N-token chunks, interleaved with decode in one fused call per step.
``--packed`` (with ``--chunk-size``) replaces the padded (B, W) window step
with the token-packed step: only valid tokens reach the model, and the
padding-efficiency counters are reported. ``--paged`` (with
``--chunk-size``; composes with ``--packed``) swaps the per-slot contiguous
KV buffers for a paged pool (``--page-size`` tokens per page, ``--kv-pages``
pool size) and reports the page-pool utilization counters. ``--calibrate`` records measured
step times against the mapper's analytical model and reports which layers a
calibrated re-plan would re-map (optionally saving the table with
``--calibration-out``).

Chaos flags (see ``docs/serving.md`` "Failure semantics"): ``--inject`` adds
deterministic faults (repeatable; e.g. ``--inject nan:step=3,slot=0
--inject fail:step=7``), ``--admission preempt`` + per-request priorities
exercise preemption-and-recompute, ``--max-waiting``/``--deadline`` bound
the queue and request lifetimes. The launcher exits non-zero if any request
that was NOT deliberately poisoned fails to complete — the CI chaos smoke
rides exactly this contract.

Durability (see ``docs/serving.md`` "Durability & crash recovery"):
``--journal DIR`` arms the write-ahead request journal — admissions, token
batches, and finishes are fsync'd to DIR, and a restarted launcher pointed
at the same DIR recovers every non-terminal request token-identically
instead of re-submitting it. ``--supervise`` runs the launcher as a child
under a restart loop so ``--inject die:step=N`` (a hard ``os._exit``
mid-run, nothing catchable) exercises a real process death: the supervisor
restarts the child with the ``die`` injector stripped and the exit
contract must still hold — every request terminal exactly once.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry as R
from repro.runtime.faults import FaultPlan
from repro.serving import (LLMEngine, Request, RequestJournal, SamplingParams,
                           hw_names)


def synthetic_prompts(n: int, buffer: int, vocab: int,
                      seed: int) -> list[np.ndarray]:
    """The launcher's ``n`` request prompts: 4 .. buffer/4 random ids each,
    made from ``seed``."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        plen = int(rng.integers(4, buffer // 4))
        out.append(rng.integers(0, vocab, plen, dtype=np.int32))
    return out


def main(argv=None) -> LLMEngine | None:
    """Serve the requests; returns the drained engine (None when the run
    was handed to a supervised child)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--buffer", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hw", default="v5e", choices=list(hw_names()),
                    help="hardware target for the mapper's execution plans")
    ap.add_argument("--alpha-dtype", default="", choices=["", "int8", "int4"],
                    help="quantised alpha storage: int8 halves / int4 "
                         "quarters the streamed alpha bytes (dequantised "
                         "in-kernel by the fused generator)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples with per-request seeds")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--no-bucketing", action="store_true",
                    help="prefill each prompt at its native length")
    ap.add_argument("--admission", default="reject",
                    choices=["reject", "truncate", "preempt"])
    ap.add_argument("--inject", action="append", default=[],
                    metavar="KIND:KEY=V,...",
                    help="deterministic fault injection, repeatable: "
                         "nan:step=3,slot=0 | fail:step=7 | "
                         "delay:p=0.1,s=0.002 (seed-driven, reproducible)")
    ap.add_argument("--max-waiting", type=int, default=None,
                    help="bound the waiting queue; overloads load-shed the "
                         "least-urgent request (FINISH_SHED)")
    ap.add_argument("--step-timeout", type=float, default=None,
                    help="soft per-step watchdog: a slower step counts a "
                         "stall and triggers a core rebuild + recompute")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds (FINISH_TIMEOUT "
                         "past it)")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="step-based serving: interleave N-token prompt "
                         "chunks with decode (None = phase-based prefill)")
    ap.add_argument("--packed", action="store_true",
                    help="token-packed step: flatten the step's valid "
                         "tokens into one dense stream instead of the "
                         "padded (B, W) window (requires --chunk-size)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: per-slot page tables over a "
                         "shared page pool instead of per-slot contiguous "
                         "buffers (requires --chunk-size; composes with "
                         "--packed)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged; must divide --buffer)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="page-pool size (--paged; default slots*buffer/"
                         "page_size — enough for every slot at full length)")
    ap.add_argument("--calibrate", action="store_true",
                    help="record measured-vs-modeled step times and report "
                         "the calibrated re-plan")
    ap.add_argument("--calibration-out", default="",
                    help="write the calibration table JSON here")
    ap.add_argument("--journal", default="",
                    help="write-ahead request journal directory: every "
                         "admission/token/finish is fsync'd there, and on "
                         "startup non-terminal journaled requests are "
                         "recovered token-identically (crash durability)")
    ap.add_argument("--supervise", action="store_true",
                    help="run this launcher as a supervised child process: "
                         "an injected die fault (--inject die:step=N) "
                         "hard-kills it and the supervisor restarts it to "
                         "recover via --journal (the CI kill-9 smoke)")
    args = ap.parse_args(argv)

    if args.supervise:
        from repro.launch.supervise import supervise
        raw = list(sys.argv[1:] if argv is None else argv)
        if not args.journal:
            raise SystemExit("--supervise requires --journal: a crash "
                             "without a journal loses every live request")
        supervise("repro.launch.serve",
                  [a for a in raw if a != "--supervise"])
        return None

    print(f"[serve] compile cache: {enable_compile_cache()}")
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.alpha_dtype:
        if not cfg.ovsf.enable:
            print(f"[serve] --alpha-dtype {args.alpha_dtype} ignored: "
                  f"{cfg.name} has no OVSF layers")
        cfg = cfg.replace(ovsf=dataclasses.replace(
            cfg.ovsf, alpha_dtype=args.alpha_dtype))
    key = jax.random.PRNGKey(args.seed)
    params = R.model_init(key, cfg)
    print(f"[serve] {cfg.name}: {R.param_count(params)/1e6:.1f}M params "
          f"(hw={args.hw}"
          + (f", alphas={args.alpha_dtype}" if args.alpha_dtype else "")
          + ")")

    if args.packed and args.chunk_size is None:
        raise SystemExit("--packed requires --chunk-size")
    if args.paged and args.chunk_size is None:
        raise SystemExit("--paged requires --chunk-size")
    plan = FaultPlan.parse(args.inject, seed=args.seed)
    if any(f.kind == "flip" for f in plan.faults):
        raise SystemExit(
            "--inject flip:... corrupts a RESIDENT registry bank, which a "
            "single-engine launcher does not have; use repro.launch.gateway "
            "with --scrub-every to exercise bank corruption + scrub repair")
    if plan:
        print(f"[serve] chaos: {len(plan.faults)} injector(s) armed "
              f"(seed={args.seed}): "
              + ", ".join(f.kind for f in plan.faults))
    journal = RequestJournal(args.journal) if args.journal else None
    eng = LLMEngine(params, cfg, batch_slots=args.slots,
                    buffer_len=args.buffer, hw=args.hw,
                    bucketed_prefill=not args.no_bucketing,
                    admission=args.admission, chunk_size=args.chunk_size,
                    packed=args.packed, paged=args.paged,
                    page_size=args.page_size, kv_pages=args.kv_pages,
                    calibrate=args.calibrate,
                    max_waiting=args.max_waiting,
                    step_timeout_s=args.step_timeout,
                    faults=plan if plan else None,
                    journal=journal)
    if journal is not None and journal.entries:
        recovered = eng.recover_from_journal()
        ndone = sum(1 for e in journal.entries.values() if e.done)
        print(f"[serve] journal: {len(recovered)} live request(s) recovered "
              f"mid-stream, {ndone} already terminal (replayed, not re-run)")
    prompts = synthetic_prompts(args.requests, args.buffer, cfg.vocab,
                                args.seed)
    for rid, prompt in enumerate(prompts):
        if journal is not None and rid in journal.entries:
            continue    # journaled before the crash: recovered or terminal
        admitted, bp = eng.add_request(Request(
            rid, prompt,
            max_new_tokens=args.max_new,
            deadline_s=args.deadline,
            sampling=SamplingParams(temperature=args.temperature,
                                    top_k=args.top_k, seed=rid)))
        if not admitted:
            print(f"[serve] request {rid} not admitted "
                  f"(backpressure={bp:.2f})")
    t0 = time.perf_counter()
    stats = eng.run_until_drained()
    dt = time.perf_counter() - t0
    print(f"[serve] completed={stats.completed} rejected={stats.rejected} "
          f"steps={stats.steps} tokens={stats.tokens_out} "
          f"({stats.tokens_out/dt:.1f} tok/s)")
    if plan or stats.preemptions or stats.timeouts or stats.shed:
        print(f"[serve] faults: errors={stats.errors} "
              f"recoveries={stats.recoveries} stalls={stats.stalls} "
              f"preemptions={stats.preemptions} timeouts={stats.timeouts} "
              f"shed={stats.shed}")
    print(f"[serve] prefill={stats.prefill_s:.2f}s (batches="
          f"{stats.prefill_batches}, compiles={stats.prefill_compiles}) "
          f"decode={stats.decode_s:.2f}s mixed={stats.mixed_s:.2f}s "
          f"step_compiles={stats.step_compiles}")
    print(f"[serve] padding: valid={stats.packed_tokens} "
          f"batch={stats.padded_tokens} "
          f"efficiency={stats.padding_efficiency:.2f}"
          + (" (packed)" if args.packed else ""))
    if args.paged:
        print(f"[serve] kv_pages: total={stats.kv_pages_total} "
              f"peak_used={stats.kv_pages_used} "
              f"peak_bytes={stats.kv_bytes_used} "
              f"utilization={stats.kv_utilization:.2f}")
    print(f"[serve] weight_cache: hits={stats.weight_cache_hits} "
          f"misses={stats.weight_cache_misses} "
          f"entries={stats.weight_cache_entries} "
          f"bytes={stats.weight_cache_bytes}")

    if args.calibrate:
        old = eng.cfg.exec_plan
        new = eng.replan()
        if old is None or not len(eng.calibration):
            print("[serve] calibrate: no OVSF plan / no decode samples "
                  "recorded — nothing to correct")
        else:
            changed = [(n, a.path, b.path)
                       for (n, a), (_n, b) in zip(old.entries, new.entries)
                       if a.path != b.path]
            facs = eng.calibration.factors(eng.hw_label)
            print(f"[serve] calibrate: {len(eng.calibration)} keys, "
                  f"relative factors: "
                  + ", ".join(f"{k}={v:.2f}" for k, v in sorted(facs.items())))
            if changed:
                for n, a, b in changed:
                    print(f"[serve] calibrate: {n}: {a} -> {b}")
            else:
                print("[serve] calibrate: measured factors keep every "
                      "layer on its modeled path")
        if args.calibration_out:
            eng.calibration.save(args.calibration_out)
            print(f"[serve] calibrate: table -> {args.calibration_out}")

    # Exit contract (the CI chaos smoke rides this): every request must be
    # terminal, and any finish reason other than eos/length must be
    # attributable to a degradation this invocation deliberately configured
    # (nan injection -> error, --deadline -> timeout, bounded queue /
    # preempt admission -> shed/preempted).
    outs = {o.rid: o for o in eng.outputs()}
    if journal is not None:
        # requests that went terminal BEFORE the crash live only in the
        # journal; they count as finished (exactly once — not re-run)
        for rid, e in journal.entries.items():
            if e.done and rid not in outs:
                outs[rid] = e
    allowed = {"eos", "length", "rejected"}
    if any(f.kind == "nan" for f in plan.faults):
        allowed.add("error")
    if args.deadline is not None:
        allowed.add("timeout")
    if args.max_waiting is not None or args.admission == "preempt":
        allowed.update(("shed", "preempted"))
    missing = [r for r in range(args.requests) if r not in outs]
    bad = [(r, outs[r].finish_reason) for r in outs
           if outs[r].finish_reason not in allowed]
    if missing or bad:
        raise SystemExit(f"[serve] FAILED: unfinished={missing} "
                         f"unexpected={bad}")
    return eng


if __name__ == "__main__":
    main()
