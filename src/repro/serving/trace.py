"""Profiling hooks of the serving program.

Host spans: ``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``.
Under ``jax.profiler.trace`` it records an event on the host's timeline, on
the same clock as the device's operations; with no profiler active it costs
about a microsecond. The engine opens these, all named ``engine.*``:

* ``engine.step`` — one ``LLMEngine.step()``, holding in turn
  ``engine.schedule`` (deadline expiry, the scheduler, preemptions,
  shedding), ``engine.page_gate`` (KV page grants), ``engine.admit`` (one
  per request bound to a slot for the first time, with its ``rid`` and
  ``queue_wait_s`` as arguments), ``engine.pack`` (the step's host arrays),
  ``engine.launch`` (their upload and the jitted call), ``engine.wait`` (the
  host blocking on the device's outputs) and ``engine.commit`` (tokens,
  finishes, the journal flush);
* ``engine.recover`` — a watchdog rebuild of the engine core.

Device scopes (``jax.named_scope``, in the models and ``serving.core``) name
the layers of the jitted step in each HLO instruction's ``op_name``
metadata: ``embed``, ``attention``, ``linear.<weight type>``, ``moe`` with
``moe.router``/``moe.dispatch``/``moe.experts``/``moe.combine``,
``unembed`` and ``sample``. A profile read without HLO protos names device
operations by instruction only; ``step_program_texts()`` gives the compiled
text that maps each instruction to its scope.

Kernel counters: ``kernel_notes()`` gives what the kernels noted of each
call they traced, such as the alpha chunks the fused OVSF generator runs
per k-block. ``expert_notes()`` gives what each expert-bank call of the MoE
layer traced ran: its path and the dense W it materialised.
"""
from __future__ import annotations

import jax

from repro.kernels.ovsf_gemm import gemm_notes
from repro.models.moe import expert_notes as _expert_notes

# jitted step function -> abstract arguments of its first call. Process-wide,
# like the lru-cached step functions it keys on: a profile is reduced after
# the engine that ran them is gone.
_STEP_PROGRAMS: dict = {}


def span(name: str, **args):
    """A host span on the profiler's timeline (a no-op when none is on)."""
    return jax.profiler.TraceAnnotation(name, **args)


def _abstract(x):
    a = jax.typeof(x)
    return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                sharding=getattr(x, "sharding", None),
                                weak_type=a.weak_type)


def call_step(fn, *args):
    """Call a jitted step program, noting the shapes of its first call so
    ``step_program_texts`` can compile it again."""
    if fn not in _STEP_PROGRAMS:
        _STEP_PROGRAMS[fn] = jax.tree.map(_abstract, args)
    return fn(*args)


def step_program_texts() -> list:
    """The optimised HLO text, with ``op_name`` metadata, of each step
    program this process has called (at the shapes of its first call).
    Each is lowered and compiled again: a persistent compile-cache entry
    serves it where one exists, with the metadata of the run that wrote the
    entry (``jax_compilation_cache_include_metadata_in_key`` decides whether
    a scope change makes a new entry)."""
    return [fn.lower(*args).compile().as_text()
            for fn, args in _STEP_PROGRAMS.items()]


def kernel_notes() -> list:
    """What the kernels noted of the calls this process traced: one dict
    per distinct ``ovsf_gemm`` call (``kernels.ovsf_gemm.gemm_notes``),
    with ``n_run`` of its ``nc`` alpha chunks of ``bj`` rows run by the
    weight generator per k-block."""
    return gemm_notes()


def expert_notes() -> list:
    """What the MoE layer noted of the expert-bank calls this process
    traced: one dict per distinct call (``models.moe.expert_notes``), with
    the bank's ``name``, ``path``, ``experts``, ``rows`` per expert,
    ``d_in``, ``d_out``, ``j`` and ``dense_w_bytes``, the dense W the call
    materialises (0 on the spectral path)."""
    return _expert_notes()
