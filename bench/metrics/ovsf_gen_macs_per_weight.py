"""ovsf_gen_macs_per_weight: the fused OVSF kernel's weight-generator MACs
per generated weight per M-tile: ``n_run * bj`` (the alpha chunks of ``bj``
rows its loop runs per k-block), averaged over the distinct ``ovsf_gemm``
calls the program traced, weighted by their ``d_in * d_out`` weights. A
counter the kernel notes at trace time, read through
``repro.serving.trace.kernel_notes``; None for a program without it."""


def read(ctx):
    try:
        from repro.serving import trace as program
    except ImportError:
        return None
    notes = getattr(program, "kernel_notes", None)
    calls = notes() if notes is not None else []
    weights = sum(n["d_in"] * n["d_out"] for n in calls)
    if not weights:
        return None
    return sum(n["n_run"] * n["bj"] * n["d_in"] * n["d_out"]
               for n in calls) / weights
