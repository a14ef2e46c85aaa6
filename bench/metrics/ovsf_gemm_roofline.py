"""ovsf_gemm_roofline: least time of the window's OVSF-linear work outside
expert banks (the work the fused OVSF kernel serves: attention projections,
dense MLPs, shared experts, each in the layers that hold it;
``bench/work.py`` ``layer_linears``), over the device time of the kernel's
events in the traced window.

Least time of one call is the larger of its flops over the bf16 peak and its
bytes over the HBM peak (``bench/work.py``). The kernel's events are named
after its jitted wrapper, ``ovsf_gemm`` with an optional ``.N`` suffix.
"""
import re

from bench import trace, work

KERNEL = re.compile(r"^ovsf_gemm(\.\d+)?$")


def read(ctx):
    if ctx.trace is None or not ctx.step_tokens:
        return None
    m = ctx.model
    least = 0.0
    for st in ctx.step_tokens:
        for li, layers in work.layer_linears(m):
            if work.is_ovsf(li, m["ovsf"]):
                w = work.ovsf_linear(li, st.n_tokens, m["ovsf"])
                least += layers * w.least_seconds(
                    ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_s"])
    ops = ctx.trace.ops[min(ctx.trace.ops)]
    spent = sum(trace.seconds_by_name(ops, *ctx.trace_window,
                                      KERNEL.match).values())
    if spent <= 0:
        return None
    return 100.0 * least / spent
