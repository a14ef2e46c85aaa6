"""ovsf_linear_ms: device milliseconds per step of the window in the
``linear.<weight type>`` scopes of the trunk linears stored as OVSF
coefficients (``bench/work.py`` ``is_ovsf``): weight generation and GEMM,
by scope, not by kernel name (``bench/scopes.py``)."""
from pathlib import Path

from bench import scopes, work


def read(ctx):
    red = scopes.of(ctx, Path(__file__).parents[2])
    if red is None:
        return None
    m = ctx.model
    names = {f"linear.{li.name}" for li in work.trunk_linears(m)
             if work.is_ovsf(li, m["ovsf"])}
    return red.scope_ms(lambda s: s in names)
