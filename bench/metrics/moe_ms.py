"""moe_ms: device milliseconds per step of the window in the ``moe`` scope
and its parts (``moe.router``, ``moe.dispatch``, ``moe.experts``: expert
weight generation and GEMMs, ``moe.combine``), from the traced window by
``bench/scopes.py``."""
from pathlib import Path

from bench import scopes


def read(ctx):
    red = scopes.of(ctx, Path(__file__).parents[2])
    return None if red is None else red.scope_ms(
        lambda s: s == "moe" or s.startswith("moe."))
