"""attn_ms: device milliseconds per step of the window in the ``attention``
scope: the KV scatter and gather, scores, softmax and PV; the attention
projections count in their ``linear.*`` scopes (``bench/scopes.py``)."""
from pathlib import Path

from bench import scopes


def read(ctx):
    red = scopes.of(ctx, Path(__file__).parents[2])
    return None if red is None else red.scope_ms(lambda s: s == "attention")
