"""host_step_ms: the mean, over the window's ``engine.step`` spans, of the
span's time less its ``engine.wait`` (the host blocked on the device): the
engine's own host work per step, from the program's spans in the trace
(``bench/scopes.py``)."""
from pathlib import Path

from bench import scopes


def read(ctx):
    red = scopes.of(ctx, Path(__file__).parents[2])
    if red is None or not red.host_step_s:
        return None
    return 1e3 * sum(red.host_step_s) / len(red.host_step_s)
