"""queue_wait_p90_s: p90, over the requests admitted in the window, of the
time from the engine's submit to the request's first binding to a slot
(``t_admit - t_submit``, carried by the program's ``engine.admit`` span in
the trace; ``bench/scopes.py``). The scheduler's share of ``ttft_p90_s``."""
from pathlib import Path

from bench import scopes, window


def read(ctx):
    red = scopes.of(ctx, Path(__file__).parents[2])
    return None if red is None else window.percentile(red.queue_waits, 90)
