"""ttft_p90_s: p90, over requests whose first token falls in the window, of
the time from the client's send to that token (host clock). A per-layer
reading of the scheduler's queue: in a closed loop at this load (olmoe.chat
on a TPU v5e) it swings by 10-15% from seed to seed, too much for an
end-to-end bound."""
from bench import window


def read(ctx):
    return window.percentile(ctx.window.ttfts, 90)
