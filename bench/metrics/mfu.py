"""mfu: least flops of the tokens the window's steps processed
(``bench/work.py``), over the window's seconds times the chip's bf16 peak."""
from bench import work


def read(ctx):
    if not ctx.step_tokens or ctx.window_s <= 0:
        return None
    flops = sum(work.step_work(ctx.model, st).flops for st in ctx.step_tokens)
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops"])
