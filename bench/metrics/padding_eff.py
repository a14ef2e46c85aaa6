"""padding_eff: valid tokens over the tokens the device batches carried, in
the window (the engine's packed/padded counters, as deltas)."""


def read(ctx):
    if not ctx.batch_tokens:
        return None
    return 100.0 * ctx.valid_tokens / ctx.batch_tokens
