"""step_ms: the window's seconds over its ``LLMEngine.step()`` calls, by the
benchmark's host clock."""


def read(ctx):
    if not ctx.steps:
        return None
    return 1e3 * ctx.window_s / ctx.steps
