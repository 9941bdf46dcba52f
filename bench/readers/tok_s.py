"""Prompt and output tokens processed in the window over its measured
seconds."""


def read(ctx):
    return (ctx.window.out_tokens + ctx.window.prefill_tokens) / ctx.window.seconds
