"""Output tokens committed in the window over its measured seconds."""


def read(ctx):
    return ctx.window.out_tokens / ctx.window.seconds
