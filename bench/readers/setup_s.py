"""Seconds from process start to the first round of the window."""


def read(ctx):
    return ctx.setup_s
