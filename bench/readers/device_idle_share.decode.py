"""Share of the traced window with no operation running on the device."""

from bench import measure


def read(ctx):
    return measure.idle_share(ctx)
