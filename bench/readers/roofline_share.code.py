"""Least time of the traced rounds over their device time, in %."""

from bench import measure


def read(ctx):
    return measure.roofline_share(ctx)
