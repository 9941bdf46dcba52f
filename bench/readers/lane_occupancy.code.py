"""Mean share of lanes holding a request over the window's rounds."""

from bench import measure


def read(ctx):
    return measure.occupancy(ctx)
