"""Model operations of the traced rounds over traced window x peak bf16 FLOP/s."""

from bench import measure


def read(ctx):
    return measure.mfu(ctx)
