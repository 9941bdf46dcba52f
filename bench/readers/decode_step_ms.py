"""Device time of the traced decode-only rounds over their decode steps, in
milliseconds."""

from bench import measure


def read(ctx):
    return measure.decode_step_ms(ctx)
