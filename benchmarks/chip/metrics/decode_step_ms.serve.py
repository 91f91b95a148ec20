"""Device time per launch of the decode step program (trace)."""

import readers


def read(ctx):
    return readers.step_ms(ctx, readers.DECODE_PROGRAM)
