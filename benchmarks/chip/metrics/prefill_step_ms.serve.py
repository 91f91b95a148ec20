"""Device time per launch of the chunked-prefill step program (trace)."""

import readers


def read(ctx):
    return readers.step_ms(ctx, readers.PREFILL_PROGRAM)
