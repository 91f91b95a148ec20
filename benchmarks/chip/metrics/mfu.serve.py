"""Whole steps: model work at the chip's peaks over the traced window
(trace, launches)."""

import readers


def read(ctx):
    return readers.mfu_pct(ctx)
