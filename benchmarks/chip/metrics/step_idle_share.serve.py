"""Device idle share inside the benchmark's spans around engine.step
(trace)."""

import readers


def read(ctx):
    return readers.idle_share_pct(ctx)
