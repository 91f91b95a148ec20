"""Mean wait from due time to admission over the window's requests
(program span: Request.admit_time)."""

import readers


def read(ctx):
    return readers.queue_wait_mean_ms(ctx)
