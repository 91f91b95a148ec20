"""Host ms per ``engine.step`` inside the engine's ``engine.batch`` spans:
numpy batch, block tables, copy-on-write, device puts (program span)."""

import op_scopes


def read(ctx):
    return op_scopes.host_ms_per_step(ctx, "engine.batch")
