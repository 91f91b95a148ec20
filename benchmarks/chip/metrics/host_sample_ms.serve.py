"""Host ms per ``engine.step`` inside the engine's ``engine.sample``
spans: sampling every row of a pass (program span)."""

import op_scopes


def read(ctx):
    return op_scopes.host_ms_per_step(ctx, "engine.sample")
