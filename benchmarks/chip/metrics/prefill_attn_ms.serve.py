"""Device ms per prefill launch under the model's ``attn/core`` scope: the
read of the packed paged cache for each layer's chunk window (trace, op
scopes)."""

import op_scopes
import readers


def read(ctx):
    return op_scopes.device_ms(ctx, readers.PREFILL_PROGRAM,
                               lambda op: op_scopes.under(op, "attn/core"))
