"""Device ms per decode launch under the model's ``kv_write`` scope: the
quantize, pack and scatter of each layer's new K/V into the page pool
(trace, op scopes)."""

import op_scopes
import readers


def read(ctx):
    return op_scopes.device_ms(ctx, readers.DECODE_PROGRAM,
                               lambda op: op_scopes.under(op, "kv_write"))
