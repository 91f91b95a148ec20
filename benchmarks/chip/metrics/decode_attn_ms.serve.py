"""Device ms per decode launch of the Pallas kernel
``ulppack_attention_decode`` (trace, kernel name)."""

import op_scopes
import readers


def read(ctx):
    return op_scopes.device_ms(
        ctx, readers.DECODE_PROGRAM,
        lambda op: op.base == "ulppack_attention_decode")
