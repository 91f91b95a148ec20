"""Device ms per decode launch under the model's ``head`` scope: final
norm, output head and pad bias over the [16, vocab] logits (trace, op
scopes)."""

import op_scopes
import readers


def read(ctx):
    return op_scopes.device_ms(ctx, readers.DECODE_PROGRAM,
                               lambda op: op_scopes.under(op, "head"))
