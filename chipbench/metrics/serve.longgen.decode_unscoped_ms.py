"""Device milliseconds per decode step of the hybrid model in operations of
no model scope: the copies XLA puts in and the benchmark's own work around
the model (the argmax of the logits among it)."""
from chipbench.driver_scopes import decode_ms


def read(ctx):
    return decode_ms(ctx, "unscoped")
