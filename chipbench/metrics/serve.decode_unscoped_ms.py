"""Device milliseconds per decode step in operations of no model scope: the
copies XLA puts in (of the whole cache among them) and the benchmark's own
work around the model."""
from chipbench.scopes import decode_ms


def read(ctx):
    return decode_ms(ctx, "unscoped")
