"""Device milliseconds per decode step in the model's ``mlp`` scope (ln2, the
MLP and its residual)."""
from chipbench.scopes import decode_ms


def read(ctx):
    return decode_ms(ctx, "mlp")
