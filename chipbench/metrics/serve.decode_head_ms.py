"""Device milliseconds per decode step in the model's ``embed`` and ``head``
scopes (the token embedding, the final norm and the logits)."""
from chipbench.scopes import decode_ms


def read(ctx):
    return decode_ms(ctx, "embed", "head")
