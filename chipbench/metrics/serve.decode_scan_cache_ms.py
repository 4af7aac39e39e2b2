"""Device milliseconds per decode step in the layer scan's own work: what
lies under the model's ``layers`` scope and under no block scope, that is the
per-layer slicing and write-back of the stacked cache the scan carries."""
from chipbench.scopes import decode_ms


def read(ctx):
    return decode_ms(ctx, "layers")
