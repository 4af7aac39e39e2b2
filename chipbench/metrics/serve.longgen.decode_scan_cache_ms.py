"""Device milliseconds per decode step in the layer scan's own work (the
model's ``layers`` scope under no block scope): slicing each layer's
weights and reading each layer's keys and values and Mamba-2 state out of
the stacked cache the scan carries (the state's write back lies in
``ssd_state``, ``serve.longgen.decode_ssd_ms``)."""
from chipbench.driver_scopes import decode_ms


def read(ctx):
    return decode_ms(ctx, "layers")
