"""Device milliseconds per decode step in the Mamba-2 mixers: the model's
``ssd`` scope (ln1, the projections, the convolution, the gated norm and
the residual) and ``ssd_state`` within it (the state recurrence and the
state's write back into the stacked cache, into which XLA fuses the
update)."""
from chipbench.driver_scopes import decode_ms


def read(ctx):
    return decode_ms(ctx, "ssd", "ssd_state")
