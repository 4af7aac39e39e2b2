"""Device milliseconds per decode step in the FFNs: the model's ``mlp`` scope
(ln2 and the residual) and within it ``moe_route`` (the router),
``moe_experts`` (the held experts) and ``moe_shared`` (the shared
expert)."""
from chipbench.driver_scopes import decode_ms


def read(ctx):
    return decode_ms(ctx, "mlp", "moe_route", "moe_experts", "moe_shared")
