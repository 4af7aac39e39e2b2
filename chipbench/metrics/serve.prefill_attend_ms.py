"""Device milliseconds per prefill program run in the model's ``attend``
scope: the attention over the prompt."""
from chipbench import scopes


def read(ctx):
    res = scopes.read(ctx, "prefill")
    return None if res is None else res["buckets"].get("attend", 0.0)
