"""Device milliseconds per decode step in the attention blocks' scopes:
``attn_qkv``, ``kv_write``, ``attend`` and ``attn_out``."""
from chipbench.scopes import decode_ms


def read(ctx):
    return decode_ms(ctx, "attn_qkv", "kv_write", "attend", "attn_out")
