"""``serve.decode_unscoped_ms`` in the saturated cell, where it moves the
tokens completed per second."""
from chipbench.harness import read_metric


def read(ctx):
    return read_metric("serve.decode_unscoped_ms", ctx)
