"""``serve.device_idle_share`` in the long-generation cell: per cent of the
traced window in which no operation ran on the chip."""
from chipbench.harness import read_metric


def read(ctx):
    return read_metric("serve.device_idle_share", ctx)
