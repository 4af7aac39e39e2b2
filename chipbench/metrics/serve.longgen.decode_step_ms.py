"""Device milliseconds of one decode step over every slot of the hybrid
model, from the trace's ``jit_chipbench_decode`` runs in the window."""
from chipbench.trace import module_ms


def read(ctx):
    return module_ms(ctx["trace"], "jit_chipbench_decode")
