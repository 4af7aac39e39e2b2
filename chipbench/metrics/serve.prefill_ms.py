"""Device milliseconds of one prefill program run (batch 1, one prompt),
from the trace's ``jit_chipbench_prefill`` runs in the window."""
from chipbench.trace import module_ms


def read(ctx):
    return module_ms(ctx["trace"], "jit_chipbench_prefill")
