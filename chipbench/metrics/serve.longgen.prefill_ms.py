"""Device milliseconds of one prefill program run of the hybrid model
(batch 1, one prompt), from the trace's ``jit_chipbench_prefill`` runs."""
from chipbench.trace import module_ms


def read(ctx):
    return module_ms(ctx["trace"], "jit_chipbench_prefill")
