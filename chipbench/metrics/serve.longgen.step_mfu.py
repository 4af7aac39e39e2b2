"""Model FLOPs utilisation of the hybrid model's serving step: the model
FLOPs (``costs.hybrid_lm``: of the routed experts only the assignments that
land on experts held here) of every prompt prefilled and every token
decoded in the traced part of the window, over the device time of the
prefill, insertion and decode programs and the chip's bf16 peak."""
from chipbench.costs import hybrid_lm
from chipbench.trace import module_stats


def read(ctx):
    t, st, pk = ctx["trace"], ctx["state"], ctx["peaks"]
    if not t or not pk or st["traced_from"] is None:
        return None
    n_steps, n_admitted = st["traced_from"]
    cfg = st["dims"]
    flops = sum(hybrid_lm.prefill_flops(cfg, p)
                for _, p in st["admitted"][n_admitted:])
    flops += sum(hybrid_lm.decode_flops(cfg, live, keys)
                 for _, live, keys in st["steps"][n_steps:])
    secs = sum(module_stats(t["events"], f"jit_chipbench_{p}")["seconds"]
               for p in ("prefill", "insert", "decode"))
    if secs <= 0:
        return None
    return 100.0 * flops / (secs * pk["bf16_flops_per_s"])
