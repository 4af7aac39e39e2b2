"""Per cent of its roofline that the decode step reaches: for each step
in the traced part of the window the least time, the larger of its model
FLOPs over the bf16 peak and the bytes it must move
(``costs.dense_lm.decode_bytes``: every weight, and the keys and values
its live sequences attend to) over the HBM peak, summed and divided by
the decode programs' device time. The bytes bound it."""
from chipbench.costs import dense_lm
from chipbench.trace import module_stats


def read(ctx):
    t, st, pk = ctx["trace"], ctx["state"], ctx["peaks"]
    if not t or not pk or st["traced_from"] is None:
        return None
    dev = module_stats(t["events"], "jit_chipbench_decode")
    steps = st["steps"][st["traced_from"][0]:]
    if not dev["count"] or not steps:
        return None
    dims = st["dims"]
    least = sum(max(dense_lm.decode_flops(dims, live, keys)
                    / pk["bf16_flops_per_s"],
                    dense_lm.decode_bytes(dims, live, keys)
                    / pk["hbm_bytes_per_s"]) for _, live, keys in steps)
    # the trace may hold a step more or less than the list
    least *= dev["count"] / len(steps)
    return 100.0 * least / dev["seconds"]
