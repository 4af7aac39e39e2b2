"""Per cent of its roofline that the hybrid model's decode step reaches: for
each step in the traced part of the window the least time, the larger of
its model FLOPs over the bf16 peak and the bytes it must move
(``costs.hybrid_lm.decode_bytes``: every weight held here, each live
sequence's Mamba-2 state read and written, the keys and values its live
sequences attend to) over the HBM peak, summed and divided by the decode
programs' device time. The bytes bound it."""
from chipbench.costs import hybrid_lm
from chipbench.trace import module_stats


def read(ctx):
    t, st, pk = ctx["trace"], ctx["state"], ctx["peaks"]
    if not t or not pk or st["traced_from"] is None:
        return None
    dev = module_stats(t["events"], "jit_chipbench_decode")
    steps = st["steps"][st["traced_from"][0]:]
    if not dev["count"] or not steps:
        return None
    cfg = st["dims"]
    least = sum(max(hybrid_lm.decode_flops(cfg, live, keys)
                    / pk["bf16_flops_per_s"],
                    hybrid_lm.decode_bytes(cfg, live, keys)
                    / pk["hbm_bytes_per_s"]) for _, live, keys in steps)
    # the trace may hold a step more or less than the list
    least *= dev["count"] / len(steps)
    return 100.0 * least / dev["seconds"]
