"""Routed assignments that one expert held here receives in one layer of
one decode step, the mean over the traced part's steps, the layers and the
held experts: the program's own counter (``expert_counts``, which the
decode program returns for a model with experts)."""


def read(ctx):
    import numpy as np
    st = ctx["state"]
    counts = st.get("expert_counts")
    if not counts or st["traced_from"] is None:
        return None
    traced = counts[st["traced_from"][0]:]
    if not traced:
        return None
    return float(np.mean([np.asarray(n).mean() for n in traced]))
