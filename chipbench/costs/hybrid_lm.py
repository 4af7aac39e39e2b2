"""Operations and bytes of the hybrid decoder (granite-4.0-h's stage) from
its configuration's keys alone.

FLOPs are model FLOPs: 2 per weight touched per token (of the routed
experts, only the assignments that land on experts held here, ``k x held
/ router_experts`` a token on average), the Mamba-2 state's update and read
(4 per state element a token), attention's 4 x heads x head_dim per key
attended, and the head for each row whose logits are computed. Nothing
recomputed or padded counts.
"""
from __future__ import annotations

from typing import Dict

from chipbench.reference.hybrid_lm import shapes


def _kinds(cfg: Dict) -> Dict[str, int]:
    t = cfg["layer_types"]
    return {"mamba": t.count("mamba"), "attention": t.count("attention")}


def mixer_params(cfg: Dict, kind: str) -> int:
    """Matrix weights of one layer's mixer."""
    s = shapes(cfg)
    d = s["d"]
    if kind == "mamba":
        return (d * (2 * s["di"] + 2 * s["n"] + s["nh"]) + s["di"] * d
                + s["w"] * (s["di"] + 2 * s["n"]))
    return 2 * d * s["hq"] * s["dh"] + 2 * d * s["hkv"] * s["dh"]


def ffn_params(cfg: Dict, experts: float) -> float:
    """Matrix weights of one layer's FFN with ``experts`` routed experts
    (and the router, and the shared expert)."""
    s = shapes(cfg)
    d = s["d"]
    return (d * s["experts"] + 3 * d * s["sff"]
            + experts * 3 * d * s["ff"])


def state_elems(cfg: Dict) -> int:
    """Float32 elements of one Mamba-2 layer's state a sequence: h and the
    convolution's last inputs."""
    s = shapes(cfg)
    return s["nh"] * s["p"] * s["n"] + (s["w"] - 1) * (s["di"] + 2 * s["n"])


def forward_flops(cfg: Dict, tokens: int, keys_attended: int,
                  logit_rows: int) -> float:
    """``tokens`` new tokens that attend to ``keys_attended`` keys in all
    (summed over the tokens), with logits for ``logit_rows`` of them."""
    s, n = shapes(cfg), _kinds(cfg)
    routed = s["k"] * s["held"] / s["experts"]
    per_token = (sum(n[k] * mixer_params(cfg, k) for k in n)
                 + (n["mamba"] + n["attention"]) * ffn_params(cfg, routed))
    return (2 * per_token * tokens
            + 4 * n["mamba"] * s["nh"] * s["p"] * s["n"] * tokens
            + 4 * n["attention"] * s["hq"] * s["dh"] * keys_attended
            + 2 * s["d"] * s["v"] * logit_rows)


def prefill_flops(cfg: Dict, prompt: int) -> float:
    """A causal prefill of ``prompt`` tokens, logits of the last only."""
    return forward_flops(cfg, prompt, prompt * (prompt + 1) // 2, 1)


def decode_flops(cfg: Dict, batch: int, keys_attended: int) -> float:
    return forward_flops(cfg, batch, keys_attended, batch)


def decode_bytes(cfg: Dict, batch: int, keys_attended: int,
                 w_bytes: int = 2, kv_bytes: int = 2,
                 f32_bytes: int = 4) -> int:
    """HBM bytes one decode step of ``batch`` sequences must move at least:
    every weight held here once (the router in float32, as the program
    keeps it), the tied embedding as the head and the batch's embedding
    rows, the norm scales and Mamba-2 scalars, each sequence's Mamba-2
    state read and written, the keys and values of the ``keys_attended``
    keys (summed over the sequences) read, and the new token's keys and
    values written."""
    s, n = shapes(cfg), _kinds(cfg)
    d, layers = s["d"], n["mamba"] + n["attention"]
    matrices = (sum(n[k] * mixer_params(cfg, k) for k in n)
                + layers * (ffn_params(cfg, s["held"]) - d * s["experts"])
                + d * s["v"])
    f32 = (layers * (2 * d + d * s["experts"]) + d
           + n["mamba"] * (3 * s["nh"] + s["di"]))
    state = 2 * n["mamba"] * batch * state_elems(cfg) * f32_bytes
    kv = 2 * n["attention"] * s["hkv"] * s["dh"] * kv_bytes
    return (matrices * w_bytes + f32 * f32_bytes + batch * d * w_bytes
            + state + kv * (keys_attended + batch))
