"""Base configuration dataclasses for the repro framework.

Every architecture in ``src/repro/configs/<arch>.py`` instantiates a
:class:`ModelConfig`; every benchmark shape is a :class:`ShapeConfig`;
meshes and runtime knobs live in :class:`MeshConfig` / :class:`RunConfig`.

Configs are plain frozen dataclasses (no framework dependency) so they can be
hashed, used as jit static args, and serialized into checkpoints/manifests.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Mapping, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Block kinds understood by models/model.py. A layer stack is described by a
# repeating ``pattern`` of these (e.g. ("local", "global") for gemma2's
# alternating attention); remainder layers (depth % len(pattern)) are applied
# unscanned at the top of the stack.
# ---------------------------------------------------------------------------
BLOCK_GLOBAL_ATTN = "global"  # full (causal/prefix) attention
BLOCK_LOCAL_ATTN = "local"    # sliding-window attention
BLOCK_RGLRU = "rglru"         # RG-LRU recurrent block (recurrentgemma)
BLOCK_SSD = "ssd"             # Mamba-2 state-space duality block
VALID_BLOCKS = (BLOCK_GLOBAL_ATTN, BLOCK_LOCAL_ATTN, BLOCK_RGLRU, BLOCK_SSD)

ATTN_BLOCKS = (BLOCK_GLOBAL_ATTN, BLOCK_LOCAL_ATTN)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description. One instance per assigned architecture."""

    name: str
    family: str                      # dense | hybrid | moe | vlm | ssm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # Layer stack -----------------------------------------------------------
    pattern: Tuple[str, ...] = (BLOCK_GLOBAL_ATTN,)
    local_window: int = 0            # sliding window for BLOCK_LOCAL_ATTN

    # Attention variants ----------------------------------------------------
    use_qk_norm: bool = False        # qwen3-style RMSNorm on q/k heads
    attn_logit_softcap: float = 0.0  # gemma2: tanh softcap on attn logits
    final_logit_softcap: float = 0.0 # gemma2: tanh softcap on lm logits
    query_scale: float = 0.0         # 0 -> 1/sqrt(head_dim)
    rope_theta: float = 10000.0
    use_rope: bool = True            # False: no positional encoding (NoPE)
    parallel_block: bool = False     # command-r: attn & ffn in parallel
    attn_bias: bool = False

    # MLP -------------------------------------------------------------------
    mlp_activation: str = "swiglu"   # swiglu | geglu | gelu | squared_relu

    # MoE -------------------------------------------------------------------
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_d_ff: int = 0
    moe_dense_residual: bool = False # arctic: dense FFN residual alongside MoE
    # "ep": experts sharded over model axis (requires num_experts % tp == 0)
    # "tp": experts replicated, per-expert d_ff sharded over model axis
    moe_parallelism: str = "ep"
    # The experts held here, a chip's share under expert parallelism: the
    # router keeps all num_experts outputs, and the layer holds and computes
    # experts [moe_held_start, moe_held_start + moe_held). 0 holds them all.
    moe_held: int = 0
    moe_held_start: int = 0

    # SSM / recurrent -------------------------------------------------------
    ssm_state_dim: int = 0           # Mamba2 N
    ssm_head_dim: int = 64           # Mamba2 P
    ssm_expand: int = 2              # d_inner = expand * d_model
    ssm_chunk: int = 256             # SSD chunk length
    conv_width: int = 4              # depthwise conv width (mamba2 / rglru)
    rglru_width: int = 0             # RG-LRU recurrence width (0 -> d_model)
    ssd_ffn: bool = False            # an FFN (ln2, MLP or MoE) after each SSD mixer

    # Encoder-decoder -------------------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0

    # Modality frontend (stub: input_specs provides precomputed embeddings) --
    frontend: str = ""               # "" | "vision" | "audio"
    frontend_len: int = 256          # number of prefix embedding positions
    prefix_lm: bool = False          # full attention over the prefix segment

    # Embeddings ------------------------------------------------------------
    tie_embeddings: bool = True
    embed_scale: bool = True         # gemma-style sqrt(d_model) embed scaling
    embed_multiplier: float = 1.0    # the embeddings times this (granite)
    residual_multiplier: float = 1.0 # each block branch times this (granite)
    logits_scaling: float = 1.0      # the logits divided by this (granite)
    norm_eps: float = 1e-6

    # Sharding / runtime overrides (merged over parallel/sharding.py defaults)
    sharding_overrides: Tuple[Tuple[str, Any], ...] = ()
    # Optimizer memory class: "adamw" (fp32 m+v) or "adafactor" (factored).
    optimizer: str = "adamw"
    # Sub-quadratic decode support: archs with every-layer full attention
    # cannot run long_500k (see DESIGN.md §5).
    supports_long_context: bool = False

    # ------------------------------------------------------------------ utils
    def __post_init__(self):
        for b in self.pattern:
            if b not in VALID_BLOCKS:
                raise ValueError(f"unknown block kind {b!r} in pattern")
        if self.num_heads and self.num_heads % max(self.num_kv_heads, 1):
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if self.num_experts and self.num_experts_per_tok <= 0:
            raise ValueError("MoE config needs num_experts_per_tok > 0")
        if self.moe_held_start + self.moe_held > self.num_experts:
            raise ValueError("the experts held lie past num_experts")

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def attention_free(self) -> bool:
        return all(b not in ATTN_BLOCKS for b in self.pattern)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_num_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def scan_repeats(self) -> int:
        return self.num_layers // len(self.pattern)

    @property
    def remainder_blocks(self) -> Tuple[str, ...]:
        rem = self.num_layers % len(self.pattern)
        return self.pattern[:rem]

    def block_counts(self) -> Mapping[str, int]:
        counts: dict = {}
        for b in self.pattern:
            counts[b] = counts.get(b, 0) + self.scan_repeats
        for b in self.remainder_blocks:
            counts[b] += 1
        return counts

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        n = 0
        n += v * d                                    # token embedding
        if not self.tie_embeddings:
            n += v * d                                # lm head
        gated = self.mlp_activation in ("swiglu", "geglu")
        per_block = {}
        qkv = d * self.num_heads * self.head_dim + 2 * d * self.num_kv_heads * self.head_dim
        o = self.num_heads * self.head_dim * d
        attn = qkv + o + (2 * self.head_dim if self.use_qk_norm else 0)
        mlp = d * ff * (3 if gated else 2)
        ffn = mlp
        if self.num_experts:
            e_ff = self.moe_d_ff or ff
            held = self.moe_held or self.num_experts
            ffn = held * d * e_ff * (3 if gated else 2) + d * self.num_experts
            if self.moe_dense_residual:
                ffn += mlp
        per_block[BLOCK_GLOBAL_ATTN] = attn + 2 * d + ffn + (0 if self.num_experts else d)
        per_block[BLOCK_LOCAL_ATTN] = per_block[BLOCK_GLOBAL_ATTN]
        rw = self.rglru_width or d
        per_block[BLOCK_RGLRU] = (d * rw * 2 + rw * d + 3 * rw + rw * self.conv_width
                                  + 2 * d + mlp)
        di, ns, p = self.d_inner, self.ssm_state_dim, self.ssm_head_dim
        nh = di // p if p else 0
        per_block[BLOCK_SSD] = (d * (2 * di + 2 * ns + nh) + di * d
                                + (di + 2 * ns) * self.conv_width + 2 * nh + di + 2 * d)
        if self.ssd_ffn:
            per_block[BLOCK_SSD] += d + ffn
        for kind, cnt in self.block_counts().items():
            n += cnt * per_block[kind]
        n += d                                        # final norm
        if self.is_encoder_decoder:
            # encoder self-attn blocks + decoder cross-attn additions
            n += self.num_encoder_layers * (attn + mlp + 3 * d)
            n += self.num_layers * (attn + d)         # cross attention
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if not self.num_experts:
            return self.param_count()
        d = self.d_model
        gated = self.mlp_activation in ("swiglu", "geglu")
        e_ff = self.moe_d_ff or self.d_ff
        per_expert = d * e_ff * (3 if gated else 2)
        held = self.moe_held or self.num_experts
        inactive = (held - self.num_experts_per_tok * held // self.num_experts) * per_expert
        n_moe_blocks = sum(c for k, c in self.block_counts().items()
                           if k in ATTN_BLOCKS or (k == BLOCK_SSD and self.ssd_ffn))
        return self.param_count() - inactive * n_moe_blocks

    def fingerprint(self) -> str:
        blob = json.dumps(dataclasses.asdict(self), sort_keys=True, default=str)
        return hashlib.sha1(blob.encode()).hexdigest()[:12]


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One benchmark cell's input shape. ``mode`` selects the lowered fn."""

    name: str
    seq_len: int
    global_batch: int
    mode: str                        # "train" | "prefill" | "decode"

    def __post_init__(self):
        if self.mode not in ("train", "prefill", "decode"):
            raise ValueError(self.mode)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Runtime knobs for train/serve; defaults are the *baseline* used for
    the paper-faithful §Perf baselines — hillclimbs override these."""

    remat_policy: str = "full"       # full | dots | none
    grad_accum: int = 1
    loss_chunk: int = 0              # 0 = unchunked CE; >0 = seq-chunked remat CE
    attn_chunk: int = 0              # 0 = auto; kv-chunk for online-softmax attn
    gradient_compression: str = ""   # "" | "int8" (cross-pod, error feedback)
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    max_grad_norm: float = 1.0
    seed: int = 0
    param_dtype: str = "bfloat16"
    decode_kv_seq_shard: bool = True  # shard KV cache seq dim over model axis


# v5e-class roofline constants (per chip) used by benchmarks/roofline.py.
PEAK_BF16_FLOPS = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
