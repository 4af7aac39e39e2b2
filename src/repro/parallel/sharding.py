"""Logical-axis sharding: names -> mesh axes -> NamedSharding/constraints.

Model code never names physical mesh axes; it annotates arrays with logical
axis names ("act_batch", "p_heads", ...). A :class:`ShardEnv` resolves those
through a *rules* table onto whatever mesh is active, silently dropping
physical axes the mesh doesn't have (so the same model code runs on the
1-device CPU test mesh, the 256-chip single pod and the 512-chip pod pair).

Baseline parallelism (the §Perf baseline; hillclimbs edit rules):
  * FSDP: weight "p_embed"/"p_ff_in" dims over ``data``
  * TP:   heads / mlp hidden / vocab / experts over ``model``
  * DP:   activation batch over ``pod`` + ``data``
  * SP (decode): KV-cache sequence over ``model`` (flash-decode style
    partial-softmax combine is induced by GSPMD)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# Rule tables. Keys are logical axis names; values are physical mesh axes.
# ---------------------------------------------------------------------------
DEFAULT_RULES: Dict[str, Axes] = {
    # --- activations ---
    "act_batch": ("pod", "data"),
    "act_seq": None,
    "act_kv_seq": None,
    "act_heads": "model",
    "act_kv_heads": "model",
    "act_embed": None,
    "act_mlp": "model",
    "act_vocab": "model",
    "act_experts": "model",
    "act_inner": "model",       # ssm/rglru recurrent width
    # --- params ---
    "p_vocab": "model",
    "p_embed": "data",          # FSDP shard of the model dim
    "p_heads": "model",
    "p_mlp": "model",
    "p_experts": "model",       # EP (arctic)
    "p_expert_ff": None,        # per-expert ff; "model" in TP-expert mode
    "p_ff_in": "data",          # FSDP shard of FFN input dim
    "p_inner": "model",         # ssm/rglru inner width
    "p_state": None,
    "layers": None,
    "p_none": None,
    "pod_stack": "pod",         # leading per-pod dim (compression err state)
}

# Decode: batch stays on data, KV sequence sharded over model (SP); heads
# replicated (kv_heads < model size for every assigned arch).
DECODE_RULES: Dict[str, Axes] = {
    **DEFAULT_RULES,
    "act_heads": None,
    "act_kv_heads": None,
    "act_kv_seq": "model",
    "act_mlp": "model",
}

# long_500k: batch=1 -> nothing for data/pod to do on activations; spread the
# half-million-token KV across every chip.
LONG_DECODE_RULES: Dict[str, Axes] = {
    **DECODE_RULES,
    "act_batch": None,
    "act_kv_seq": ("pod", "data", "model"),
}

RULE_SETS = {
    "train": DEFAULT_RULES,
    "prefill": DEFAULT_RULES,
    "decode": DECODE_RULES,
    "long_decode": LONG_DECODE_RULES,
}


@dataclasses.dataclass(frozen=True)
class ShardEnv:
    """Mesh + logical rules, threaded through model code."""

    mesh: Mesh
    rules: Mapping[str, Axes]

    # -- resolution --------------------------------------------------------
    def _resolve(self, name: Optional[str]) -> Axes:
        if name is None:
            return None
        if name not in self.rules:
            raise KeyError(f"unknown logical axis {name!r}")
        axes = self.rules[name]
        if axes is None:
            return None
        if isinstance(axes, str):
            axes = (axes,)
        present = tuple(a for a in axes if a in self.mesh.axis_names)
        if not present:
            return None
        return present if len(present) > 1 else present[0]

    def _fit(self, axes: Axes, dim: int) -> Axes:
        """Drop trailing mesh axes until ``dim`` is divisible by the shard
        product (kv_heads=4 cannot shard 16 ways; vocab 49155 is odd; ...)."""
        if axes is None:
            return None
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        while tup:
            prod = 1
            for a in tup:
                prod *= self.mesh.shape[a]
            if dim % prod == 0:
                break
            tup = tup[:-1]
        if not tup:
            return None
        return tup if len(tup) > 1 else tup[0]

    def pspec(self, *logical: Optional[str], shape=None) -> P:
        axes = [self._resolve(n) for n in logical]
        if shape is not None:
            axes = [self._fit(a, d) for a, d in zip(axes, shape)]
        # a mesh axis may appear in at most one dimension: first one wins
        # (lets rule overrides like act_seq=model coexist with act_mlp=model)
        used: set = set()
        deduped = []
        for a in axes:
            tup = () if a is None else ((a,) if isinstance(a, str) else tuple(a))
            kept = tuple(x for x in tup if x not in used)
            used.update(kept)
            if not kept:
                deduped.append(None)
            elif len(kept) == 1:
                deduped.append(kept[0])
            else:
                deduped.append(kept)
        return P(*deduped)

    def sharding(self, *logical: Optional[str], shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.pspec(*logical, shape=shape))

    def constrain(self, x, *logical: Optional[str]):
        """with_sharding_constraint by logical names ('' / None = replicated dim)."""
        if self.mesh.empty or self.mesh.size == 1:
            return x
        names = [n if n else None for n in logical]
        return jax.lax.with_sharding_constraint(
            x, self.sharding(*names, shape=x.shape))

    # -- axis sizes ---------------------------------------------------------
    def axis_size(self, *axes: str) -> int:
        n = 1
        for a in axes:
            if a in self.mesh.axis_names:
                n *= self.mesh.shape[a]
        return n

    @property
    def tp(self) -> int:
        return self.axis_size("model")

    @property
    def dp(self) -> int:
        return self.axis_size("pod", "data")

    @property
    def fsdp(self) -> int:
        return self.axis_size("data")

    def with_rules(self, overrides: Mapping[str, Axes]) -> "ShardEnv":
        merged = dict(self.rules)
        merged.update(overrides)
        return dataclasses.replace(self, rules=merged)

    def without_axes(self, *axes: str) -> "ShardEnv":
        """Strip mesh axes from every rule — needed inside shard_map bodies
        that are Manual over those axes (constraints may only name Auto
        axes)."""
        drop = set(axes)

        def strip(v: Axes) -> Axes:
            if v is None:
                return None
            tup = (v,) if isinstance(v, str) else tuple(v)
            kept = tuple(a for a in tup if a not in drop)
            if not kept:
                return None
            return kept if len(kept) > 1 else kept[0]

        return dataclasses.replace(
            self, rules={k: strip(v) for k, v in self.rules.items()})


def make_env(mesh: Mesh, mode: str = "train",
             overrides: Sequence[Tuple[str, Axes]] = ()) -> ShardEnv:
    """The model places activations with ``with_sharding_constraint``, which
    only Auto mesh axes accept, so the env's mesh is always all-Auto, also
    when ``mesh`` comes from ``jax.make_mesh`` (whose axes are Explicit)."""
    if any(t != AxisType.Auto for t in mesh.axis_types):
        mesh = Mesh(mesh.devices, mesh.axis_names,
                    axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    rules = dict(RULE_SETS[mode])
    for k, v in overrides:
        rules[k] = v
    return ShardEnv(mesh=mesh, rules=rules)


def local_env(mode: str = "train") -> ShardEnv:
    """1-device env for CPU tests: constraints become no-ops, shard_map still
    runs (all axes size 1)."""
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(dev, ("data", "model"))
    return make_env(mesh, mode)


def is_spec_leaf(x) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def tree_shardings(env: ShardEnv, logical_tree, struct_tree=None) -> Any:
    """Map a pytree of logical-axis tuples to NamedShardings.

    With ``struct_tree`` (matching ShapeDtypeStructs/arrays), resolution is
    divisibility-aware per dimension — required for jit in_shardings, which
    reject uneven sharding."""
    if struct_tree is None:
        return jax.tree.map(lambda spec: env.sharding(*spec),
                            logical_tree, is_leaf=is_spec_leaf)

    flat_specs, treedef = jax.tree.flatten(logical_tree, is_leaf=is_spec_leaf)
    flat_structs = treedef.flatten_up_to(struct_tree)
    out = []
    for spec, st in zip(flat_specs, flat_structs):
        shape = getattr(st, "shape", ())
        if len(spec) != len(shape):
            spec = tuple(spec[:len(shape)]) + (None,) * max(
                0, len(shape) - len(spec))
        out.append(env.sharding(*spec, shape=shape))
    return treedef.unflatten(out)
