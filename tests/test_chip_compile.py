"""Compile the main-path kernels and the gemma2-2b decode step for a TPU v5e
chip that is described, not attached: the TPU compiler refuses here what it
would refuse on the chip (unaligned blocks, SMEM layouts, memory that does
not fit), at no chip time. Nothing runs, so nothing here checks a value.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
import json
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.kernels.ciao_gather.ops import ciao_gather
from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.flash_attn.ops import flash_attention
from repro.models import model as M
from repro.parallel.sharding import local_env

HQ, HKV, D, CAP = 8, 4, 256, 50.0       # gemma2-2b attention widths
ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture(scope="module")
def topo():
    pytest.importorskip("libtpu", reason="the TPU compiler is not installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_compiles_for_v5e(one_chip):
    c = _compile(one_chip,
                 lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 softcap=CAP),
                 ((2, 2048, HQ, D), jnp.bfloat16),
                 ((2, 2048, HKV, D), jnp.bfloat16),
                 ((2, 2048, HKV, D), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_decode_attention_compiles_for_v5e(one_chip):
    c = _compile(one_chip,
                 lambda q, k, v, n: decode_attention(q, k, v, n,
                                                     softcap=CAP),
                 ((8, 1, HQ, D), jnp.bfloat16),
                 ((8, 8192, HKV, D), jnp.bfloat16),
                 ((8, 8192, HKV, D), jnp.bfloat16),
                 ((8,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ciao_gather_compiles_for_v5e(one_chip, dtype):
    c = _compile(one_chip, ciao_gather,
                 ((262144, 256), dtype), ((65536,), jnp.int32),
                 ((65536,), jnp.int32), ((4,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_gemma2_decode_step_compiles_for_v5e(one_chip):
    cfg = get_config("gemma2-2b")
    run = RunConfig(remat_policy="none", param_dtype="bfloat16")
    env = local_env()

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(M.param_shapes(cfg, run))
    cache = on_chip(M.cache_struct(cfg, 8, 2048))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    c = jax.jit(lambda p, t, n, kv: M.decode_step(env, cfg, p, t, n, kv,
                                                  run),
                donate_argnums=3).lower(params, tok, pos, cache).compile()
    mem = c.memory_analysis()
    # full-width weights (5.2 GB) and cache must fit one 16 GB chip
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 5e9 < mem.argument_size_in_bytes and used < 16e9


def test_stage_decode_step_writes_the_cache_in_place_for_v5e(one_chip):
    """nemotron-4-15b's served stage (chipbench) at 48 slots of 2048, the
    cache donated: the decode step keeps no second cache as a temporary,
    and no copy or dynamic-update-slice writes the whole stacked cache."""
    from chipbench.drivers.serve import model_config
    cfg = model_config(json.loads(
        (ROOT / "chipbench/configs/nemotron-4-15b-pp4stage.json").read_text()))
    run = RunConfig(remat_policy="none", param_dtype="bfloat16")
    env = local_env()

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    cache = on_chip(M.cache_struct(cfg, 48, 2048))
    tok = jax.ShapeDtypeStruct((48, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((48,), jnp.int32, sharding=one_chip)
    c = jax.jit(lambda p, t, n, kv: M.decode_step(env, cfg, p, t, n, kv,
                                                  run),
                donate_argnums=3).lower(on_chip(M.param_shapes(cfg, run)),
                                        tok, pos, cache).compile()
    assert c.memory_analysis().temp_size_in_bytes < 0.5e9
    whole = {",".join(map(str, leaf.shape)) for leaf in jax.tree.leaves(cache)}
    assert whole == {"8,48,2048,8,128"}
    for line in c.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                     r"(copy|dynamic-update-slice)\(", line)
        assert not (m and m.group(1) in whole), line


def test_stage_decode_step_reads_each_layer_in_place_for_v5e(one_chip,
                                                            monkeypatch):
    """nemotron-4-15b's served stage at 48 slots of 2048 with the
    ``decode_attn`` kernel, as the model takes it on one chip: the kernel
    is in the program; no instruction outside it has one layer's keys or
    values as its result; and no copy or dynamic-update-slice writes the
    whole stacked cache, so nothing shields the kernel's read from the
    next layer's in-place write."""
    from chipbench.drivers.serve import model_config
    monkeypatch.setattr(M, "_in_place_attention", lambda env: decode_attention)
    cfg = model_config(json.loads(
        (ROOT / "chipbench/configs/nemotron-4-15b-pp4stage.json").read_text()))
    run = RunConfig(remat_policy="none", param_dtype="bfloat16")
    env = local_env()

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    cache = on_chip(M.cache_struct(cfg, 48, 2048))
    tok = jax.ShapeDtypeStruct((48, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((48,), jnp.int32, sharding=one_chip)
    c = jax.jit(lambda p, t, n, kv: M.decode_step(env, cfg, p, t, n, kv,
                                                  run),
                donate_argnums=3).lower(on_chip(M.param_shapes(cfg, run)),
                                        tok, pos, cache).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert c.memory_analysis().temp_size_in_bytes < 0.5e9
    one_layer = {"1,48,2048,8,128", "48,2048,8,128"}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if m:
            assert m.group(1) not in one_layer, line
            assert not (m.group(1) == "8,48,2048,8,128" and m.group(2) in (
                "copy", "copy-start", "dynamic-update-slice")), line


def test_granite_stage_decode_and_longest_prefill_fit_v5e(one_chip):
    """granite-4.0-h-small's served stage (chipbench) at 96 slots of 4096:
    the decode step, its cache donated, keeps no second cache as a
    temporary (the Mamba-2 state is rewritten in place at its layer), and
    the weights, the cache and the 1792-token prefill's temporaries fit one
    16 GB chip together."""
    from chipbench.drivers import serve_hybrid
    cfg = serve_hybrid.model_config(json.loads(
        (ROOT / "chipbench/configs/granite-4.0-h-small-pp4ep4.json")
        .read_text()))
    run = RunConfig(remat_policy="none", param_dtype="bfloat16")
    fns = serve_hybrid.programs(cfg, run, 4096, jnp.bfloat16)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(M.param_shapes(cfg, run))
    cache = on_chip(M.cache_struct(cfg, 96, 4096))
    dec = fns["decode"].lower(
        params, jax.ShapeDtypeStruct((96, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((96,), jnp.int32, sharding=one_chip),
        cache).compile().memory_analysis()
    pre = fns["prefill"].lower(
        params, jax.ShapeDtypeStruct((1, 1792), jnp.int32,
                                     sharding=one_chip)).compile()
    pre = pre.memory_analysis()
    # 2.96 B parameters in bf16 and a 5.32 GB cache
    assert 11.2e9 < dec.argument_size_in_bytes < 11.3e9
    assert dec.temp_size_in_bytes < 0.5e9
    # the whole cache, the donated argument, is the output's buffer
    assert dec.alias_size_in_bytes == sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache))
    assert dec.argument_size_in_bytes + pre.temp_size_in_bytes \
        + pre.output_size_in_bytes < 14e9
