"""The model's named scopes: every compiled instruction of the prefill and
decode programs carries the scopes of ``model.SCOPES`` that it lies in, in
their nesting, and no other scope."""
import contextlib
import re

import jax
import pytest

from repro.configs import reduced_config
from repro.configs.base import RunConfig, ShapeConfig
from repro.configs.granite_4_0_h_small import CONFIG as GRANITE_H
from repro.models import model as M
from repro.parallel.sharding import local_env

BLOCK_SCOPES = {"attn_qkv", "kv_write", "attend", "attn_out", "cross_attn",
                "mlp", "rglru", "ssd"}
ATTENTION = {"embed", "layers", "attn_qkv", "kv_write", "attend", "attn_out",
             "mlp", "head"}
CASES = {
    "dense": ("nemotron-4-15b", ATTENTION),
    "local-attention": ("gemma2-2b", ATTENTION),
    "rglru": ("recurrentgemma-9b", ATTENTION | {"rglru"}),
    "ssd": ("mamba2-2.7b", {"embed", "layers", "ssd", "ssd_state", "head"}),
    "cross-attention": ("seamless-m4t-medium", ATTENTION | {"cross_attn"}),
    "hybrid-moe": (GRANITE_H, ATTENTION | {"ssd", "ssd_state", "moe_route",
                                           "moe_experts", "moe_shared"}),
}


def _compiled(cfg, run, program):
    """HLO text of the compiled ``prefill`` or ``decode`` program."""
    env = local_env()
    params = M.param_shapes(cfg, run)
    if program == "prefill":
        def prefill(p, b):
            return M.prefill(env, cfg, p, b, run, max_len=48)
        spec = M.input_specs(cfg, ShapeConfig(name="p", seq_len=32,
                                              global_batch=2,
                                              mode="prefill"), run)
        return jax.jit(prefill).lower(params, spec).compile().as_text()

    def decode(p, t, n, kv):
        return M.decode_step(env, cfg, p, t, n, kv, run)
    d = M.input_specs(cfg, ShapeConfig(name="d", seq_len=48, global_batch=2,
                                       mode="decode"), run)
    return jax.jit(decode).lower(params, d["token"], d["pos"],
                                 d["cache"]).compile().as_text()


def _op_names(text):
    """The ``op_name`` of every instruction, but those of the reducers'
    bodies (``region_*``), which XLA names by the reduction alone."""
    names, region = [], False
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            region = head.group(1).startswith("region_")
        m = re.search(r'op_name="([^"]*)"', line)
        if m and not region:
            names.append(m.group(1))
    return names


def _segments(text):
    """(segments above the operation, operation names), with the names XLA
    joins when it merges instructions (``max;mul``) taken apart."""
    inner, ops = set(), set()
    for name in _op_names(text):
        *path, op = name.split("/")
        ops.update(op.split(";"))
        for seg in path:
            inner.update(seg.split(";"))
    return inner, ops


@pytest.fixture(scope="module", params=sorted(CASES))
def programs(request):
    """The case's config, the scopes it should show, and its prefill and
    decode programs compiled with the model's scopes and without them."""
    name, expected = CASES[request.param]
    cfg = reduced_config(name)
    run = RunConfig(remat_policy="none", param_dtype="float32")
    scoped = {p: _compiled(cfg, run, p) for p in ("prefill", "decode")}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        bare = {p: _compiled(cfg, run, p) for p in ("prefill", "decode")}
    return expected, scoped, bare


def test_scopes_lie_under_the_program_in_their_nesting(programs):
    _, scoped, _ = programs
    for program, text in scoped.items():
        for name in _op_names(text):
            segs = name.split("/")[:-1]
            found = [s for s in segs if s in M.SCOPES]
            if not found:
                continue
            assert segs[0] == f"jit({program})", name
            blocks = [s for s in found if s in BLOCK_SCOPES]
            assert len(blocks) <= 1, name
            if blocks:
                assert "layers" in found, name
                assert found.index("layers") < found.index(blocks[0]), name
            assert not ("head" in found and "layers" in found), name


def test_every_scope_of_the_family_appears(programs):
    expected, scoped, _ = programs
    for program, text in scoped.items():
        seen = {s for name in _op_names(text)
                for s in name.split("/")[:-1] if s in M.SCOPES}
        assert seen == expected, program


def test_no_scope_outside_the_tuple(programs):
    _, scoped, bare = programs
    for program in ("decode", "prefill"):
        inner, ops = _segments(scoped[program])
        bare_inner, bare_ops = _segments(bare[program])
        added = inner - bare_inner - bare_ops - ops
        assert added <= set(M.SCOPES), (program, added - set(M.SCOPES))
        assert not bare_inner & set(M.SCOPES)


def test_scopes_change_only_metadata(programs):
    _, scoped, bare = programs

    def strip(text):
        # the computations, with neither metadata nor names
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        text = re.sub(r"%[\w.\-]+|[\w.\-]+(?=: )", "x", text)
        return [ln for ln in text.splitlines()
                if ln.startswith(("x", "ENTRY", " ", "}"))]
    for program in ("decode", "prefill"):
        assert strip(scoped[program]) == strip(bare[program]), program


def _instructions(text):
    """(opcode, result dims, op_name) of every instruction that has an
    array result."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            yield (m.group(2), tuple(int(d) for d in m.group(1).split(",")
                                     if d), name.group(1) if name else "")


def _stacked_cache(text):
    """{leaf key: dims} of the decode program's stacked cache arguments,
    named by their path in the cache tree."""
    leaves = {}
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* parameter\(\d+\).*op_name="
                      r"\"kv\[\\'stack\\'\]\[\\'b\d+\\'\]\[\\'(\w+)\\'\]",
                      line)
        if m:
            leaves.setdefault(m.group(2), set()).add(
                tuple(int(d) for d in m.group(1).split(",")))
    return leaves


def test_decode_carries_the_cache_and_writes_it_in_place(programs):
    """The decode program reads each layer's cache out of the stacked cache
    under ``layers`` and no block scope, writes a new key and value at its
    point in the stacked cache under ``kv_write``, rewrites a recurrent
    state at its layer under ``layers`` (an SSD's under ``ssd_state``, with
    the state update fused into the write), and writes no whole layer of
    keys or values back."""
    expected, scoped, _ = programs
    text = scoped["decode"]
    leaves = _stacked_cache(text)
    assert leaves
    ops = list(_instructions(text))

    def scopes(name):
        return [s for s in name.split("/")[:-1] if s in M.SCOPES]

    for key, shapes in leaves.items():
        for dims in shapes:
            reads = [n for op, d, n in ops
                     if op == "dynamic-slice" and d == (1,) + dims[1:]]
            assert reads, (key, dims)
            assert all(scopes(n) == ["layers"] for n in reads), (key, reads)
            writes = [(op, n) for op, d, n in ops if d == dims and op in (
                "scatter", "dynamic-update-slice")]
            if key in ("k", "v"):
                assert writes, key
                assert all(op == "scatter" and scopes(n) == [
                    "layers", "kv_write"] for op, n in writes), (key, writes)
            elif key in ("h", "conv"):
                assert writes, key
                state = (["layers", "ssd", "ssd_state"] if "ssd" in expected
                         else ["layers"])
                assert all(op == "dynamic-update-slice"
                           and scopes(n) == state
                           for op, n in writes), (key, writes)
