"""The hybrid serving driver (granite-4.0-h's stage) end to end on the CPU
at a tiny size, sound and with the timed path broken underneath; its
control; its weights; and its expert counter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.drivers import serve_hybrid

_REAL_PROGRAMS = serve_hybrid.programs
# a limit for the tiny hybrid cell, whose logits are divided by 16: between
# its sound readings (0 to 0.0004 on the CPU, seeds 1 to 8) and its
# control's (0.0015 to 0.0020); every fault reads above it
TINY_GAP_LIMIT = 0.0008


@pytest.fixture
def hybrid_gap_limit():
    return TINY_GAP_LIMIT


@pytest.fixture
def hybrid_spec():
    """granite-4.0-h's served stage at a tiny size: a Mamba-2, an attention
    and a Mamba-2 layer, 4 of 16 experts held here, top 4."""
    cfg = dict(harness.load_json(
        harness.HERE / "configs/granite-4.0-h-small-pp4ep4.json"),
        num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        attention_multiplier=1 / 16, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=16, intermediate_size=32, shared_intermediate_size=64,
        router_experts=16, num_local_experts=4, held_experts_start=4,
        num_experts_per_tok=4, vocab_size=512)
    mix = dict(harness.load_json(
        harness.HERE / "traffic/longgen-saturated.json"),
        slots=4, cache_len=64, rate_per_s=16.0, warm_slots=4,
        sample={"requests": 8},
        prompt_len={"kind": "lognormal", "median": 12, "sigma": 0.3,
                    "round_to": 8, "min": 8, "max": 16},
        output_len={"kind": "lognormal", "median": 16, "sigma": 0.6,
                    "min": 4, "max": 40})
    return {"cell": {"name": "serve.tiny-hybrid", "chips": 1},
            "config": cfg, "traffic": mix,
            "limits": {"widest_gap": TINY_GAP_LIMIT},
            "driver": harness.HERE / "drivers/serve_hybrid.py",
            "end_to_end": [], "per_layer": []}


def _run(spec, seed=2**33 + 11):
    return harness.execute(spec["cell"]["name"], seed, 1.0, False,
                           jax.devices()[:1], spec)


def test_sound_run_is_correct(hybrid_spec, hybrid_gap_limit):
    line = _run(hybrid_spec)
    assert line["correct"], line["checks"]
    assert line["checks"]["widest_gap"]["value"] < hybrid_gap_limit / 2
    assert line["attempted"] > 0


def _held_expert_dropped(params):
    """Every layer's first held expert contributes nothing."""
    stack = {b: dict(blk, moe=dict(blk["moe"], w_out=blk["moe"]["w_out"]
                                   .at[:, 0].set(0)))
             for b, blk in params["stack"].items()}
    return dict(params, stack=stack)


def _broken(fault):
    def programs(*a, **kw):
        fns = _REAL_PROGRAMS(*a, **kw)
        dec, ins, pre = fns["decode"], fns["insert"], fns["prefill"]
        if fault == "ssd_state_unchanged":
            def decode(p, tok, pos, cache):
                nt, new, n = dec(p, tok, pos, jax.tree.map(jnp.copy, cache))
                stack = {b: dict(new["stack"][b], **{
                    k: cache["stack"][b][k] for k in ("h", "conv")
                    if k in cache["stack"][b]}) for b in new["stack"]}
                return nt, dict(new, stack=stack), n
        elif fault == "held_expert_dropped":
            def decode(p, tok, pos, cache):
                return dec(_held_expert_dropped(p), tok, pos, cache)

            def prefill(p, tokens):
                return pre(_held_expert_dropped(p), tokens)
            fns["prefill"] = prefill
        elif fault == "token_altered":
            def decode(p, tok, pos, cache):
                nt, cache, n = dec(p, tok, pos, cache)
                return (nt + 1) % 512, cache, n
        elif fault == "wrong_slot":
            decode = dec

            def insert(bc, c, slot):
                return ins(bc, c, (slot + 1) % 4)
            fns["insert"] = insert
        decode.lower = dec.lower
        fns["decode"] = decode
        return fns
    return programs


@pytest.mark.parametrize("fault", ["ssd_state_unchanged",
                                   "held_expert_dropped", "token_altered",
                                   "wrong_slot"])
def test_fault_is_caught(hybrid_spec, monkeypatch, fault, hybrid_gap_limit):
    monkeypatch.setattr(serve_hybrid, "programs", _broken(fault))
    line = _run(hybrid_spec)
    assert not line["correct"]
    assert line["checks"]["widest_gap"]["value"] > hybrid_gap_limit


def test_control_fails(hybrid_spec, hybrid_gap_limit):
    """The control: the reference in float8 e4m3 in the program's place
    reads above the limit, where the program reads below it."""
    drv = harness.driver_module(hybrid_spec)
    st = drv.setup(hybrid_spec, 5, jax.devices()[:1], 1.0)
    drv.window(st, 1.0)
    drv.release(st)
    res = drv.compare(st, quants=(None, "fp8"))
    assert res["gaps"][None] < hybrid_gap_limit < res["gaps"]["fp8"]


def test_weights_layer_by_layer_match_the_stack(hybrid_spec):
    """The reference's weights, drawn one layer at a time, are the ones
    the system was given, bit for bit."""
    from chipbench.reference import hybrid_lm
    from repro.configs.base import RunConfig
    cfg = hybrid_spec["config"]
    key = harness.jax_key(3, 0)
    params = serve_hybrid.make_params(
        serve_hybrid.model_config(cfg), RunConfig(param_dtype="bfloat16"),
        key, cfg)
    for l, kind in enumerate(cfg["layer_types"]):
        w = jax.jit(lambda k, l: hybrid_lm.layer_weights(k, l, kind, cfg))(
            key, l)
        blk = params["stack"][f"b{l}"]
        for mine, theirs in ((w["w_out"], blk["moe"]["w_out"][0]),
                             (w["s_in"], blk["mlp"]["w_in"][0]),
                             (w["ln1"], blk["ln1"]["scale"][0])):
            np.testing.assert_array_equal(np.asarray(mine, np.float32),
                                          np.asarray(theirs, np.float32))
        mixer = (w["in_proj"], blk["ssd"]["w_in"][0]) if kind == "mamba" \
            else (w["wq"], blk["attn"]["wq"][0])
        np.testing.assert_array_equal(*(np.asarray(m, np.float32)
                                        for m in mixer))


def test_every_seed_gets_the_same_work(hybrid_spec):
    """The mix's lengths and gaps are the same for every seed; each answer
    ends where the cache does."""
    from chipbench import gen
    mix = hybrid_spec["traffic"]
    a = gen.serve_requests(mix, 2**33 + 1, 5.0)
    b = gen.serve_requests(mix, 12, 5.0)
    assert len(a) == len(b) == 80
    assert sorted((r["prompt_len"], r["output_len"]) for r in a) == \
        sorted((r["prompt_len"], r["output_len"]) for r in b)
    assert [r["due"] for r in a] != [r["due"] for r in b]
    assert all(r["prompt_len"] + r["output_len"] <= mix["cache_len"]
               for r in a)


def test_warm_slots_hold_full_slots_steady_state():
    """The warm requests are the cell's answers in flight with every slot
    full: drawn in proportion to their length, so their mean share still
    to come is about half the length-weighted mean answer, E[L^2]/(2 E[L]),
    and longer than the count-weighted warm requests of ``gen``. The seed
    only assigns them to slots; each ends where the cache does."""
    from chipbench import gen
    mix = harness.load_json(harness.HERE / "traffic/longgen-saturated.json")
    a = serve_hybrid.warm_requests(mix, 2**33 + 1)
    b = serve_hybrid.warm_requests(mix, 12)
    assert len(a) == mix["warm_slots"]
    assert [r["id"] for r in a] == [r["id"] for r in b]
    assert sorted((r["prompt_len"], r["output_len"]) for r in a) == \
        sorted((r["prompt_len"], r["output_len"]) for r in b)
    assert [r["output_len"] for r in a] != [r["output_len"] for r in b]
    _, lengths = gen._lengths(mix, 100_000)
    lengths = lengths.astype(np.float64)
    want = (lengths ** 2).mean() / (2 * lengths.mean())
    got = np.mean([r["output_len"] for r in a])
    assert abs(got - want) < 0.05 * want, (got, want)
    by_count = np.mean([r["output_len"] for r in gen.warm_requests(mix, 12)])
    assert got > 1.2 * by_count
    assert all(r["prompt_len"] + r["output_len"] <= mix["cache_len"]
               for r in a)


def test_the_counter_counts_each_step_s_assignments(hybrid_spec):
    """Each decode step returns, per layer, the routed assignments on each
    held expert: over all experts they would be slots x top-k, and the
    held share of them lies between none and all."""
    drv = harness.driver_module(hybrid_spec)
    st = drv.setup(hybrid_spec, 6, jax.devices()[:1], 0.5)
    drv.window(st, 0.5)
    cfg = hybrid_spec["config"]
    got = np.stack([np.asarray(n) for n in st["expert_counts"]])
    assert len(got) == len(st["steps"])
    assert got.shape[1:] == (cfg["num_hidden_layers"],
                             cfg["num_local_experts"])
    per_layer = got.sum(-1)
    assert (per_layer >= 0).all() and per_layer.max() > 0
    assert (per_layer <= hybrid_spec["traffic"]["slots"]
            * cfg["num_experts_per_tok"]).all()


def test_costs_match_hand_counts():
    """The stage: 9 Mamba-2 layers of 102,269,952 matrix weights (in_proj
    4096 x 16768, out_proj 8192 x 4096, conv 4 x 8448), one attention
    layer of 41,943,040 (q and o 4096 x 4096, k and v 4096 x 1024), and in
    each of the 10 layers 18 held experts of 3 x 4096 x 768, a shared
    expert of 3 x 4096 x 1536 and a float32 router of 4096 x 72."""
    from chipbench.costs import hybrid_lm as C
    cfg = harness.load_json(
        harness.HERE / "configs/granite-4.0-h-small-pp4ep4.json")
    assert C.mixer_params(cfg, "mamba") == 68_681_728 + 33_554_432 + 33_792
    assert C.mixer_params(cfg, "attention") == 33_554_432 + 8_388_608
    # a decode step of 96 slots at position 999 each (1000 keys): the
    # matrices and the tied head in bf16 (2,952,569,856 weights), the
    # router, norms and Mamba-2 scalars in f32 (3,112,320), 96 embedding
    # rows, the state (128 x 64 x 128 + 3 x 8448 floats a slot and layer)
    # read and written, and 4096 bytes of keys and values a key
    assert C.decode_bytes(cfg, 96, 96_000) == \
        5_905_139_712 + 12_449_280 + 786_432 + 7_422_935_040 \
        + 4096 * 96_096
    # 2 per weight touched (of the routed experts, 10 x 18 / 72 a token),
    # 4 per state element, attention's 4 x 32 x 128 a key, the head
    per_token = 9 * 102_269_952 + 41_943_040 + 10 * (
        294_912 + 18_874_368 + 2.5 * 9_437_184)
    assert C.decode_flops(cfg, 96, 96_000) == \
        2 * per_token * 96 + 4 * 9 * 128 * 64 * 128 * 96 \
        + 4 * 32 * 128 * 96_000 + 2 * 4096 * 25_088 * 96


def test_step_mfu_counts_the_traced_prefills_and_steps():
    """``serve.longgen.step_mfu``: the model FLOPs of the prompts admitted
    and the steps run since the trace began, over the device time of the
    prefill, insertion and decode programs and the bf16 peak."""
    from chipbench.costs import hybrid_lm as C
    cfg = harness.load_json(
        harness.HERE / "configs/granite-4.0-h-small-pp4ep4.json")
    modules = [("jit_chipbench_prefill", 1e8, 5e7),
               ("jit_chipbench_insert", 2e8, 1e6),
               ("jit_chipbench_decode", 3e8, 2.5e7),
               ("jit_chipbench_decode", 4e8, 2.5e7),
               ("jit_chipbench_mark", 5e8, 1e3)]
    events = {"_window": (0.0, 1e10, 0.0, False), "host": [],
              "devices": {"0": {"modules": modules, "ops": []}}}
    st = {"dims": cfg, "traced_from": (1, 1),
          "admitted": [(0.0, 512), (1.0, 1024)],
          "steps": [(0.0, 96, 1000), (1.0, 96, 96_000), (2.0, 90, 90_000)]}
    ctx = {"trace": {"events": events}, "state": st,
           "peaks": {"bf16_flops_per_s": 197e12}}
    # 256 tokens prefilled: 2 per weight touched, 4 per state element,
    # attention's 4 x 32 x 128 over 256 x 257 / 2 keys, one row of logits
    assert C.prefill_flops(cfg, 256) == C.forward_flops(
        cfg, 256, 256 * 257 // 2, 1)
    flops = (C.prefill_flops(cfg, 1024) + C.decode_flops(cfg, 96, 96_000)
             + C.decode_flops(cfg, 90, 90_000))
    got = harness.read_metric("serve.longgen.step_mfu", ctx)
    assert got == pytest.approx(100 * flops / (0.101 * 197e12))
    assert harness.read_metric("serve.longgen.step_mfu",
                               dict(ctx, trace=None)) is None
