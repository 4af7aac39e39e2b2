"""Jitted JAX backend for the batched lockstep engine.

This is the third stepper over the same stacked state the numpy and C
steppers drive (:mod:`repro.core.batched`): the whole batch state is a
**pytree of int64/bool arrays** with a leading batch axis, one
lockstep iteration (one scheduler dispatch per live row, the full
per-access chain, plus the epoch / warp-retirement / timeline servicing
the C stepper runs in-stepper) is a **pure function** ``state -> state``,
and a run is ``jax.jit(lax.while_loop(any_live, iteration, state))``.
Rare per-dispatch events — epoch boundaries, warp retirement, timeline
samples, fully-throttled stretches — are gated with ``lax.cond`` on
batch-level "any row flagged" predicates, so the common iteration skips
their sort/scatter kernels entirely.

**Bit-exactness contract.** Every arithmetic step mirrors the numpy
stepper elementwise under the fixed-point rules of
:mod:`repro.core.epoch`: all counters are int64 (x64 mode is enabled in
a scope around trace and execution — never globally), every cutoff or
threshold decision is the int64 compare ``hits*act*den <> num*win``
against a ``(num, den)`` knob, sorts are stable, and arg-reductions
break ties on the first index exactly like numpy. No float64 reaches
the device, whose float64 may not be IEEE double (TPU v5e emulates it
with a pair of float32): timeline IPC samples leave it as integer
pairs and are divided on the host. ``tests/test_batched.py``
and ``tests/test_jax_backend.py`` pin golden cells and mixed batches
bit-for-bit across all three steppers.

**Gating.** The backend takes single-SM batches (``gpu is None`` — the
post-L1 planes are then private per row, so no cross-row phase
interleaving is needed) whose rows all map to the known policy /
warp-done families (no ``F_OBJECT``/``WD_OBJECT`` object fallbacks —
those need per-cell Python). :func:`supports_engine` is the predicate;
``BatchedSMEngine.run`` with ``backend="jax"`` raises when it does not
hold, and ``runner.run_grid(engine="jax")`` routes only eligible cells
here (the rest fall back to the batched/process paths).

The jit cache is keyed on the static config tuple — shape-affecting
fields only. Scalar knobs that vary within sweeps (latencies, epoch
cutoffs, cycle caps) ride as per-row ``(B,)`` leaves of the ``consts``
pytree, so heterogeneous hyperparameter batches share one compiled
program instead of fragmenting the cache per config. Changing batch
width, warp count or stream length retraces through jax's own
shape-keyed cache. The batch axis is the explicit leading axis of every
leaf, so the compiled step is also ``vmap``-able over an outer grid
axis. Results are written back into the engine's numpy arrays and the
standard ``BatchedSMEngine._finalize`` assembles ``SimResult``s, so
downstream aggregation is shared with the other steppers.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple

import numpy as np

try:                                   # gate, never a hard dependency
    import jax
    import jax.numpy as jnp
    from jax import lax
    _IMPORT_ERROR = None
except Exception as exc:               # pragma: no cover - env without jax
    jax = None
    jnp = None
    lax = None
    _IMPORT_ERROR = exc

from repro.core.batched import (F_CCWS, F_CIAO, F_OBJECT, F_STATP,
                                WD_OBJECT, WD_STATP, WD_SWL)
from repro.core.epoch import _DEAD_KEY, NO_WARP
from repro.core.policies import CCWSPolicy
from repro.core.simulator import _HUGE
from repro.workloads import tokens as _tokens

_SHIFT = _tokens.TOKEN_LINE_SHIFT


def available() -> bool:
    """True when jax imports (the backend is usable)."""
    return jax is not None


def unavailable_reason() -> str:
    return "" if jax is not None else f"jax import failed: {_IMPORT_ERROR}"


def supports_engine(eng) -> str:
    """Empty string when the engine can run on the jax backend, else the
    human-readable reason it cannot."""
    if jax is None:
        return unavailable_reason()
    if eng.gpu is not None:
        return "multi-SM batches are not jax-able yet (shared post-L1 " \
               "planes need phase interleaving); use backend='auto'"
    if (eng.fam == F_OBJECT).any() or (eng.wd_kind == WD_OBJECT).any():
        return "batch contains custom policy objects (F_OBJECT/" \
               "WD_OBJECT rows need per-cell Python)"
    return ""


class _Static(NamedTuple):
    """Hashable static config: the jit cache key (together with jax's
    own shape/dtype keying of the traced arrays)."""
    n: int
    L: int
    P: int
    l1_sets: int
    l1_ways: int
    xor_hash: bool
    reuse_filter: bool
    nrb: int
    v_sets: int
    v_k: int
    nw: int
    le: int
    sat_max: int
    l2_sets: int
    l2_ways: int
    dram_channels: int
    max_mlp: int
    timeline_every: int
    tl_cap: int


def _static_of(eng) -> _Static:
    return _Static(
        n=eng.n_warps, L=eng.L, P=eng.P,
        l1_sets=eng.l1_sets, l1_ways=eng.l1_ways,
        xor_hash=bool(eng.xor_hash), reuse_filter=bool(eng.reuse_filter),
        nrb=eng.nrb, v_sets=eng.v_sets, v_k=eng.v_k,
        nw=eng.nw, le=eng.list_entries, sat_max=eng.sat_max,
        l2_sets=eng.l2_sets, l2_ways=eng.l2_ways,
        dram_channels=eng.dram_channels,
        max_mlp=eng.max_mlp,
        timeline_every=eng.timeline_every, tl_cap=eng.tl_cap)


# mutable state: (engine attribute, state key); det planes/consts below
_STATE_ATTRS = (
    "ready", "done", "avail", "iso", "byp", "op_idx", "pend",
    "cycle", "instr", "li", "irs_off", "last_wid", "window_mark",
    "last_instr", "last_cycle", "tick",
    "l1_tags", "l1_owners", "l1_reused", "l1_stamp",
    "smem_tags", "smem_owner",
    "v_addr", "v_evic", "v_head", "v_count", "v_inserts",
    "l2_tags", "l2_stamp", "l2_tick", "l2_hits", "l2_misses",
    "dram_free", "dram_requests", "cnt_dram_reqs",
    "cnt_l1_hit", "cnt_l1_miss", "cnt_smem_hit", "cnt_smem_miss",
    "cnt_smem_migrate", "cnt_bypass", "cnt_evictions",
    "cnt_smem_evictions", "cnt_vta_hits", "vta_hit_events",
    "pair_dense", "next_epoch", "remaining",
    "allowed_pl", "isolated_pl", "bypass_pl", "score_pl",
    "sp_bypass", "sp_base", "swl_next",
    "ciao_stall", "ciao_iso", "stall_len", "iso_len",
    "tl_cycle", "tl_act", "tl_n",
)
# detector planes stacked in the state with a d_ prefix
_DET_FIELDS = (
    "inst_total", "irs_inst", "low_idx", "high_idx",
    "low_base_inst", "high_base_inst", "high_crossings",
    "irs_hits", "low_base_hits", "high_base_hits",
    "low_snap_hits", "high_snap_hits", "low_snap_win", "high_snap_win",
    "low_snap_act", "high_snap_act",
    "vta_hits", "interfering", "sat", "pair_list",
)


def _arrays_of(eng):
    """(state, consts) pytrees as numpy arrays; jit converts on entry."""
    state = {k: getattr(eng, k) for k in _STATE_ATTRS}
    for f in _DET_FIELDS:
        state["d_" + f] = getattr(eng.det_pl, f)
    # timeline IPC samples leave the device as integer (instructions,
    # cycles) pairs and are divided on the host (_write_back)
    state["tl_dins"] = np.zeros_like(eng.tl_cycle)
    state["tl_dcyc"] = np.zeros_like(eng.tl_cycle)
    bump = np.zeros(eng.B, np.int64)
    for b, pol in enumerate(eng.policies):
        if isinstance(pol, CCWSPolicy):
            bump[b] = pol.bump
    consts = {
        "toks": eng.toks, "u_of": eng.u_of, "n_ops": eng.n_ops,
        "region_blocks": eng.region_blocks,
        "fam": eng.fam.astype(np.int64), "wd_kind": eng.wd_kind,
        "mode_p": eng.mode_p, "mode_t": eng.mode_t,
        "ccws_base": eng.ccws_base, "ccws_budget": eng.ccws_budget,
        "sp_thresh": eng.sp_thresh, "bump": bump,
        # per-row config planes: knobs that vary within a shape class
        # ride as (B,) consts so heterogeneous sweeps share one compile
        "lat_l1": eng.lat_l1, "lat_smem": eng.lat_smem,
        "lat_migrate": eng.lat_migrate, "lat_l2": eng.lat_l2,
        "lat_dram": eng.lat_dram, "dram_gap": eng.dram_gap,
        "max_cycles": eng.max_cycles,
        "low_epoch": eng.low_epoch, "high_epoch": eng.high_epoch,
        "stride_ok": eng._stride_ok,
        "aging": eng.det_pl.aging_high,
        "low_cutoff": eng.det_pl.low_cutoff,
        "high_cutoff": eng.det_pl.high_cutoff,
    }
    return state, consts


def _write_back(eng, out) -> None:
    for k in _STATE_ATTRS:
        np.copyto(getattr(eng, k), np.asarray(out[k]))
    for f in _DET_FIELDS:
        np.copyto(getattr(eng.det_pl, f), np.asarray(out["d_" + f]))
    dcyc = np.asarray(out["tl_dcyc"])
    new = dcyc > 0                     # samples taken in this run
    eng.tl_dipc[new] = np.asarray(out["tl_dins"])[new] / dcyc[new]


# -------------------------------------------------------------- kernels
# Everything below is a transliteration of BatchedSMEngine._np_iteration
# / _np_mem_chain / _epoch_batch / _warp_done_rows / _timeline_rows to
# jnp: boolean-subset scatters become `.at[rows, cols].set(where(mask,
# new, old))` full-width masked scatters (one target slot per row, so
# they never collide), and per-cell fallbacks (the VTA FIFO pop) are
# vectorized over the logical window.

def _gated(st, mask, fn, *extra):
    """Run ``fn(st, mask, *extra)`` only when any row is flagged."""
    return lax.cond(mask.any(),
                    lambda op: fn(*op),
                    lambda op: op[0],
                    (st, mask) + extra)


def _ccws_tick(S, cst, st, m):
    arB = jnp.arange(st["cycle"].shape[0])
    s0 = st["score_pl"]
    s = s0 - jnp.maximum(1, s0 // 8)
    s = jnp.maximum(s, cst["ccws_base"][:, None])
    score = jnp.where(m[:, None], s, s0)
    alive = ~st["done"]
    key = jnp.where(alive, -s, _DEAD_KEY)
    order = jnp.argsort(key, axis=1, stable=True)
    s_sorted = jnp.take_along_axis(s, order, 1)
    a_sorted = jnp.take_along_axis(alive, order, 1)
    csum = jnp.cumsum(jnp.where(a_sorted, s_sorted, 0), axis=1)
    blk = a_sorted & (csum > cst["ccws_budget"][:, None])
    blk = blk.at[:, 0].set(False)      # the top-score warp always runs
    blocked = jnp.zeros_like(blk).at[arB[:, None], order].set(blk)
    st = dict(st)
    st["score_pl"] = score
    st["allowed_pl"] = jnp.where(m[:, None], ~blocked, st["allowed_pl"])
    return st


def _statp_tick(S, cst, st, m):
    # epoch.util_below; single-SM: the chip-wide request counter is the
    # row's own
    cc = S.dram_channels * st["cycle"]
    num, den = cst["sp_thresh"][:, 0], cst["sp_thresh"][:, 1]
    busy = jnp.minimum(st["dram_requests"] * cst["dram_gap"], cc)
    new = jnp.where(cc > 0, busy * den < num * cc, num > 0)
    ch = m & (new != st["sp_bypass"])
    bm = st["sp_base"]
    st = dict(st)
    st["sp_bypass"] = jnp.where(ch, new, st["sp_bypass"])
    st["allowed_pl"] = jnp.where(ch[:, None],
                                 new[:, None] | bm, st["allowed_pl"])
    st["bypass_pl"] = jnp.where(ch[:, None],
                                new[:, None] & ~bm, st["bypass_pl"])
    return st


def _irs_cum_leq(S, cst, st, wid, act):
    """Integer cumulative-IRS cutoff (epoch.irs_cum_leq)."""
    arB = jnp.arange(st["cycle"].shape[0])
    inst = st["d_irs_inst"]
    hits = st["d_irs_hits"][arB, wid % S.nw]
    bad = (inst <= 0) | (act <= 0)
    cut = cst["low_cutoff"]
    return bad | (hits * act * cut[:, 1] <= cut[:, 0] * inst)


def _ciao_low(S, cst, st, m, act):
    """epoch.ciao_low_tick: pop at most one stalled and one isolated
    warp per flagged cell, newest first."""
    arB = jnp.arange(st["cycle"].shape[0])
    le = S.le
    st = dict(st)
    sl = st["stall_len"]
    has = m & (sl > 0)
    top = st["ciao_stall"][arB, jnp.maximum(sl - 1, 0)]
    topc = jnp.where(has, top, 0)
    k1 = st["d_pair_list"][arB, topc % le, 1]
    kc = jnp.where(k1 >= 0, k1, 0)
    pop = has & ((k1 == NO_WARP) | st["done"][arB, kc]
                 | _irs_cum_leq(S, cst, st, kc, act))
    st["stall_len"] = sl - pop
    st["allowed_pl"] = st["allowed_pl"].at[arB, topc].set(
        st["allowed_pl"][arB, topc] | pop)
    st["d_pair_list"] = st["d_pair_list"].at[arB, topc % le, 1].set(
        jnp.where(pop, NO_WARP, st["d_pair_list"][arB, topc % le, 1]))
    # isolated pops read `allowed` after the stall pops (scalar order)
    il = st["iso_len"]
    hasi = m & (il > 0)
    topi = st["ciao_iso"][arB, jnp.maximum(il - 1, 0)]
    tic = jnp.where(hasi, topi, 0)
    ok = hasi & st["allowed_pl"][arB, tic]
    k2 = st["d_pair_list"][arB, tic % le, 0]
    k2c = jnp.where(k2 >= 0, k2, 0)
    pop2 = ok & ((k2 == NO_WARP) | st["done"][arB, k2c]
                 | _irs_cum_leq(S, cst, st, k2c, act))
    st["iso_len"] = il - pop2
    st["isolated_pl"] = st["isolated_pl"].at[arB, tic].set(
        st["isolated_pl"][arB, tic] & ~pop2)
    st["d_pair_list"] = st["d_pair_list"].at[arB, tic % le, 0].set(
        jnp.where(pop2, NO_WARP, st["d_pair_list"][arB, tic % le, 0]))
    return st


def _ciao_high(S, cst, st, m):
    """epoch.ciao_high_tick: the batched descending-IRS walk and the one
    isolate/stall action per flagged cell."""
    B = st["cycle"].shape[0]
    n, le = S.n, S.le
    arB = jnp.arange(B)
    st = dict(st)
    alive = st["allowed_pl"] & ~st["done"]
    act = st["d_high_snap_act"][:, None]
    win = st["d_high_snap_win"][:, None]
    hits = st["d_high_snap_hits"][:, np.arange(n) % S.nw]
    cut = cst["high_cutoff"][:, None]
    over = hits * act * cut[..., 1] > cut[..., 0] * win
    cand = m[:, None] & alive & over \
        & (jnp.sum(alive, axis=1) > 1)[:, None]
    order = jnp.argsort(jnp.where(cand, -hits, _DEAD_KEY), axis=1,
                        stable=True)
    cand_s = jnp.take_along_axis(cand, order, 1)
    j = st["d_interfering"][arB[:, None], order % le]
    jc = jnp.where(j >= 0, j, 0)
    valid = cand_s & (j != NO_WARP) & (j != order) \
        & ~st["done"][arB[:, None], jc]
    iso_j = st["isolated_pl"][arB[:, None], jc]
    alw_j = st["allowed_pl"][arB[:, None], jc]
    mp = cst["mode_p"][:, None]
    mt = cst["mode_t"][:, None]
    p_ok = valid & mp & ~iso_j & alw_j
    t_ok = valid & mt & alw_j & (iso_j | ~mp)
    hit = p_ok | t_ok
    changed = hit.any(axis=1)
    pos = jnp.argmax(hit, axis=1)           # first actionable walk pos
    take_p = changed & p_ok[arB, pos]
    take_t = changed & ~take_p
    jj = jnp.where(changed, j[arB, pos], 0)     # the victim warp
    ii = order[arB, pos]                        # the interferer
    ilc = jnp.minimum(st["iso_len"], n - 1)
    st["isolated_pl"] = st["isolated_pl"].at[arB, jj].set(
        st["isolated_pl"][arB, jj] | take_p)
    st["d_pair_list"] = st["d_pair_list"].at[arB, jj % le, 0].set(
        jnp.where(take_p, ii, st["d_pair_list"][arB, jj % le, 0]))
    st["ciao_iso"] = st["ciao_iso"].at[arB, ilc].set(
        jnp.where(take_p, jj, st["ciao_iso"][arB, ilc]))
    st["iso_len"] = st["iso_len"] + take_p
    slc = jnp.minimum(st["stall_len"], n - 1)
    st["allowed_pl"] = st["allowed_pl"].at[arB, jj].set(
        st["allowed_pl"][arB, jj] & ~take_t)
    st["d_pair_list"] = st["d_pair_list"].at[arB, jj % le, 1].set(
        jnp.where(take_t, ii, st["d_pair_list"][arB, jj % le, 1]))
    st["ciao_stall"] = st["ciao_stall"].at[arB, slc].set(
        jnp.where(take_t, jj, st["ciao_stall"][arB, slc]))
    st["stall_len"] = st["stall_len"] + take_t
    return st


def _ciao_tick(S, cst, st, m):
    """epoch.poll_epochs (snapshots + aging) then the low/high ticks."""
    arB = jnp.arange(st["cycle"].shape[0])
    st = dict(st)
    n_act = jnp.maximum(
        jnp.sum(st["allowed_pl"] & ~st["done"], axis=1), 1)
    ws = np.arange(S.nw) % S.v_sets             # wid -> vta set (static)
    it = st["d_inst_total"]
    cur = st["d_vta_hits"][:, ws]
    lo, hi = cst["low_epoch"], cst["high_epoch"]
    lowm = m & ((it // lo) != st["d_low_idx"])
    win = jnp.maximum(it - st["d_low_base_inst"], 1)
    st["d_low_idx"] = jnp.where(lowm, it // lo, st["d_low_idx"])
    st["d_low_snap_hits"] = jnp.where(
        lowm[:, None], cur - st["d_low_base_hits"], st["d_low_snap_hits"])
    st["d_low_snap_win"] = jnp.where(lowm, win, st["d_low_snap_win"])
    st["d_low_snap_act"] = jnp.where(lowm, n_act, st["d_low_snap_act"])
    st["d_low_base_hits"] = jnp.where(lowm[:, None], cur,
                                      st["d_low_base_hits"])
    st["d_low_base_inst"] = jnp.where(lowm, it, st["d_low_base_inst"])
    highm = m & ((it // hi) != st["d_high_idx"])
    winh = jnp.maximum(it - st["d_high_base_inst"], 1)
    st["d_high_idx"] = jnp.where(highm, it // hi,
                                 st["d_high_idx"])
    st["d_high_snap_hits"] = jnp.where(
        highm[:, None], cur - st["d_high_base_hits"],
        st["d_high_snap_hits"])
    st["d_high_snap_win"] = jnp.where(highm, winh, st["d_high_snap_win"])
    st["d_high_snap_act"] = jnp.where(highm, n_act,
                                      st["d_high_snap_act"])
    st["d_high_base_hits"] = jnp.where(highm[:, None], cur,
                                       st["d_high_base_hits"])
    st["d_high_base_inst"] = jnp.where(highm, it,
                                       st["d_high_base_inst"])
    st["d_high_crossings"] = st["d_high_crossings"] + highm
    ag = cst["aging"]
    aged = highm & (ag > 0) \
        & (st["d_high_crossings"] % jnp.maximum(ag, 1) == 0)
    st["d_irs_inst"] = jnp.where(aged, st["d_irs_inst"] // 2,
                                 st["d_irs_inst"])
    st["d_irs_hits"] = jnp.where(aged[:, None],
                                 st["d_irs_hits"] // 2,
                                 st["d_irs_hits"])
    st = _gated(st, lowm,
                lambda s, mm, a: _ciao_low(S, cst, s, mm, a), n_act)
    st = _gated(st, highm, lambda s, mm: _ciao_high(S, cst, s, mm))
    del arB
    return st


def _epoch_service(S, cst, st, mask, anchor):
    """BatchedSMEngine._epoch_batch: snapshot the IRS denominators, run
    the family ticks, refresh the dispatch masks, advance the anchors."""
    st = dict(st)
    li = st["li"]
    fam = cst["fam"]
    st["d_inst_total"] = jnp.where(mask, li, st["d_inst_total"])
    st["d_irs_inst"] = jnp.where(mask, li - st["irs_off"],
                                 st["d_irs_inst"])
    st = _gated(st, mask & (fam == F_CCWS),
                lambda s, mm: _ccws_tick(S, cst, s, mm))
    st = _gated(st, mask & (fam == F_STATP),
                lambda s, mm: _statp_tick(S, cst, s, mm))
    st = _gated(st, mask & (fam == F_CIAO),
                lambda s, mm: _ciao_tick(S, cst, s, mm))
    st["irs_off"] = jnp.where(mask, li - st["d_irs_inst"],
                              st["irs_off"])             # aging moves it
    st["avail"] = jnp.where(mask[:, None],
                            st["allowed_pl"] & ~st["done"], st["avail"])
    st["iso"] = jnp.where(mask[:, None], st["isolated_pl"], st["iso"])
    st["byp"] = jnp.where(mask[:, None], st["bypass_pl"], st["byp"])
    lo, hi = cst["low_epoch"], cst["high_epoch"]
    nxt = (li // lo + 1) * lo
    skip = cst["stride_ok"] & (fam == F_CIAO) \
        & (st["stall_len"] + st["iso_len"] == 0)
    nxt = jnp.where(skip, (li // hi + 1) * hi, nxt)
    st["next_epoch"] = jnp.where(anchor, nxt, st["next_epoch"])
    return st


def _warp_done(S, cst, st, fin, widc):
    """BatchedSMEngine._warp_done_rows minus the remaining-decrement
    (done by the caller): Best-SWL / statPCAL released-set rotation."""
    arB = jnp.arange(st["cycle"].shape[0])
    n = S.n
    st = dict(st)
    for kind, key in ((WD_SWL, "allowed_pl"), (WD_STATP, "sp_base")):
        km = fin & (cst["wd_kind"] == kind)
        mask_pl = st[key]
        in_set = km & mask_pl[arB, widc]
        mask_pl = mask_pl.at[arB, widc].set(
            mask_pl[arB, widc] & ~in_set)
        nx = st["swl_next"]
        can = in_set & (nx < n)
        nxc = jnp.minimum(nx, n - 1)
        mask_pl = mask_pl.at[arB, nxc].set(mask_pl[arB, nxc] | can)
        st[key] = mask_pl
        st["swl_next"] = jnp.where(can, nx + 1, nx)
        if kind == WD_STATP:
            sb = st["sp_bypass"][:, None]
            st["allowed_pl"] = jnp.where(in_set[:, None],
                                         sb | mask_pl, st["allowed_pl"])
            st["bypass_pl"] = jnp.where(in_set[:, None],
                                        sb & ~mask_pl, st["bypass_pl"])
        st["avail"] = jnp.where(in_set[:, None],
                                st["allowed_pl"] & ~st["done"],
                                st["avail"])
        st["byp"] = jnp.where(in_set[:, None], st["bypass_pl"],
                              st["byp"])
    return st


def _timeline(S, st, m):
    """BatchedSMEngine._timeline_rows."""
    arB = jnp.arange(st["cycle"].shape[0])
    st = dict(st)
    act = jnp.sum(st["allowed_pl"], axis=1)
    k = st["tl_n"]
    kc = jnp.minimum(k, S.tl_cap - 1)           # capacity is proven ample
    cyc, ins = st["cycle"], st["instr"]
    dc = jnp.maximum(cyc - st["last_cycle"], 1)
    st["tl_cycle"] = st["tl_cycle"].at[arB, kc].set(
        jnp.where(m, cyc, st["tl_cycle"][arB, kc]))
    st["tl_dins"] = st["tl_dins"].at[arB, kc].set(
        jnp.where(m, ins - st["last_instr"], st["tl_dins"][arB, kc]))
    st["tl_dcyc"] = st["tl_dcyc"].at[arB, kc].set(
        jnp.where(m, dc, st["tl_dcyc"][arB, kc]))
    st["tl_act"] = st["tl_act"].at[arB, kc].set(
        jnp.where(m, act, st["tl_act"][arB, kc]))
    st["tl_n"] = jnp.where(m, k + 1, k)
    st["last_instr"] = jnp.where(m, ins, st["last_instr"])
    st["last_cycle"] = jnp.where(m, cyc, st["last_cycle"])
    st["window_mark"] = jnp.where(m, st["window_mark"] + S.timeline_every,
                                  st["window_mark"])
    return st


def _vta_insert(S, st, mask, owner, victim_line, evictor):
    """BatchedSMEngine._np_vta_insert (circular FIFO insert)."""
    arB = jnp.arange(st["cycle"].shape[0])
    v_k = S.v_k
    st = dict(st)
    s = owner % S.v_sets
    h = st["v_head"][arB, s]
    cc = st["v_count"][arB, s]
    full = cc == v_k
    slot = s * v_k + jnp.where(full, h, (h + cc) % v_k)
    st["v_addr"] = st["v_addr"].at[arB, slot].set(
        jnp.where(mask, victim_line, st["v_addr"][arB, slot]))
    st["v_evic"] = st["v_evic"].at[arB, slot].set(
        jnp.where(mask, evictor, st["v_evic"][arB, slot]))
    st["v_head"] = st["v_head"].at[arB, s].set(
        jnp.where(mask & full, (h + 1) % v_k, h))
    st["v_count"] = st["v_count"].at[arB, s].set(
        jnp.where(mask & ~full, cc + 1, cc))
    st["v_inserts"] = st["v_inserts"] + mask
    return st


def _vta_probe(S, cst, st, pm, widc, line):
    """The probe + FIFO pop + detector bookkeeping, vectorized over the
    logical window (BatchedSMEngine._vta_probe_pop per flagged row)."""
    B = st["cycle"].shape[0]
    arB = jnp.arange(B)
    v_k = S.v_k
    st = dict(st)
    s = widc % S.v_sets
    base = s * v_k
    h = st["v_head"][arB, s]
    cc = st["v_count"][arB, s]
    ar_k = jnp.arange(v_k)
    phys = base[:, None] + (h[:, None] + ar_k) % v_k
    lvals = st["v_addr"][arB[:, None], phys]
    levic = st["v_evic"][arB[:, None], phys]
    member = pm & (lvals == line[:, None]).any(1)
    matchl = (lvals == line[:, None]) & (ar_k[None] < cc[:, None])
    found = member & matchl.any(1)
    jm = jnp.argmax(matchl, axis=1)             # oldest logical match
    evictor = jnp.where(found, levic[arB, jm], NO_WARP)
    shift = found[:, None] & (ar_k >= jm[:, None]) \
        & (ar_k < (cc - 1)[:, None])
    nl = jnp.where(shift, jnp.roll(lvals, -1, axis=1), lvals)
    ne = jnp.where(shift, jnp.roll(levic, -1, axis=1), levic)
    clear = found[:, None] & (ar_k == (cc - 1)[:, None])
    nl = jnp.where(clear, -1, nl)
    ne = jnp.where(clear, -1, ne)
    st["v_addr"] = st["v_addr"].at[arB[:, None], phys].set(nl)
    st["v_evic"] = st["v_evic"].at[arB[:, None], phys].set(ne)
    st["v_count"] = st["v_count"].at[arB, s].set(cc - found)
    st["d_vta_hits"] = st["d_vta_hits"].at[arB, s].add(found)
    st["vta_hit_events"] = st["vta_hit_events"] + member
    st["cnt_vta_hits"] = st["cnt_vta_hits"] + member
    st["d_irs_hits"] = st["d_irs_hits"].at[arB, widc % S.nw].add(member)
    pidx = (evictor + 1) * S.n + widc
    st["pair_dense"] = st["pair_dense"].at[arB, pidx].add(member)
    # interference list (2-bit saturating replacement)
    i = widc % S.le
    interf = st["d_interfering"][arB, i]
    sat = st["d_sat"][arB, i]
    same = interf == evictor
    empty = interf == NO_WARP
    ni = jnp.where(same, interf,
                   jnp.where(empty | (sat == 0), evictor, interf))
    ns = jnp.where(same, jnp.minimum(sat + 1, S.sat_max),
                   jnp.where(empty, 0,
                             jnp.where(sat == 0, sat, sat - 1)))
    st["d_interfering"] = st["d_interfering"].at[arB, i].set(
        jnp.where(member, ni, interf))
    st["d_sat"] = st["d_sat"].at[arB, i].set(jnp.where(member, ns, sat))
    # CCWS lost-locality bump (policy.on_mem_event(wid, "vta_hit"))
    st["score_pl"] = st["score_pl"].at[arB, widc].add(
        jnp.where(member, cst["bump"], 0))
    return st


def _mem_chain(S, cst, st, mem, tok, widc, cycle):
    """BatchedSMEngine._np_mem_chain. Returns (st, lat, done_t parts are
    derived by the caller): all state scatters happen here, ``lat`` is
    the per-row access latency."""
    B = st["cycle"].shape[0]
    arB = jnp.arange(B)
    st = dict(st)
    line = tok >> _SHIFT
    bypm = mem & st["byp"][arB, widc]
    isom = mem & st["iso"][arB, widc] & ~bypm
    norm = mem & ~bypm & ~isom
    st["cnt_bypass"] = st["cnt_bypass"] + bypm
    post = bypm
    lat = jnp.zeros(B, jnp.int64)

    # ---- L1 way scan (shared with the CIAO-P migration probe) ----
    s1 = line % S.l1_sets
    if S.xor_hash:
        s1 = (s1 ^ ((line // S.l1_sets) % S.l1_sets)) % S.l1_sets
    base1 = s1 * S.l1_ways
    way_idx = base1[:, None] + jnp.arange(S.l1_ways)
    tags = st["l1_tags"]
    eq = jnp.take_along_axis(tags, way_idx, 1) == line[:, None]
    resident = eq.any(1)
    f_hit = base1 + jnp.argmax(eq, axis=1)

    hit = norm & resident
    miss = norm & ~resident
    st["cnt_l1_hit"] = st["cnt_l1_hit"] + hit
    st["cnt_l1_miss"] = st["cnt_l1_miss"] + miss
    reused = st["l1_reused"].at[arB, f_hit].set(
        st["l1_reused"][arB, f_hit] | hit)
    stamp = st["l1_stamp"].at[arB, f_hit].set(
        jnp.where(hit, st["tick"], st["l1_stamp"][arB, f_hit]))
    lat = jnp.where(hit, cst["lat_l1"], lat)

    # ---- CIAO-P smem region: evictions insert before the probe ----
    rb = cst["region_blocks"]
    no_region = isom & (rb <= 0)
    post = post | no_region
    iso2 = isom & ~no_region
    sidx = line % jnp.maximum(rb, 1)
    sold = st["smem_tags"][arB, sidx]
    shit = iso2 & (sold == line)
    st["cnt_smem_hit"] = st["cnt_smem_hit"] + shit
    lat = jnp.where(shit, cst["lat_smem"], lat)
    smiss = iso2 & ~shit
    sevict = smiss & (sold >= 0)
    st["cnt_smem_evictions"] = st["cnt_smem_evictions"] + sevict
    sown = st["smem_owner"][arB, sidx]
    st = _vta_insert(S, st, sevict & (sown != widc), sown, sold, widc)

    # ---- VTA probe (after smem inserts, before L1-fill inserts) ----
    st = _vta_probe(S, cst, st, miss | smiss, widc, line)

    # ---- L1 fill (miss path) ----
    vic = base1 + jnp.argmin(jnp.take_along_axis(stamp, way_idx, 1),
                             axis=1)
    old = tags[arB, vic]
    owners = st["l1_owners"]
    oldown = owners[arB, vic]
    oldreu = reused[arB, vic]
    evict = miss & (old >= 0)
    st["cnt_evictions"] = st["cnt_evictions"] + evict
    ins = evict & (oldown != widc)
    if S.reuse_filter:
        ins = ins & oldreu
    st = _vta_insert(S, st, ins, oldown, old, widc)
    tags = tags.at[arB, vic].set(jnp.where(miss, line, old))
    owners = owners.at[arB, vic].set(jnp.where(miss, widc, oldown))
    reused = reused.at[arB, vic].set(jnp.where(miss, False, oldreu))
    stamp = stamp.at[arB, vic].set(
        jnp.where(miss, st["tick"], stamp[arB, vic]))
    post = post | miss

    # ---- smem migration / fill (after the probe, like the scalar) ----
    mig = smiss & resident
    tags = tags.at[arB, f_hit].set(
        jnp.where(mig, -1, tags[arB, f_hit]))
    owners = owners.at[arB, f_hit].set(
        jnp.where(mig, -1, owners[arB, f_hit]))
    st["cnt_smem_migrate"] = st["cnt_smem_migrate"] + mig
    lat = jnp.where(mig, cst["lat_migrate"], lat)
    smiss2 = smiss & ~mig
    st["cnt_smem_miss"] = st["cnt_smem_miss"] + smiss2
    post = post | smiss2
    st["smem_tags"] = st["smem_tags"].at[arB, sidx].set(
        jnp.where(smiss, line, sold))
    st["smem_owner"] = st["smem_owner"].at[arB, sidx].set(
        jnp.where(smiss, widc, st["smem_owner"][arB, sidx]))
    st["l1_tags"], st["l1_owners"] = tags, owners
    st["l1_reused"], st["l1_stamp"] = reused, stamp
    st["tick"] = st["tick"] + norm

    # ---- post-L1 stage: L2 tags + DRAM bandwidth queueing ----
    b2 = (line % S.l2_sets) * S.l2_ways
    wi2 = b2[:, None] + jnp.arange(S.l2_ways)
    t2 = st["l2_tags"]
    eq2 = jnp.take_along_axis(t2, wi2, 1) == line[:, None]
    l2res = eq2.any(1)
    h2 = post & l2res
    m2 = post & ~l2res
    st["l2_hits"] = st["l2_hits"] + h2
    lat = jnp.where(h2, cst["lat_l2"], lat)
    f2 = b2 + jnp.argmax(eq2, axis=1)
    vic2 = b2 + jnp.argmin(jnp.take_along_axis(st["l2_stamp"], wi2, 1),
                           axis=1)
    st["l2_tags"] = t2.at[arB, vic2].set(
        jnp.where(m2, line, t2[arB, vic2]))
    st["l2_misses"] = st["l2_misses"] + m2
    chn = (line >> 2) % S.dram_channels
    free = st["dram_free"][arB, chn]
    start = jnp.maximum(cycle, free)
    st["dram_free"] = st["dram_free"].at[arB, chn].set(
        jnp.where(m2, start + cst["dram_gap"], free))
    st["dram_requests"] = st["dram_requests"] + m2
    st["cnt_dram_reqs"] = st["cnt_dram_reqs"] + m2
    lat = jnp.where(m2, cst["lat_dram"] + start - cycle, lat)
    f2 = jnp.where(m2, vic2, f2)
    st["l2_stamp"] = st["l2_stamp"].at[arB, f2].set(
        jnp.where(post, st["l2_tick"], st["l2_stamp"][arB, f2]))
    st["l2_tick"] = st["l2_tick"] + post
    return st, lat


def _iteration(S, cst, st):
    """One lockstep iteration == BatchedSMEngine._np_iteration for a
    single-SM batch that runs to the cycle cap (until == max_cycles)."""
    B = st["cycle"].shape[0]
    arB = jnp.arange(B)
    st = dict(st)
    cycle = st["cycle"]
    act = (st["remaining"] > 0) & (cycle < cst["max_cycles"])

    # ---- warp selection (greedy-then-oldest + fused event skip) ----
    ready, avail = st["ready"], st["avail"]
    lw = st["last_wid"]
    lw_ok = lw >= 0
    lwc = jnp.where(lw_ok, lw, 0)
    greedy = act & lw_ok & avail[arB, lwc] & (ready[arB, lwc] <= cycle)
    wid = jnp.where(greedy, lw, -1)
    need = act & ~greedy
    cand = (ready <= cycle[:, None]) & avail
    w = jnp.argmax(cand, axis=1)
    found = need & cand[arB, w]
    wid = jnp.where(found, w, wid)
    lw = jnp.where(found, w, lw)
    skip = need & ~found
    sched = jnp.where(avail, ready, _HUGE)
    w2 = jnp.argmin(sched, axis=1)
    thr = skip & ~avail[arB, w2]
    # everything throttled: advance to let epochs fire (no re-anchor)
    st["cycle"] = cycle = jnp.where(thr, cycle + cst["low_epoch"], cycle)
    st["li"] = jnp.where(thr, st["li"] + cst["low_epoch"], st["li"])
    st = _gated(st, thr,
                lambda s, mm: _epoch_service(S, cst, s, mm,
                                             jnp.zeros_like(mm)))
    sk = skip & ~thr
    best = ready[arB, w2]
    clamp = sk & (best >= cst["max_cycles"])    # slice stop at the cap
    st["cycle"] = cycle = jnp.where(
        clamp, cst["max_cycles"], jnp.where(sk & ~clamp, best, cycle))
    sk = sk & ~clamp
    lw_ok2 = lw >= 0
    lwc2 = jnp.where(lw_ok2, lw, 0)
    tie = sk & lw_ok2 & st["avail"][arB, lwc2] \
        & (ready[arB, lwc2] <= best)
    wid = jnp.where(tie, lw, wid)
    w2sel = sk & ~tie
    wid = jnp.where(w2sel, w2, wid)
    lw = jnp.where(w2sel, w2, lw)
    st["last_wid"] = lw

    disp = act & (wid >= 0)
    widc = jnp.where(disp, wid, 0)

    # ---- token fetch ----
    oi = st["op_idx"][arB, widc]
    tok = cst["toks"][cst["u_of"], widc, oi]
    alu = disp & (tok < 0)
    mem = disp & ~alu
    adv = jnp.where(alu, -tok, 0) + mem

    new_ready = st["ready"][arB, widc]
    st, lat = _mem_chain(S, cst, st, mem, tok, widc, cycle)
    done_t = cycle + lat
    dep = mem & ((tok & 1) == 1)
    nondep = mem & ~dep
    new_ready = jnp.where(dep, done_t, new_ready)
    prow = st["pend"][arB, widc]                 # (B, P)
    slot = jnp.argmin(prow, axis=1)              # a stale (<=cycle) slot
    nv = jnp.where(nondep, done_t, prow[arB, slot])
    st["pend"] = st["pend"].at[arB, widc, slot].set(nv)
    prow = prow.at[arB, slot].set(nv)
    valid = prow > cycle[:, None]
    outstanding = jnp.sum(valid, axis=1)
    earliest = jnp.min(jnp.where(valid, prow, _HUGE), axis=1)
    new_ready = jnp.where(
        nondep,
        jnp.where(outstanding >= S.max_mlp, earliest, cycle + 1),
        new_ready)
    new_ready = jnp.where(alu, cycle + adv, new_ready)

    adv = jnp.where(disp, adv, 0)
    st["li"] = st["li"] + adv
    st["cycle"] = cycle = cycle + adv            # mem rows: +1
    st["ready"] = st["ready"].at[arB, widc].set(new_ready)
    oi_new = oi + disp
    st["op_idx"] = st["op_idx"].at[arB, widc].set(oi_new)
    st["instr"] = st["instr"] + adv

    # ---- warp retirement -> epoch -> timeline (the scalar order) ----
    fin = disp & (oi_new >= cst["n_ops"][arB, widc])
    st["done"] = st["done"].at[arB, widc].set(
        st["done"][arB, widc] | fin)
    st["avail"] = st["avail"].at[arB, widc].set(
        st["avail"][arB, widc] & ~fin)
    st["last_wid"] = jnp.where(fin, -1, st["last_wid"])
    st["remaining"] = st["remaining"] - fin
    st = _gated(st, fin,
                lambda s, mm, ww: _warp_done(S, cst, s, mm, ww), widc)
    ep = disp & (st["li"] >= st["next_epoch"])
    st = _gated(st, ep, lambda s, mm: _epoch_service(S, cst, s, mm, mm))
    tl = disp & (st["instr"] >= st["window_mark"])
    st = _gated(st, tl, lambda s, mm: _timeline(S, s, mm))
    return st


@functools.lru_cache(maxsize=None)
def _compiled(S: _Static):
    def run(state, cst):
        def cond(st):
            return jnp.any((st["remaining"] > 0)
                           & (st["cycle"] < cst["max_cycles"]))

        def body(st):
            return _iteration(S, cst, st)
        return lax.while_loop(cond, body, state)
    return jax.jit(run)


def run_engine(eng) -> None:
    """Run every row of a BatchedSMEngine to completion under jit and
    write the final state back into the engine's numpy arrays; the
    engine's ``_finalize`` then assembles results exactly like the
    numpy/C paths. Raises RuntimeError when :func:`supports_engine`
    says no."""
    why = supports_engine(eng)
    if why:
        raise RuntimeError(f"jax backend unavailable for this batch: "
                           f"{why}")
    S = _static_of(eng)
    state, cst = _arrays_of(eng)
    with jax.enable_x64(True):
        fn = _compiled(S)
        t0 = time.perf_counter()
        out = jax.device_get(fn(state, cst))
        eng.perf["stepper_s"] += time.perf_counter() - t0
        eng.perf["rounds"] += 1
    t0 = time.perf_counter()
    _write_back(eng, out)
    for b in range(eng.B):
        eng._finalize(b)
    eng.perf["drain_s"] += time.perf_counter() - t0
