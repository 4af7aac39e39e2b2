"""Vectorized epoch-path kernels (repro.core.epoch): a batch-of-k kernel
call over stacked planes must equal k independent batch-of-1 object
calls, for every policy family, on random counter states.

The scalar objects (InterferenceDetector, the policy classes) *are*
batch-of-1 views onto the same kernels, so this property pins exactly
what the batched engine adds on top: the batch indexing. Two identical
sets of cells are built from one seed; set A ticks through the objects
cell by cell, set B is adopted into full-batch planes (the engine's
``adopt_*`` path) and ticked by one kernel call, mirroring
``BatchedSMEngine._epoch_batch``."""
from fractions import Fraction

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import epoch as _epoch
from repro.core.interference import DetectorConfig, InterferenceDetector
from repro.core.policies import (CCWSPolicy, CIAOPolicy, StatPCALPolicy,
                                 make_policy)

N = 12          # warps per cell
K = 5           # cells per batch


def _det_cfg():
    return DetectorConfig(num_warps=N, vta_sets=N, list_entries=16,
                          high_epoch=1000, low_epoch=50)


def _rand_cell(rng, policy_name):
    """One (detector, policy) pair with randomized epoch-relevant state,
    reproducible from the rng stream."""
    det = InterferenceDetector(_det_cfg())
    pol = make_policy(policy_name, N, det)
    det.on_instruction(int(rng.integers(0, 4000)))
    det.irs_hits[:] = rng.integers(0, 60, N)
    det.vta.hits[:] = rng.integers(0, 60, N)
    det.interfering_wid[:] = rng.integers(-1, N, det.cfg.list_entries)
    det.sat_counter[:] = rng.integers(0, det.cfg.sat_max + 1,
                                      det.cfg.list_entries)
    # misalign the epoch ordinals so poll crossings vary per cell
    det._pl.low_idx[0] = rng.integers(0, 3)
    det._pl.high_idx[0] = rng.integers(0, 2)
    det._pl.high_crossings[0] = rng.integers(0, 20)
    if isinstance(pol, CCWSPolicy):
        pol.score[:] = rng.integers(pol.base, 4000, N)
    if isinstance(pol, StatPCALPolicy):
        if rng.integers(0, 2):
            # flip into bypass mode through the real epoch path
            pol.epoch_tick(None, [False] * N, (0, 0))
    if isinstance(pol, CIAOPolicy):
        # push a few legitimate stack entries (stall via the public
        # API; isolation white-box, as high_epoch_tick would)
        for w in rng.choice(N, size=int(rng.integers(0, 3)),
                            replace=False):
            trig = int(rng.integers(0, N))
            if pol.mode != "p" and rng.integers(0, 2):
                pol.stall_directly(int(w), trig)
            elif not pol.isolated_mask[w]:
                pol.isolated_mask[w] = True
                det.record_isolation(int(w), trig)
                pol._iso[int(pol._iso_len[0])] = int(w)
                pol._iso_len[0] += 1
    return det, pol


def _batch_tick(dets, pols, done, req_gap, chan_cyc):
    """Mirror of BatchedSMEngine._epoch_batch over freshly adopted
    planes (the engine's exact call sequence, minus the stepper)."""
    k = len(dets)
    cfg = dets[0].cfg
    pl = _epoch.DetPlanes.alloc(k, cfg)
    allowed = np.ones((k, N), bool)
    isolated = np.zeros((k, N), bool)
    bypass = np.zeros((k, N), bool)
    score = np.zeros((k, N), np.int64)
    base = np.zeros(k, np.int64)
    budget = np.zeros(k, np.int64)
    sp_byp = np.zeros(k, bool)
    sp_thr = np.zeros((k, 2), np.int64)
    sp_base = np.zeros((k, N), bool)
    stall = np.full((k, N), -1, np.int64)
    iso = np.full((k, N), -1, np.int64)
    stall_len = np.zeros(k, np.int64)
    iso_len = np.zeros(k, np.int64)
    for b, (det, pol) in enumerate(zip(dets, pols)):
        det.adopt_row(pl, b)
        pol.adopt_mask_rows(allowed[b], isolated[b], bypass[b])
        if isinstance(pol, CCWSPolicy):
            pol.adopt_score_row(score[b])
            base[b], budget[b] = pol.base, pol.budget
        if isinstance(pol, StatPCALPolicy):
            pol.adopt_statpcal_rows(sp_byp[b:b + 1], sp_thr[b:b + 1],
                                    sp_base[b])
        if isinstance(pol, CIAOPolicy):
            pol.adopt_ciao_rows(stall[b], stall_len[b:b + 1],
                                iso[b], iso_len[b:b + 1])
    idx = np.arange(k, dtype=np.int64)
    pol0 = pols[0]
    if isinstance(pol0, CCWSPolicy):
        _epoch.ccws_tick(score, base, budget, ~done, allowed, idx)
    elif isinstance(pol0, StatPCALPolicy):
        _epoch.statpcal_tick(
            sp_byp, _epoch.util_below(req_gap, chan_cyc, sp_thr[idx]),
            sp_base, allowed, bypass, idx)
    elif isinstance(pol0, CIAOPolicy):
        n_act = np.count_nonzero(allowed & ~done, axis=1)
        low, high = _epoch.poll_epochs(pl, idx, n_act)
        lo = idx[low]
        if lo.size:
            _epoch.ciao_low_tick(pl, stall, stall_len, iso, iso_len,
                                 allowed, isolated, done, n_act[low], lo)
        hi = idx[high]
        if hi.size:
            _epoch.ciao_high_tick(
                pl, stall, stall_len, iso, iso_len, allowed,
                isolated, done, allowed[hi] & ~done[hi],
                np.full(len(hi), pol0.mode in ("p", "c")),
                np.full(len(hi), pol0.mode in ("t", "c")), hi)
    return pl


FAMILY = st.sampled_from(["ccws", "statpcal", "ciao-p", "ciao-t",
                          "ciao-c"])


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10**9), FAMILY)
def test_batched_kernels_equal_per_cell_objects(seed, family):
    mk = lambda: [_rand_cell(np.random.default_rng(seed + i), family)
                  for i in range(K)]  # noqa: E731
    cells_a, cells_b = mk(), mk()
    rng = np.random.default_rng(seed ^ 0xC1A0)
    done = rng.integers(0, 2, (K, N)).astype(bool)
    done[:, 0] = False                  # keep at least one warp alive
    # DRAM load (busy, capacity): utilization min(1, busy / capacity)
    req_gap = rng.integers(0, 1000, K)
    chan_cyc = rng.integers(0, 1000, K)

    # A: the per-cell object path (batch-of-1 views)
    for (det, pol), d, g, c in zip(cells_a, done, req_gap, chan_cyc):
        pol.epoch_tick(None, d, (int(g), int(c)))
    # B: one batched kernel pass over stacked planes
    pl_b = _batch_tick([d for d, _ in cells_b],
                       [p for _, p in cells_b], done, req_gap, chan_cyc)

    for b, ((det_a, pol_a), (det_b, pol_b)) in enumerate(
            zip(cells_a, cells_b)):
        tag = f"cell {b} ({family})"
        np.testing.assert_array_equal(
            pol_a.allowed_mask, pol_b.allowed_mask, tag)
        np.testing.assert_array_equal(
            pol_a.isolated_mask, pol_b.isolated_mask, tag)
        np.testing.assert_array_equal(
            pol_a.bypass_mask, pol_b.bypass_mask, tag)
        if isinstance(pol_a, CCWSPolicy):
            np.testing.assert_array_equal(pol_a.score, pol_b.score, tag)
        if isinstance(pol_a, StatPCALPolicy):
            assert pol_a.bypass_active == pol_b.bypass_active, tag
        if isinstance(pol_a, CIAOPolicy):
            assert pol_a.stall_stack == pol_b.stall_stack, tag
            assert pol_a.isolate_stack == pol_b.isolate_stack, tag
        # detector epoch state: the full planes row must agree, floats
        # bit-for-bit (same IEEE ops scalar vs vectorized)
        for f in _epoch.DetPlanes._ROW_FIELDS:
            np.testing.assert_array_equal(
                getattr(det_a._pl, f)[0], getattr(pl_b, f)[b],
                f"{tag}: detector plane {f}")


@settings(max_examples=80, deadline=None)
@given(st.integers(0, 10**9), st.integers(0, 1 << 11))
def test_cutoff_decisions_match_exact_rationals(seed, knum):
    """The fixed-point contract behind the shared cutoff decisions:
    ``irs_cum_leq`` / ``snap_over`` evaluate the IRS compare as the int64
    compare ``hits*act*den <> num*X`` against the knob's ``(num, den)``
    from ``epoch.ratio``. For dyadic (k/1024) and decimal (k/1000) knobs
    ``ratio`` is exact, so the decision must equal arbitrary-precision
    rational arithmetic bit-for-bit. This is what lets the numpy, C, and
    XLA steppers share one decision kernel without drift, on any chip.
    ``util_below`` is held to the same standard."""
    rng = np.random.default_rng(seed)
    pl = _epoch.DetPlanes.alloc(K, _det_cfg())
    pl.irs_inst[:] = rng.integers(0, 1 << 20, K)
    pl.irs_inst[rng.integers(0, K)] = 0     # exercise the 0-IRS guard
    pl.irs_hits[:] = rng.integers(0, 1 << 16, (K, N))
    idx = np.arange(K, dtype=np.int64)
    wid = rng.integers(0, N, K)
    act = rng.integers(0, N + 1, K)
    hits = rng.integers(0, 1 << 16, (K, N)).astype(np.int64)
    win = rng.integers(0, 1 << 20, K).astype(np.int64)
    req_gap = rng.integers(0, 1 << 20, K)
    chan_cyc = rng.integers(0, 1 << 20, K)
    chan_cyc[rng.integers(0, K)] = 0        # cycle 0: utilization 0

    for exact in (Fraction(knum, 1024), Fraction(knum, 1000)):
        cut = np.array(_epoch.ratio(float(exact)), np.int64)
        assert Fraction(int(cut[0]), int(cut[1])) == exact
        got = _epoch.irs_cum_leq(pl, idx, wid, act, cut)
        for b in range(K):
            inst, a = int(pl.irs_inst[b]), int(act[b])
            h = int(pl.irs_hits[b, wid[b] % N])
            want = (inst <= 0 or a <= 0) or Fraction(h * a) <= exact * inst
            assert bool(got[b]) == want, f"irs_cum_leq cell {b}"

        got2 = _epoch.snap_over(hits, win[:, None], act[:, None], cut)
        for b in range(K):
            for w in range(N):
                want = Fraction(int(hits[b, w]) * int(act[b])) \
                    > exact * int(win[b])
                assert bool(got2[b, w]) == want, f"snap_over {b},{w}"

        got3 = _epoch.util_below(req_gap, chan_cyc, cut)
        for b in range(K):
            g, c = int(req_gap[b]), int(chan_cyc[b])
            util = min(Fraction(1), Fraction(g, c)) if c else Fraction(0)
            assert bool(got3[b]) == (util < exact), f"util_below {b}"


@pytest.mark.parametrize("family", ["ccws", "ciao-c"])
def test_repeated_ticks_stay_equal(family):
    """Several consecutive epochs (state feeding back into itself)."""
    seed = 1234
    mk = lambda: [_rand_cell(np.random.default_rng(seed + i), family)
                  for i in range(K)]  # noqa: E731
    cells_a, cells_b = mk(), mk()
    done = np.zeros((K, N), bool)
    dets_b = [d for d, _ in cells_b]
    pols_b = [p for _, p in cells_b]
    for step in range(4):
        for (det, pol) in cells_a:
            det.on_instruction(60)
            pol.epoch_tick(None, done[0], (0, 0))
        for det in dets_b:
            det.on_instruction(60)
        _batch_tick(dets_b, pols_b, done, np.zeros(K, np.int64),
                    np.zeros(K, np.int64))
        for (det_a, pol_a), pol_b in zip(cells_a, pols_b):
            np.testing.assert_array_equal(
                pol_a.allowed_mask, pol_b.allowed_mask, f"step {step}")
