"""Algorithm 1 controller + Eq. 2–5 adaptive model: unit + hypothesis
property tests on the system's control invariants."""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.adaptive import (AdaptiveDrafter,
                                 alpha_from_accept_len,
                                 expected_accept_len, min_accept_len_for_gain,
                                 practical_speedup,
                                 PAPER_PROFILES)
from repro.core.controller import Decision, TrainingController


# ------------------------------------------------------------- Eq. 2–5
@given(st.floats(0.0, 0.999), st.integers(1, 8))
def test_expected_accept_len_bounds(alpha, gamma):
    ell = expected_accept_len(alpha, gamma)
    assert 1.0 <= ell <= gamma + 1 + 1e-9


@given(st.floats(0.0, 0.99), st.floats(0.0, 0.99), st.integers(1, 8))
def test_expected_accept_len_monotone(a1, a2, gamma):
    lo, hi = sorted((a1, a2))
    assert expected_accept_len(lo, gamma) <= \
        expected_accept_len(hi, gamma) + 1e-9


@given(st.floats(1.001, 3.9), st.integers(3, 6))
def test_alpha_inversion_roundtrip(ell, gamma):
    alpha = alpha_from_accept_len(ell, gamma)
    assert abs(expected_accept_len(alpha, gamma) - ell) < 1e-3


def test_practical_speedup_matches_paper_regime():
    """With the paper's gpt-oss-120b profile (Table 5), speculation helps
    at small batch and fades at large batch (Figs. 4/8)."""
    prof = PAPER_PROFILES["gpt-oss-120b"]
    alpha = 0.65                      # ~accept len 2.4 at γ=3 (Table 4)
    s1 = practical_speedup(alpha, 3, prof, 1)
    s64 = practical_speedup(alpha, 3, prof, 64)
    s512 = practical_speedup(alpha, 3, prof, 512)
    assert s1 > 1.15                  # clear win at b=1
    assert s1 > s64 > s512            # degrades with batch (Fig. 4)


def test_beta_grows_with_batch():
    prof = PAPER_PROFILES["gpt-oss-120b"]
    betas = [prof.beta(b, 3) for b in (1, 8, 64, 128)]
    assert betas[0] < betas[-1]
    assert betas[-1] > 1.5            # decidedly not memory-bound at 128


def test_min_accept_len_threshold_consistency():
    prof = PAPER_PROFILES["llama-3.3-70b-instruct"]
    for b in (1, 16, 128):
        thr = min_accept_len_for_gain(3, prof, b)
        alpha = alpha_from_accept_len(min(thr, 3.99), 3)
        s = practical_speedup(alpha, 3, prof, b)
        assert abs(s - 1.0) < 0.05    # threshold sits at breakeven


def test_adaptive_drafter_toggles():
    prof = PAPER_PROFILES["gpt-oss-120b"]
    d = AdaptiveDrafter(prof, gamma=3)
    assert d.update(batch=1, accept_len_ema=2.5) is True
    assert d.update(batch=256, accept_len_ema=1.05) is False


# --------------------------------------------------------- Algorithm 1
def test_controller_init_phase():
    c = TrainingController(n_init=4)
    for _ in range(3):
        assert c.observe(0.5) == Decision.NONE
        assert c.alpha_short is None
    c.observe(0.5)
    assert c.alpha_short == pytest.approx(0.5)
    assert c.alpha_long == pytest.approx(0.5)


def test_controller_detects_shift_and_triggers():
    c = TrainingController(n_init=2, epsilon=0.02, n_threshold=10,
                           lambda_short=0.5, lambda_long=0.99)
    c.observe(0.8)
    c.observe(0.8)
    # distribution shift: acceptance collapses
    decisions = [c.observe(0.1, n_new_samples=0) for _ in range(4)]
    assert Decision.START_COLLECTION in decisions
    assert c.collection_enabled
    # samples accumulate -> training triggers
    d = None
    for _ in range(5):
        d = c.observe(0.1, n_new_samples=4)
        if d == Decision.TRIGGER_TRAINING:
            break
    assert d == Decision.TRIGGER_TRAINING


def test_controller_deploy_gate():
    c = TrainingController(n_init=1)
    c.observe(0.5)
    c.collection_enabled = True
    c.observe(0.2, n_new_samples=8)
    base = c.alpha_train
    assert base == pytest.approx(0.2)
    assert c.training_result(alpha_eval=0.5) is True       # improved
    assert c.stored_samples == 0                            # buffer reset
    c.collection_enabled = True
    c.observe(0.4, n_new_samples=8)
    assert c.training_result(alpha_eval=0.1) is False       # regressed
    assert c.collection_enabled is False                    # Alg.1 disable


@given(st.lists(st.floats(0.0, 1.0), min_size=8, max_size=60),
       st.floats(0.5, 0.95), st.floats(0.96, 0.999))
@settings(max_examples=40, deadline=None)
def test_controller_ema_invariants(alphas, lam_s, lam_l):
    """EMAs stay within [0, 1]; short EMA tracks recent values faster."""
    c = TrainingController(n_init=4, lambda_short=lam_s, lambda_long=lam_l,
                           n_threshold=10**9)
    for a in alphas:
        c.observe(a)
    if c.alpha_short is not None:
        assert 0.0 <= c.alpha_short <= 1.0
        assert 0.0 <= c.alpha_long <= 1.0
    # a sustained collapse must eventually flip collection on
    for _ in range(200):
        c.observe(0.0)
    if max(alphas[:4] or [0]) > 0.2:
        assert c.collection_enabled


def test_hetero_allocation_model():
    from repro.core.hetero import (PAPER_DEVICES, best_split,
                                   paper_figure12_grid, plan_tpu_submesh)
    # paper Fig. 12 anchor points
    r = best_split(PAPER_DEVICES["H100"], PAPER_DEVICES["MI250"], 4, 1,
                   1.3)
    assert r["relative_throughput"] == pytest.approx(1.26, abs=0.02)
    r2 = best_split(PAPER_DEVICES["MI300X"], PAPER_DEVICES["MI250"], 2, 1,
                    1.1)
    assert r2["relative_throughput"] == pytest.approx(0.99, abs=0.02)
    assert not r2["use_tide"]
    grid = paper_figure12_grid()
    assert len(grid) == 12
    plan = plan_tpu_submesh(256, s=1.3)
    assert plan.train_chips > 0 and plan.relative_throughput() > 1.0
