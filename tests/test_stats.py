"""Bounded serving-statistics primitives (serving/stats.py).

Pins the ``Peak`` lazy-max regression (all-negative streams must report
their true negative max, not 0.0) and checks the P² streaming quantile
estimator against ``np.percentile`` — exactly on the first five
observations (the estimator's exact path), by rank error afterwards
(P² keeps five markers, so its estimate must sit at the right *rank*
of the stream even though the height is approximate).
"""
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.serving.stats import P2Quantile, Peak, Ring


# ------------------------------------------------------------------ Peak
def test_peak_all_negative_stream():
    p = Peak()
    for x in (-5.0, -2.0, -9.0):
        p.add(x)
    assert p.max == -2.0          # not 0.0: the max must come from data
    assert p.mean == (-5.0 - 2.0 - 9.0) / 3
    assert p.n == 3


def test_peak_empty_is_stable():
    p = Peak()
    assert p.max == 0.0 and p.n == 0
    assert "Peak(" in repr(p)     # repr must not divide by zero


def test_peak_positive_stream():
    p = Peak()
    for x in (1.0, 7.0, 3.0):
        p.add(x)
    assert p.max == 7.0 and p.n == 3 and p.total == 11.0


@settings(max_examples=30)
@given(st.lists(st.floats(-1e3, 1e3), min_size=1, max_size=40))
def test_peak_matches_numpy(xs):
    p = Peak()
    for x in xs:
        p.add(x)
    assert p.max == max(xs)
    assert abs(p.mean - np.mean(xs)) < 1e-6 * max(1.0, abs(np.mean(xs)))


# ------------------------------------------------------------------ Ring
def test_ring_drops_oldest():
    r = Ring(maxlen=4)
    for i in range(10):
        r.append(i)
    assert list(r) == [6, 7, 8, 9]


# ------------------------------------------------------- P2Quantile exact
def test_p2_exact_small_sample():
    """n <= 5 takes the exact path: linear interpolation identical to
    np.percentile's default method."""
    rng = np.random.default_rng(3)
    for n in range(1, 6):
        for q in (0.25, 0.5, 0.95):
            xs = rng.normal(size=n)
            est = P2Quantile(q)
            for x in xs:
                est.add(float(x))
            np.testing.assert_allclose(est.value,
                                       np.percentile(xs, 100 * q),
                                       rtol=1e-12, atol=1e-12)


def test_p2_empty_is_zero():
    assert P2Quantile(0.5).value == 0.0


# ---------------------------------------------------- P2Quantile property
def _rank_error(xs, q, est):
    """|empirical CDF at the estimate - q| — the natural accuracy metric
    for a quantile estimator (height error is distribution-dependent)."""
    xs = np.asarray(xs)
    return abs(np.mean(xs <= est) - q)


@settings(max_examples=15)
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.1, 0.9))
def test_p2_tracks_numpy_rank(seed, q):
    """On continuous distributions the P² estimate must land within a
    few percentile points of ``np.percentile``'s rank (measured worst
    case over 1500 seed/quantile pairs: 0.038)."""
    rng = np.random.default_rng(seed)
    n = 400
    xs = (rng.uniform(-10, 10, n) if seed % 2 == 0
          else rng.lognormal(0.0, 1.0, n))      # heavy tail
    est = P2Quantile(float(q))
    for x in xs:
        est.add(float(x))
    assert _rank_error(xs, q, est.value) <= 0.06
    assert xs.min() <= est.value <= xs.max()
    ref = np.percentile(xs, 100 * q)
    assert abs(np.mean(xs <= est.value) - np.mean(xs <= ref)) <= 0.06


def test_p2_bimodal_stays_in_range():
    """Gapped (bimodal) streams are P²'s documented weak spot — the
    markers interpolate across the density gap, so rank error can reach
    ~0.2 there.  Pin only the containment contract: the estimate stays
    inside the sample range and on the correct side of the far
    cluster."""
    rng = np.random.default_rng(7)
    xs = np.concatenate([rng.normal(-5, 0.5, 200),
                         rng.normal(5, 0.5, 200)])
    for q, lo, hi in ((0.1, xs.min(), 0.0), (0.9, 0.0, xs.max())):
        est = P2Quantile(q)
        for x in xs:
            est.add(float(x))
        assert lo <= est.value <= hi


def test_p2_sorted_adversarial_stream():
    """Monotone input is the P² worst case; the markers must still
    track the quantile's rank."""
    xs = np.arange(1000, dtype=float)
    for q in (0.5, 0.95):
        est = P2Quantile(q)
        for x in xs:
            est.add(x)
        assert _rank_error(xs, q, est.value) <= 0.08
