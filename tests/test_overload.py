"""Overload resilience: lane spill/restore, deadline preemption,
weighted-EDF admission, and load shedding (docs/overload.md).

The tentpole invariant mirrors the paged/chunked ones: changing *where*
a request's serving state lives (spilled to the host-side SpillStore
and restored onto a different/same lane, possibly onto different
physical pages) must never change *what* it generates.  Preemption only
reorders service; restored streams are byte-identical to never-evicted
runs, greedy and per-request-keyed sampled, dense and paged.

Overload is an arrival-dynamics phenomenon — in backlog mode EDF simply
admits the tight requests first — so the end-to-end tests replay gated
traces on the engine's injected clock bound to its own executed-round
counter (``stats.steps``): arrivals, deadlines, and latency stamps all
live in deterministic round units, reproducible on noisy shared hosts.

All tests run on randomly initialized weights (overload behavior is a
property of the control plane, not the model); the sampled parity
combos and the randomized property sweep carry the ``slow`` mark, the
rest stays in the fast tier.
"""
import dataclasses

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.configs as C
from repro.core import eagle, paging
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.policy import (DeadlinePreemption, ExpiredShed,
                                  PreemptionPolicy, QueueDepthShed,
                                  ServingConfig, WeightedEdfAdmission)
from repro.serving.request import Request

_MODEL = None


def _get_model():
    global _MODEL
    if _MODEL is None:
        cfg = C.get("tide-tiny")
        params = T.init(cfg, jax.random.key(0))
        dcfg = eagle.draft_config(cfg)
        dparams = eagle.draft_init(dcfg, jax.random.key(7))
        _MODEL = (cfg, params, dcfg, dparams)
    return _MODEL


_ENGINES = {}


def teardown_module():
    """Free the cached engines (and their compiled executables) once
    the module finishes: the full-tier session compiles enough programs
    that late-session LLVM compiles are sensitive to resident state."""
    _ENGINES.clear()


def _cached_engine(**kw):
    """One engine per config variant (compiles stay warm across tests
    and property examples); ``reset_adaptation`` restores the
    post-construction state between uses."""
    key = tuple(sorted(kw.items()))
    eng = _ENGINES.get(key)
    if eng is None:
        cfg, params, dcfg, dparams = _get_model()
        config = ServingConfig(batch_size=2, max_len=96, gamma=3, seed=5,
                               superstep_rounds=4, idle_wait_s=0.0005,
                               **kw)
        eng = _ENGINES[key] = ServingEngine(cfg, params, dcfg, dparams,
                                            config=config)
    eng.reset_adaptation(eng.dparams)
    eng.deploy_source = None
    return eng


def _round_clock(eng):
    """Bind the engine's injected clock to its own executed-round
    counter: gated arrivals, deadlines, and every latency stamp become
    deterministic round units."""
    eng._clock = lambda: float(eng.stats.steps)
    return eng


def _trace(spec, seed=3, plen=6):
    """Build a gated trace from (arrives_at, deadline, budget) rows,
    with sids pre-assigned in creation order so sampled streams are
    scheduling-invariant across engines and policies."""
    cfg = _get_model()[0]
    rng = np.random.default_rng(seed)
    out = []
    for i, (a, d, m) in enumerate(spec):
        r = Request(prompt=list(rng.integers(1, cfg.vocab_size, plen)),
                    max_new_tokens=m, deadline=d)
        r.arrives_at = a
        r.sid = i
        out.append(r)
    return out


# loose pair resident from round 0, tight burst at round 10 (while the
# loose pair is still mid-decode), one loose tail: EDF without
# preemption parks the burst behind the loose residents; preemption
# spills both residents and restores them after the burst drains
_BURST = [(0.0, 1000.0, 60), (0.0, 1001.0, 60),
          (10.0, 40.0, 8), (10.0, 41.0, 8), (0.0, 1004.0, 10)]


def _serve(eng, reqs):
    _round_clock(eng).serve_stream(list(reqs))
    if eng.allocator is not None:
        eng.release_prefix_cache()
        eng.allocator.assert_clean()
    return {r.sid: list(r.generated) for r in reqs}


# ================================================= policy-layer units
def test_weighted_edf_ordering():
    """wedf ranks by priority-relaxed deadline: a high-priority request
    beats an earlier plain deadline when the weight covers the gap."""
    pol = WeightedEdfAdmission(weight=10.0)
    a = Request(prompt=[1], deadline=20.0, priority=0)
    b = Request(prompt=[1], deadline=25.0, priority=1)   # 25-10 = 15
    c = Request(prompt=[1], deadline=None, priority=5)   # inf stays last
    assert pol.select([a, b, c], 0.0) == 1
    assert pol.select([a, c], 0.0) == 0
    # zero weight degenerates to plain EDF
    assert WeightedEdfAdmission(weight=0.0).select([a, b], 0.0) == 0


def test_preemption_policy_selects_loosest_victim():
    pol = DeadlinePreemption()
    cand = Request(prompt=[1], deadline=5.0)
    r1 = Request(prompt=[1], deadline=100.0)
    r2 = Request(prompt=[1], deadline=900.0)
    r3 = Request(prompt=[1], deadline=None)     # loosest of all
    assert pol.select_victim(cand, [(0, r1), (1, r2), (2, r3)], 0) == 2
    assert pol.select_victim(cand, [(0, r1), (1, r2)], 0) == 1
    # a candidate without a deadline never evicts anyone
    assert pol.select_victim(Request(prompt=[1]), [(0, r2)], 0) is None
    # no resident looser than the candidate -> decline
    tight = Request(prompt=[1], deadline=4.0)
    assert pol.select_victim(cand, [(0, tight)], 0) is None
    # margin: the win must exceed it
    assert DeadlinePreemption(margin=1000.0).select_victim(
        cand, [(0, r2)], 0) is None


def test_preemption_policy_respects_max_evictions():
    pol = DeadlinePreemption(max_evictions=2)
    cand = Request(prompt=[1], deadline=5.0)
    r = Request(prompt=[1], deadline=900.0)
    r.evictions = 2
    assert pol.select_victim(cand, [(0, r)], 0) is None
    r.evictions = 1
    assert pol.select_victim(cand, [(0, r)], 0) == 0


def test_shed_policy_units():
    now = 50.0
    live = Request(prompt=[1], deadline=90.0)
    dead = Request(prompt=[1], deadline=10.0)
    none = Request(prompt=[1])
    assert ExpiredShed().pick([live, dead, none], now) == [dead]
    assert PreemptionPolicy().shed.pick([dead], now) == []
    # queue-depth shed drops the loosest beyond the bound
    q = [Request(prompt=[1], deadline=float(d)) for d in (5, 99, 40)]
    picked = QueueDepthShed(depth=2).pick(q, now)
    assert picked == [q[1]]
    assert QueueDepthShed(depth=8).pick(q, now) == []


def test_spill_store_units():
    store = paging.SpillStore()
    assert not store and len(store) == 0
    r1, r2 = Request(prompt=[1]), Request(prompt=[2])
    store.put(paging.SpilledLane(r1, {"x": 1}, 3))
    store.put(paging.SpilledLane(r2, {"x": 2}, 0))
    with pytest.raises(AssertionError):
        store.put(paging.SpilledLane(r1, {}, 0))     # double spill
    assert [e.request is r for e, r in zip(store.pending(), (r1, r2))] \
        == [True, True]
    e = store.pop(r1.rid)
    assert e.pages == 3 and store.restores == 1
    store.drop(r2.rid)
    assert store.dropped == 1 and not store
    assert store.spills == 2


def test_allocator_spill_lane_accounting():
    a = paging.PageAllocator(16, 8, 4, 64)
    assert a.reserve(0, 20)                       # 3 pages
    assert a.lane_pages(0) == 3
    assert a.spill_lane(0) == 3
    assert a.spilled_pages == 3
    assert a.lane_pages(0) == 0 and a.pages_in_use == 0
    a.assert_clean()


# ====================================== engine guards + null parity
def test_preempt_requires_superstep_mode():
    cfg, params, dcfg, dparams = _get_model()
    with pytest.raises(ValueError, match="superstep"):
        ServingEngine(cfg, params, dcfg, dparams,
                      config=ServingConfig(batch_size=2, max_len=96,
                                           superstep_rounds=0,
                                           preempt="deadline"))


def test_preempt_enabled_idle_is_byte_identical():
    """A preemption-enabled engine on a trace that never overloads must
    be indistinguishable from the baseline: same streams, same round
    stamps, zero preemption activity."""
    spec = [(0.0, 1000.0, 8), (0.0, 1001.0, 8), (0.0, 1002.0, 6)]
    kw = dict(admission="deadline", admission_lookahead=4,
              gate_arrivals=True)
    base = _cached_engine(**kw)
    a = _trace(spec)
    _serve(base, a)
    eng = _cached_engine(**kw, preempt="deadline")
    b = _trace(spec)
    _serve(eng, b)
    assert eng.stats.preemptions == 0 and eng.stats.restores == 0
    for ra, rb in zip(a, b):
        assert rb.generated == ra.generated
        assert (rb.admit_round, rb.first_token_round, rb.finish_round) \
            == (ra.admit_round, ra.first_token_round, ra.finish_round)


# ========================= spill/restore end-to-end byte parity
@pytest.mark.parametrize(
    "greedy", [True, pytest.param(False, marks=pytest.mark.slow)])
@pytest.mark.parametrize(
    "page_size", [0, pytest.param(16, marks=pytest.mark.slow)])
def test_preempt_restore_stream_parity(greedy, page_size):
    """The tentpole pin: a tight-deadline burst preempts loose resident
    lanes (spill to host), the burst drains, the victims restore and
    resume mid-stream — and every stream is byte-identical to the
    never-evicted baseline, with zero leaked pages."""
    kw = dict(greedy=greedy, admission="deadline", admission_lookahead=4,
              gate_arrivals=True, page_size=page_size,
              num_pages=12 if page_size else 0)
    base = _serve(_cached_engine(**kw), _trace(_BURST))
    eng = _cached_engine(**kw, preempt="deadline")
    reqs = _trace(_BURST)
    out = _serve(eng, reqs)
    assert eng.stats.preemptions >= 1, "trace must force preemption"
    assert eng.stats.restores >= 1, "victims must restore mid-stream"
    assert out == base, "restored streams must be byte-identical"
    assert sum(r.evictions for r in reqs) == eng.stats.preemptions
    if page_size:
        assert eng.allocator.spilled_pages > 0
    # the preemption won: the burst's deadline-hit rate can only improve
    hits = lambda rs: sum(r.finish_round is not None
                          and r.finish_round <= r.deadline for r in rs)
    assert hits([r for r in reqs if r.deadline < 100]) == 2


def test_preempted_victim_finishing_in_flight_is_dropped():
    """A victim whose final tokens were already in flight at spill time
    finishes from that superstep's telemetry: the spill entry is
    dropped (never restored) and the request still routes to
    ``completed`` exactly once."""
    # small loose budgets: when the burst preempts, the in-flight
    # superstep often completes the victims while they sit parked (the
    # round clock only advances while lanes are busy, so the burst must
    # arrive before the loose pair can possibly drain: >= 1 token per
    # round makes round 2 safe for budget-10 lanes)
    spec = [(0.0, 1000.0, 10), (0.0, 1001.0, 10),
            (2.0, 30.0, 8), (2.0, 31.0, 8)]
    kw = dict(admission="deadline", admission_lookahead=4,
              gate_arrivals=True, preempt="deadline")
    eng = _cached_engine(**kw)
    reqs = _trace(spec)
    completed = _round_clock(eng).serve_stream(list(reqs))
    assert sorted(r.rid for r in completed) == sorted(r.rid for r in reqs)
    assert len(completed) == len(reqs)
    assert eng.stats.completed == len(reqs)
    assert all(len(r.generated) == r.max_new_tokens for r in reqs)


@pytest.mark.slow
@settings(max_examples=4, deadline=None)
@given(st.integers(6, 14), st.integers(40, 70), st.integers(0, 10 ** 6))
def test_preempt_parity_property(burst_round, loose_budget, seed):
    """Randomized overload traces (random burst timing, loose budgets,
    prompts): preemption-enabled serving stays byte-identical to the
    baseline, dense and paged, with clean allocators."""
    rng = np.random.default_rng(seed)
    spec = [(0.0, 1000.0, int(loose_budget)),
            (0.0, 1001.0, int(loose_budget)),
            (float(burst_round), 40.0, int(rng.integers(4, 10))),
            (float(burst_round), 41.0, int(rng.integers(4, 10))),
            (0.0, 1004.0, int(rng.integers(6, 14)))]
    for page_size in (0, 16):
        kw = dict(admission="deadline", admission_lookahead=4,
                  gate_arrivals=True, page_size=page_size,
                  num_pages=12 if page_size else 0)
        base = _serve(_cached_engine(**kw), _trace(spec, seed=seed % 97))
        eng = _cached_engine(**kw, preempt="deadline")
        out = _serve(eng, _trace(spec, seed=seed % 97))
        assert out == base


# ======================================================= load shedding
def test_expired_shed_drops_hopeless_requests():
    """Queued requests whose deadline already passed are dropped (shed
    flag + counter), finish with empty streams, and still route to
    ``completed``; survivors stream byte-identically."""
    spec = [(0.0, 1000.0, 30), (0.0, 1001.0, 30),
            (2.0, 4.0, 8),        # expires in queue long before a lane
            (0.0, 1002.0, 8)]
    kw = dict(admission="deadline", admission_lookahead=4,
              gate_arrivals=True)
    base_reqs = _trace(spec)
    _serve(_cached_engine(**kw), base_reqs)
    eng = _cached_engine(**kw, shed="expired")
    reqs = _trace(spec)
    completed = _round_clock(eng).serve_stream(list(reqs))
    assert eng.stats.shed_requests == 1
    shed = [r for r in reqs if r.shed]
    assert [r.sid for r in shed] == [2]
    assert shed[0].generated == [] and shed[0].finish_round is not None
    assert len(completed) == len(reqs)
    for rb, ra in zip(reqs, base_reqs):
        if not rb.shed:
            assert rb.generated == ra.generated


def test_queue_depth_shed_bounds_backlog():
    spec = ([(0.0, 1000.0, 24), (0.0, 1001.0, 24)]
            + [(4.0, 500.0 + i, 6) for i in range(6)])
    eng = _cached_engine(admission="deadline", admission_lookahead=8,
                         gate_arrivals=True, shed="queue",
                         shed_queue_depth=2)
    reqs = _trace(spec)
    _round_clock(eng).serve_stream(list(reqs))
    assert eng.stats.shed_requests > 0
    # the loosest deadlines shed first
    shed = sorted(r.deadline for r in reqs if r.shed)
    kept = sorted(r.deadline for r in reqs if not r.shed and r.sid >= 2)
    assert not kept or not shed or min(shed) >= max(kept)


# ============================================= clock-domain regression
def test_engine_single_clock_domain():
    """The clock-domain bugfix: with a fake clock injected, every
    latency stamp (admit/first-token/finish, scheduler re-anchored
    arrival, wall_s) lives in the fake domain — no stamp may leak from
    ``time.perf_counter``."""
    eng = _cached_engine(gate_arrivals=True)
    tick = {"t": 1000.0}

    def fake():
        tick["t"] += 1.0
        return tick["t"]

    eng._clock = fake
    cfg = _get_model()[0]
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(3):
        r = Request(prompt=list(rng.integers(1, cfg.vocab_size, 5)),
                    max_new_tokens=4)
        r.arrives_at = 0.0
        reqs.append(r)
    eng.serve_stream(list(reqs))
    for r in reqs:
        for stamp in (r.arrival_t, r.admit_t, r.first_token_t,
                      r.finish_t):
            assert stamp is not None and 1000.0 < stamp < 2000.0, (
                "stamp outside the fake clock domain: a wall-clock "
                f"read leaked into the latency path ({stamp})")
        assert r.ttft is not None and r.ttft >= 0.0
        assert r.latency is not None and r.latency >= 0.0
    assert 0.0 < eng.stats.wall_s < 1000.0


# ================================================ observability wiring
def test_overload_metrics_registered():
    eng = _cached_engine(admission="deadline", gate_arrivals=True,
                         admission_lookahead=4, preempt="deadline")
    _serve(eng, _trace(_BURST))
    snap = eng.metrics.snapshot()
    assert snap["serving.preemptions"] == eng.stats.preemptions >= 1
    assert snap["serving.restores"] == eng.stats.restores >= 1
    assert snap["serving.shed_requests"] == 0
    assert snap["serving.spilled_requests"] == 0    # all restored
    assert "paging.spilled_pages" in snap           # dense: zero gauge
    assert snap["paging.spilled_pages"] == 0


def test_config_make_policy_overload_wiring():
    cfg = ServingConfig(preempt="deadline", shed="queue",
                        shed_queue_depth=7)
    pol = cfg.make_policy()
    assert isinstance(pol.preemption, DeadlinePreemption)
    assert isinstance(pol.preemption.shed, QueueDepthShed)
    assert pol.preemption.shed.depth == 7
    base = ServingConfig().make_policy()
    assert type(base.preemption) is PreemptionPolicy
    assert not base.preemption.enabled
    rt = dataclasses.replace(cfg, preempt="none", shed="none")
    assert not rt.make_policy().preemption.enabled
