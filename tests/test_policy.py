"""Policy-driven serving control plane (serving/policy.py).

Covers the api_redesign checklist: admission-policy ordering units
(EDF, priority, FIFO ties), the admission-order permutation property
(any admission policy leaves greedy per-request streams byte-identical
— scheduling may change *when* a request is served, never *what* it
generates), eager-vs-cohort commit (round counts + deterministic
short-prompt TTFT), speculation park/resume via the acceptance probe,
deprecated-kwarg shims (byte parity with the new default
``ServingPolicy``), the unified ``ServingConfig`` plumbing through
``TideConfig``, and the ``trainer_threads`` contention knob.

Everything here runs on randomly initialized weights (policy behavior
is a property of the control plane, not the model), so the file stays
in the fast tier; the pretrained-fixture end-to-end parity suite at
the bottom carries the ``slow`` marker (see ROADMAP test tiers).
"""

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.configs as C
from repro.core import eagle
from repro.core.adaptive import AdaptiveDrafter, LatencyProfile
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.policy import (CohortCommit, DeadlineAdmission,
                                  EagerCommit, FifoAdmission,
                                  PriorityAdmission, ServingConfig,
                                  ServingPolicy, SpeculationPolicy)
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler

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


@pytest.fixture(scope="module")
def model():
    return _get_model()


_ENGINES = {}


def _cached_engine(**cfg_kw):
    """One engine per ServingConfig variant (compiles stay warm across
    tests and property examples); ``reset_adaptation`` restores the
    post-construction state between uses."""
    key = tuple(sorted(cfg_kw.items()))
    eng = _ENGINES.get(key)
    if eng is None:
        cfg, params, dcfg, dparams = _get_model()
        scfg = ServingConfig(batch_size=4, max_len=96, gamma=3, seed=5,
                             **cfg_kw)
        eng = _ENGINES[key] = ServingEngine(cfg, params, dcfg, dparams,
                                            config=scfg)
    eng.reset_adaptation(eng.dparams)
    eng.deploy_source = None
    return eng


def _requests(lens, budgets, seed=3, deadlines=None, prios=None):
    cfg = _get_model()[0]
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=list(rng.integers(1, cfg.vocab_size, L)),
                    max_new_tokens=m) for L, m in zip(lens, budgets)]
    if deadlines is not None:
        for r, d in zip(reqs, deadlines):
            r.deadline = d
    if prios is not None:
        for r, p in zip(reqs, prios):
            r.priority = p
    return reqs


# ================================================= admission ordering
def test_edf_ordering():
    """EDF admits earliest deadline first; None sorts last; deadline
    ties break on priority then FIFO order."""
    reqs = _requests([4] * 5, [2] * 5,
                     deadlines=[9.0, 1.0, None, 1.0, 4.0])
    reqs[3].priority = 1     # deadline tie with reqs[1] — priority wins
    s = Scheduler(2, reqs, policy=DeadlineAdmission())
    adm = s.admit()
    assert [r.rid for _, r in adm] == [reqs[3].rid, reqs[1].rid]
    for _, r in adm:
        r.finish()
    s.release_finished()
    adm2 = s.admit()
    assert [r.rid for _, r in adm2] == [reqs[4].rid, reqs[0].rid]
    for _, r in adm2:
        r.finish()
    s.release_finished()
    assert [r.rid for _, r in s.admit()] == [reqs[2].rid]   # None last


def test_priority_ordering_ties_fifo():
    reqs = _requests([4] * 4, [2] * 4, prios=[0, 2, 1, 2])
    s = Scheduler(1, reqs, policy=PriorityAdmission())
    order = []
    while s.has_pending():
        (slot, r), = s.admit()
        order.append(r.rid)
        r.finish()
        s.release_finished()
    assert order == [reqs[1].rid, reqs[3].rid, reqs[2].rid, reqs[0].rid]


def test_fifo_ignores_slo_annotations():
    """The default policy admits in arrival order no matter the
    annotations (SLO fields are free to carry everywhere)."""
    reqs = _requests([4] * 3, [2] * 3, deadlines=[1.0, 0.1, 0.5],
                     prios=[0, 9, 3])
    s = Scheduler(3, reqs)     # default FifoAdmission
    assert [r.rid for _, r in s.admit()] == [r.rid for r in reqs]


def test_reorder_policies_bound_materialization():
    """A reordering policy's lookahead window bounds how much of an
    unbounded stream is materialized."""
    pulled = []

    def gen():
        for i in range(100):
            pulled.append(i)
            yield Request(prompt=[1, 2], max_new_tokens=2)

    s = Scheduler(2, gen(), policy=PriorityAdmission(lookahead=4))
    s.admit()
    assert len(pulled) <= 6, "lookahead must bound the queue pull"


def test_edf_gated_arrivals_skip_unarrived():
    """Unlike strict-FIFO gating, EDF admits any *arrived* candidate —
    an unarrived head must not block an arrived tight-deadline one."""
    now = {"t": 0.0}
    reqs = [Request(prompt=[1, 2], max_new_tokens=2, arrives_at=t)
            for t in (5.0, 0.0)]
    reqs[1].deadline = 1.0
    s = Scheduler(1, reqs, policy=DeadlineAdmission(),
                  gate_arrivals=True, clock=lambda: now["t"])
    assert s.has_pending()
    (slot, r), = s.admit()
    assert r.rid == reqs[1].rid
    assert s.next_arrival_in() == pytest.approx(5.0)


# ============================== admission-order stream invariance
@pytest.mark.slow
@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_admission_permutation_stream_invariance(seed):
    """Property: under greedy decoding, ANY admission-order permutation
    (fifo / priority / deadline over random annotations) leaves every
    request's emitted token stream byte-identical — the load-bearing
    invariant that makes scheduling policy a pure performance knob."""
    rng = np.random.default_rng(seed)
    n = 7
    lens = [int(rng.integers(3, 20)) for _ in range(n)]
    budgets = [int(rng.integers(2, 12)) for _ in range(n)]
    deadlines = [float(rng.uniform(0, 50)) if rng.random() < 0.7 else None
                 for _ in range(n)]
    prios = [int(rng.integers(0, 4)) for _ in range(n)]

    streams = {}
    for name in ("fifo", "priority", "deadline"):
        eng = _cached_engine(admission=name)
        reqs = _requests(lens, budgets, seed=seed, deadlines=deadlines,
                         prios=prios)
        eng.serve_stream(reqs)
        streams[name] = [list(r.generated) for r in reqs]
        assert all(r.finish_t is not None for r in reqs)
    assert streams["priority"] == streams["fifo"], \
        f"priority admission changed a stream (seed={seed})"
    assert streams["deadline"] == streams["fifo"], \
        f"EDF admission changed a stream (seed={seed})"


# ======================================= commit policy: eager vs cohort
@pytest.mark.slow
def test_eager_vs_cohort_commit(model):
    """A short prompt co-admitted (mid-decode) with a long-tail sibling:
    cohort commit holds its lane until the long pipeline finishes —
    eager activates it as soon as its own chunk is staged — so the
    short's deterministic TTFT (rounds from admission) must drop under
    eager, at an executed-round-density cost.  Streams are
    byte-identical either way (greedy scheduling invariance).

    The scenario keeps two big-budget residents decoding while two
    early-retiring lanes free up for the mixed [long, short] refill —
    the refill must land mid-decode, because with no resident decoding
    both policies run chunks back-to-back to the next commit and the
    distinction vanishes (the cold-start fast path)."""
    lens = [6, 7, 5, 8, 72, 6, 9, 10]
    budgets = [40, 40, 8, 8, 6, 6, 6, 6]
    out = {}
    for commit in ("cohort", "eager"):
        eng = _cached_engine(prefill_chunk=16, commit=commit)
        reqs = _requests(lens, budgets)
        eng.serve_stream(reqs)
        short = reqs[5]           # co-admitted with the 72-token prompt
        out[commit] = ([list(r.generated) for r in reqs],
                       short.first_token_round - short.admit_round,
                       eng.stats.steps)
    assert out["eager"][0] == out["cohort"][0], \
        "commit policy changed per-request streams"
    assert out["eager"][1] < out["cohort"][1], \
        "eager commit did not improve the co-admitted short prompt's " \
        f"TTFT rounds (eager {out['eager'][1]} vs cohort " \
        f"{out['cohort'][1]})"
    assert out["eager"][2] >= out["cohort"][2], \
        "cohort commit lost its round-density advantage"


def test_commit_policy_refill_groups_delegation():
    """CommitPolicy.refill_groups defaults to the scheduler's per-width
    bucketing (the grouping the chunk pipelines are built from)."""
    reqs = _requests([6, 40, 7, 38], [4] * 4)
    admitted = list(enumerate(reqs))
    for pol in (CohortCommit(), EagerCommit()):
        groups = pol.refill_groups(admitted, 16)
        assert groups == Scheduler.refill_groups(admitted, 16)
    assert CohortCommit().cohort and not EagerCommit().cohort


# ===================================== speculation park / resume probe
# threshold ≈ 2.0 at every batch size: with a near-zero-acceptance
# draft the EMA decays below it and the Eq. 5 gate turns speculation
# off — the latch-off state the park control exists for
_FLAT_PROFILE = LatencyProfile([1, 2, 4, 8], [1.0, 1.0, 1.0, 1.0],
                               d0_ms=0.33)


def test_park_resume_unit():
    pol = SpeculationPolicy(AdaptiveDrafter(_FLAT_PROFILE, gamma=3),
                            park_patience=3, probe_interval=4)
    pol.prepare(4)
    gate, park, probe = pol._tables
    assert pol.dispatch_table() is gate
    # three consecutive gated-off rounds -> parked
    for _ in range(3):
        pol.observe_round(4, 1.0, use_spec=False)
    assert pol.parked and pol.parks == 1
    assert not pol.blocks_capture or pol.parked   # capture parks too
    # parked dispatches serve the never-speculate table, except every
    # probe_interval-th which forces speculation (the acceptance probe)
    tables = [pol.dispatch_table() for _ in range(4)]
    assert all(t is park for t in tables[:3])
    assert tables[3] is probe and pol.probing
    # a probe that still measures low acceptance leaves it parked...
    pol.observe_round(4, 1.0, use_spec=True)
    assert pol.parked
    # ...a probe whose refreshed EMA clears the Eq. 5 gate resumes
    for _ in range(4):
        pol.dispatch_table()
    assert pol.probing
    pol.observe_round(4, 2.5, use_spec=True)
    assert not pol.parked and pol.resumes == 1
    assert pol.dispatch_table() is gate
    assert not pol.blocks_capture
    # park control refuses to run blind (no Eq. 5 profile to probe)
    with pytest.raises(ValueError, match="park"):
        SpeculationPolicy(None, park_patience=2).prepare(4)


@pytest.mark.slow
def test_park_engine_integration(model):
    """End-to-end park: a drafter whose break-even threshold the
    observed acceptance can never clear gates speculation off, the
    policy parks after ``park_patience`` rounds, signal capture parks
    with it, and forced-speculation probes keep firing at the probe
    cadence (spec rounds while parked == probes).  Streams match the
    default engine's byte for byte — park only moves work, greedy
    verification fixes the tokens."""
    from repro.core.signals import SignalExtractor, SignalStore

    cfg, params, dcfg, dparams = model
    lens, budgets = [8, 6, 9, 7] * 3, [12] * 12
    ref = _cached_engine()
    ref_reqs = _requests(lens, budgets)
    ref.serve_stream(ref_reqs)

    scfg = ServingConfig(batch_size=4, max_len=96, gamma=3, seed=5,
                         spec_park_patience=2, spec_probe_interval=3)
    store = SignalStore()
    eng = ServingEngine(cfg, params, dcfg, dparams, config=scfg,
                        drafter=AdaptiveDrafter(_FLAT_PROFILE, gamma=3),
                        extractor=SignalExtractor(store, window=16))
    eng.accept_ema = 1.0       # below the ~2.0 threshold from round one
    reqs = _requests(lens, budgets)
    eng.serve_stream(reqs)
    pol = eng.policy.speculation
    assert pol.parks >= 1 and pol.parked, \
        "engine never parked under a hopeless Eq. 5 gate"
    assert eng.extractor.enabled is False, "capture did not park"
    # speculative rounds after the park are exactly the probes
    assert eng.stats.spec_steps < eng.stats.steps
    assert [list(r.generated) for r in reqs] == \
        [list(r.generated) for r in ref_reqs], \
        "park control changed token streams"
    # resume must restore capture even with no controller to re-drive
    # ``extractor.enabled`` (the park control owns it then); pin the
    # policy un-parked so the hopeless gate can't immediately re-park
    pol.parked = False
    pol._idle = -10 ** 9
    more = _requests([6, 5, 8, 7], [8] * 4, seed=9)
    eng.serve_stream(more)
    assert eng.extractor.enabled is True, \
        "capture not restored after speculation resumed"


def test_park_stepwise_mode(model):
    """The per-step reference loop runs the same park/probe schedule
    through ``step_decision`` — and still emits identical streams."""
    cfg, params, dcfg, dparams = model
    scfg = ServingConfig(batch_size=2, max_len=96, gamma=3, seed=5,
                         superstep_rounds=0, spec_park_patience=2,
                         spec_probe_interval=3)
    eng = ServingEngine(cfg, params, dcfg, dparams, config=scfg,
                        drafter=AdaptiveDrafter(_FLAT_PROFILE, gamma=3))
    eng.accept_ema = 1.0
    reqs = _requests([7, 5], [16, 16])
    eng.serve_stream(reqs)
    assert eng.policy.speculation.parks >= 1
    ref_reqs = _requests([7, 5], [16, 16])
    eng2 = ServingEngine(cfg, params, dcfg, dparams,
                         config=ServingConfig(batch_size=2, max_len=96,
                                              gamma=3, seed=5,
                                              superstep_rounds=0))
    eng2.serve_stream(ref_reqs)
    assert [list(r.generated) for r in reqs] == \
        [list(r.generated) for r in ref_reqs]


# ================================================ deprecated-kwarg shims
@pytest.mark.slow
def test_deprecated_kwargs_warn_and_match_policy_path(model):
    """The legacy control kwargs still work (DeprecationWarning) and
    are byte-identical to the new default ServingPolicy/ServingConfig
    path: streams, stats, completion-sink delivery."""
    cfg, params, dcfg, dparams = model
    lens = [40, 6, 9, 7, 5, 30, 4, 8]
    budgets = [6, 9, 4, 8, 7, 5, 6, 4]

    sink_old, sink_new = [], []
    with pytest.warns(DeprecationWarning):
        eng_old = ServingEngine(
            cfg, params, dcfg, dparams, batch_size=4, max_len=96,
            gamma=3, seed=5, prefill_chunk=16,
            completion_sink=sink_old.append)
    eng_new = ServingEngine(
        cfg, params, dcfg, dparams,
        config=ServingConfig(batch_size=4, max_len=96, gamma=3, seed=5,
                             prefill_chunk=16,
                             completion_sink=sink_new.append))
    r_old = _requests(lens, budgets)
    r_new = _requests(lens, budgets)
    eng_old.serve_stream(r_old)
    eng_new.serve_stream(r_new)
    assert [list(r.generated) for r in r_old] == \
        [list(r.generated) for r in r_new]
    assert [r.rid - r_old[0].rid for r in sink_old] == \
        [r.rid - r_new[0].rid for r in sink_new]
    for f in ("tokens_out", "steps", "spec_steps", "refills",
              "prefill_chunks", "prefill_row_tokens", "completed"):
        assert getattr(eng_old.stats, f) == getattr(eng_new.stats, f), f
    assert eng_old.accept_ema == eng_new.accept_ema


def test_gate_arrivals_kwarg_warns(model):
    cfg, params, dcfg, dparams = model
    with pytest.warns(DeprecationWarning, match="gate_arrivals"):
        eng = ServingEngine(cfg, params, dcfg, dparams, batch_size=2,
                            max_len=96, gate_arrivals=True)
    assert eng.gate_arrivals and eng.config.gate_arrivals


# ================================================== unified ServingConfig
def test_serving_config_tide_mirror():
    from repro.core.tide import TideConfig

    tc = TideConfig(serving=ServingConfig(batch_size=8, admission="deadline",
                                          commit="eager", prefill_chunk=16,
                                          trainer_threads=2))
    # legacy flat fields mirror the unified config
    assert tc.batch_size == 8 and tc.prefill_chunk == 16
    assert tc.admission == "deadline" and tc.commit == "eager"
    assert tc.trainer_threads == 2
    # and the flat convenience layer still assembles a ServingConfig
    tc2 = TideConfig(batch_size=2, prefill_chunk=8, admission="priority")
    assert tc2.serving.batch_size == 2
    assert tc2.serving.prefill_chunk == 8
    assert tc2.serving.admission == "priority"

    pol = tc.serving.make_policy()
    assert isinstance(pol.admission, DeadlineAdmission)
    assert isinstance(pol.commit, EagerCommit)
    assert isinstance(pol, ServingPolicy)
    with pytest.raises(KeyError):
        ServingConfig(admission="nope").make_policy()

    # dataclasses.replace on a constructed TideConfig must honor a
    # replaced flat field (post-construction, serving is always set)
    import dataclasses as dc
    tc3 = dc.replace(tc, batch_size=16)
    assert tc3.batch_size == 16 and tc3.serving.batch_size == 16
    assert tc3.serving.commit == "eager"      # untouched fields mirror
    # an explicit non-default flat field overrides the serving config
    tc4 = TideConfig(gamma=5,
                     serving=ServingConfig(batch_size=8))
    assert tc4.gamma == 5 and tc4.serving.gamma == 5
    assert tc4.batch_size == 8


def test_engine_config_attr_propagation(model):
    cfg, params, dcfg, dparams = model
    scfg = ServingConfig(batch_size=2, max_len=64, gamma=2, greedy=False,
                         superstep_rounds=4, seed=9, prefill_chunk=8)
    eng = ServingEngine(cfg, params, dcfg, dparams, config=scfg)
    assert (eng.batch, eng.max_len, eng.gamma) == (2, 64, 2)
    assert not eng.greedy and eng.superstep_rounds == 4
    assert eng.prefill_chunk == 8
    assert isinstance(eng.policy.admission, FifoAdmission)
    # engine takes a private copy: caller mutation can't skew it
    scfg.prefill_chunk = 0
    assert eng.config.prefill_chunk == 8
    # a knob kwarg passed alongside config= would be silently ignored —
    # it must fail loudly instead
    with pytest.raises(ValueError, match="knob kwargs"):
        ServingEngine(cfg, params, dcfg, dparams,
                      config=ServingConfig(), greedy=False)


def test_workload_slo_annotations():
    from repro.data.workloads import arrival_trace, make_domains

    domains = make_domains(97, ["a", "b"], seed=1)
    trace = arrival_trace(domains, 40, mode="poisson", rate=8.0,
                          deadline_slack=(10.0, 20.0), tight_frac=0.5,
                          tight_slack=(0.1, 0.5), priority_levels=3,
                          seed=2)
    slacks = [ev.deadline - ev.t for ev in trace]
    assert all(d > 0 for d in slacks)
    assert any(d <= 0.5 for d in slacks) and any(d >= 10.0 for d in slacks)
    assert {ev.priority for ev in trace} <= {0, 1, 2}
    assert len({ev.priority for ev in trace}) > 1
    # FIFO replay of an annotated trace is unchanged
    plain = arrival_trace(domains, 40, mode="poisson", rate=8.0, seed=2)
    assert [ev.prompt for ev in trace] == [ev.prompt for ev in plain]
    assert [ev.t for ev in trace] == [ev.t for ev in plain]


# ================================================= trainer_threads knob
def test_trainer_threads_knob(model):
    import time as _time

    from repro.checkpoint.ckpt import DraftDeployGate
    from repro.core.transport import SignalChannel
    from repro.training.draft_trainer import DraftTrainer
    from repro.training.service import TrainingService

    cfg, params, dcfg, dparams = model
    svc = TrainingService(DraftTrainer(cfg, dcfg, params["embed"]),
                          DraftDeployGate(dparams), SignalChannel(8),
                          n_threshold=1, signal_window=1,
                          trainer_threads=2)
    assert svc.stats()["trainer_threads"] == 2
    svc.start()
    try:
        for _ in range(100):          # wait for the loop to stamp the cap
            if svc.stats()["thread_cap"] is not None:
                break
            _time.sleep(0.01)
        # on this Linux container per-thread deprioritization must
        # engage (raising one's own nice needs no privilege)
        assert svc.stats()["thread_cap"] == "thread_nice"
    finally:
        svc.close()
    # 0 = unpinned: no cap recorded
    svc0 = TrainingService(DraftTrainer(cfg, dcfg, params["embed"]),
                           DraftDeployGate(dparams), SignalChannel(8),
                           n_threshold=1, signal_window=1)
    assert svc0.stats()["thread_cap"] is None


# ===================== pretrained end-to-end parity suite (slow tier)
@pytest.fixture(scope="module")
def pretrained():
    from repro.data.workloads import make_domains, training_corpus
    from repro.training.trainer import pretrain_target

    cfg = C.get("tide-tiny")
    params = T.init(cfg, jax.random.key(0))
    domains = make_domains(cfg.vocab_size, ["science"], branchings=[2],
                           seed=3)
    corpus = training_corpus(domains["science"], 64, 40, 1)
    params, _ = pretrain_target(cfg, params, corpus, steps=80, lr=3e-3)
    dcfg = eagle.draft_config(cfg)
    dparams = eagle.draft_init(dcfg, jax.random.key(7))
    return cfg, params, dcfg, dparams, domains


@pytest.mark.slow
@pytest.mark.parametrize("greedy", [True, False])
def test_default_policy_parity_pretrained(pretrained, greedy):
    """Acceptance gate: the default ServingPolicy (FIFO + cohort +
    Eq. 5 gate) is bitwise-identical to the pre-redesign kwarg path on
    a realistic pretrained engine — streams (greedy AND sampled),
    stats, accept-EMA, and SignalStore contents."""
    from repro.core.controller import TrainingController
    from repro.core.signals import SignalExtractor, SignalStore

    cfg, params, dcfg, dparams, domains = pretrained
    rng = np.random.default_rng(4)
    prompts = [domains["science"].sample_prompt(rng) for _ in range(10)]
    budgets = [int(b) for b in
               np.random.default_rng(5).integers(4, 28, size=10)]

    def _serve(use_config):
        store = SignalStore()
        ctrl = TrainingController(n_init=4, n_threshold=64)
        ctrl.collection_enabled = True
        kw = dict(controller=ctrl,
                  extractor=SignalExtractor(store, window=16),
                  drafter=AdaptiveDrafter(_FLAT_PROFILE, gamma=3))
        if use_config:
            eng = ServingEngine(cfg, params, dcfg, dparams,
                                config=ServingConfig(
                                    batch_size=4, max_len=96, gamma=3,
                                    seed=5, greedy=greedy), **kw)
        else:
            eng = ServingEngine(cfg, params, dcfg, dparams, batch_size=4,
                                max_len=96, gamma=3, seed=5,
                                greedy=greedy, **kw)
        eng.accept_ema = 3.0          # decays through the Eq. 5 gate
        reqs = [Request(prompt=list(p), max_new_tokens=m)
                for p, m in zip(prompts, budgets)]
        eng.serve_stream(reqs)
        sigs = [(b.tokens.tobytes(), b.feats.tobytes())
                for b in store.drain()]
        return [list(r.generated) for r in reqs], sigs, eng

    g_kw, s_kw, e_kw = _serve(use_config=False)
    g_cf, s_cf, e_cf = _serve(use_config=True)
    assert g_cf == g_kw, "default-policy streams diverged from kwargs"
    assert s_cf == s_kw, "default-policy SignalStore diverged"
    assert e_cf.accept_ema == e_kw.accept_ema
    for f in ("tokens_out", "steps", "spec_steps", "refills",
              "dispatches", "completed"):
        assert getattr(e_cf.stats, f) == getattr(e_kw.stats, f), f
