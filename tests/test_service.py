"""Decoupled draft-training subsystem: transport, service, deploys.

Covers the new-subsystem checklist: SignalChannel overflow/drop-oldest
and blocking/close semantics, deploy-version monotonicity through the
gate, ``service.drain()`` parity with the legacy synchronous
``TideSystem`` training schedule (hand-rolled reference), deploy-time
draft-cache re-seed (idempotence + acceptance effect), arrival gating /
idle supersteps, bounded stats (Ring + P² sketch), the scheduler
completion sink, and clean thread shutdown.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Pretrained-fixture-heavy end-to-end parity suite: slow tier (the
# fast smoke loop runs `pytest -m "not slow"`; see ROADMAP.md).
pytestmark = pytest.mark.slow

import repro.configs as C
from repro.core import eagle
from repro.core import speculative as spec
from repro.core.signals import SignalBatch
from repro.core.tide import TideConfig, TideSystem
from repro.core.transport import SignalChannel
from repro.data.workloads import make_domains, training_corpus
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler
from repro.serving.stats import P2Quantile, Ring
from repro.training.service import DraftVersion, TrainingService


@pytest.fixture(scope="module")
def pretrained():
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


def _batch(i, s=8, f=6):
    return SignalBatch(feats=np.full((s, f), i, np.float32),
                       tokens=np.full((s,), i, np.int32))


# ================================================== SignalChannel
def test_channel_overflow_drop_oldest():
    ch = SignalChannel(capacity=4)
    for i in range(7):
        ch.add(_batch(i))
    assert ch.peek_count() == 4
    assert ch.dropped == 3
    assert ch.total_added == 7
    kept = [int(b.tokens[0]) for b in ch.drain()]
    assert kept == [3, 4, 5, 6], "must keep the freshest batches"
    st = ch.stats()
    assert st["pushed"] == 7 and st["dropped"] == 3 and st["depth"] == 0


def test_channel_wait_and_close_wakes_consumer():
    ch = SignalChannel(capacity=8)
    got = {}

    def consumer():
        got["n"] = ch.wait(min_count=2, timeout=5.0)

    t = threading.Thread(target=consumer)
    t.start()
    ch.add(_batch(0))
    ch.add(_batch(1))
    t.join(timeout=5.0)
    assert not t.is_alive() and got["n"] == 2

    # a consumer blocked on an impossible count must be woken by close
    t2 = threading.Thread(target=lambda: ch.wait(min_count=99,
                                                 timeout=10.0))
    t2.start()
    time.sleep(0.05)
    ch.close()
    t2.join(timeout=2.0)
    assert not t2.is_alive(), "close() must wake blocked waiters"


def test_channel_add_after_close_drops_and_counts():
    """The shutdown bugfix: a straggling producer (a superstep unpacked
    after service shutdown) must not grow a ring nobody drains — the
    closed channel drops the batch and counts it, and the drained set
    stays exactly the pre-close buffer."""
    ch = SignalChannel(capacity=8)
    ch.add(_batch(0))
    ch.add(_batch(1))
    ch.close()
    ch.add(_batch(2))            # post-close: dropped, not buffered
    ch.add(_batch(3))
    assert ch.peek_count() == 2
    assert ch.rejected_after_close == 2
    assert ch.stats()["rejected_after_close"] == 2
    kept = [int(b.tokens[0]) for b in ch.drain()]
    assert kept == [0, 1], "drain must see exactly the pre-close batches"
    assert ch.drain() == []      # deterministic: later drains are empty
    ch.add(_batch(4))
    assert ch.drain() == [] and ch.rejected_after_close == 3
    # total_added never counts rejected batches
    assert ch.total_added == 2


def test_channel_reset_clears_rejection_counter():
    ch = SignalChannel(capacity=4)
    ch.close()
    ch.add(_batch(0))
    assert ch.rejected_after_close == 1
    ch.reset()
    assert ch.rejected_after_close == 0 and ch.peek_count() == 0


def test_service_rejects_starving_channel(pretrained):
    """A per-cycle threshold the bounded channel can never buffer must
    fail loudly at construction, not silently never train."""
    cfg, params, dcfg, dparams, _ = pretrained
    from repro.checkpoint.ckpt import DraftDeployGate
    from repro.training.draft_trainer import DraftTrainer

    with pytest.raises(ValueError, match="starve"):
        TrainingService(DraftTrainer(cfg, dcfg, params["embed"]),
                        DraftDeployGate(dparams),
                        SignalChannel(capacity=4),
                        n_threshold=100, signal_window=10)


# ================================================== deploy versioning
def test_deploy_version_monotonic(pretrained):
    cfg, params, dcfg, dparams, _ = pretrained
    from repro.checkpoint.ckpt import DraftDeployGate
    from repro.training.draft_trainer import DraftTrainer

    gate = DraftDeployGate(dparams)
    ch = SignalChannel(capacity=8)
    svc = TrainingService(DraftTrainer(cfg, dcfg, params["embed"]), gate,
                          ch, n_threshold=1, signal_window=1,
                          train_epochs=1, train_min_steps=2)
    assert svc.poll() is None
    # publish through the gate path directly: accepted offers bump seq
    gate.offer(dparams, 0.5, 0.1)
    svc._latest = DraftVersion(gate.version, dparams, 0.5)
    v1 = svc.poll()
    assert v1.seq == 1
    # a losing offer must not advance the version
    assert not gate.offer(dparams, 0.05, 0.5)
    assert gate.version == 1
    gate.offer(dparams, 0.9, 0.1)
    svc._latest = DraftVersion(gate.version, dparams, 0.9)
    assert svc.poll().seq == 2 > v1.seq


# ====================================== drain() parity vs legacy sync
def _waves(domains, n_waves, batch, seed=1, max_new=24):
    rng = np.random.default_rng(seed)
    return [[("science", domains["science"].sample_prompt(rng))
             for _ in range(batch)] for _ in range(n_waves)]


_TCFG = dict(gamma=3, batch_size=2, max_len=96, adaptive_spec=False,
             selective_training=True, signal_window=8, n_threshold=4,
             train_epochs=1, train_min_steps=6, seed=0)


def _legacy_maybe_train(sys_: TideSystem, events):
    """The pre-service synchronous trainer, verbatim (old
    ``TideSystem._maybe_train``), driving the same components."""
    tcfg = sys_.tcfg
    need = sys_.store.peek_count() * tcfg.signal_window
    if need < sys_.controller.n_threshold:
        return
    batches = sys_.store.drain()
    baseline = sys_.controller.alpha_train
    dparams, _ = sys_.gate.current()
    result = sys_.trainer.train_cycle(dparams, batches,
                                      epochs=tcfg.train_epochs,
                                      min_steps=tcfg.train_min_steps,
                                      seed=tcfg.seed)
    deployed = sys_.gate.offer(result["dparams"], result["eval_acc"],
                               baseline)
    if tcfg.selective_training:
        sys_.controller.training_result(result["eval_acc"])
    if deployed:
        sys_.engine.deploy_draft(result["dparams"])
    events.append({
        "kind": "train_cycle", "eval_acc": result["eval_acc"],
        "train_acc": result["train_acc"], "baseline": baseline,
        "deployed": deployed, "steps": result["steps"],
        "engine_steps": sys_.engine.stats.steps,
    })


def _strip(events):
    return [{k: v for k, v in e.items() if k != "seconds"}
            for e in events]


def test_drain_parity_with_legacy_synchronous(pretrained):
    """The service-based sync mode must reproduce the legacy blocking
    scheduler byte-for-byte: token streams, deploy versions, and the
    train-cycle event stream (timing excluded)."""
    cfg, params, dcfg, dparams, domains = pretrained
    waves = _waves(domains, 4, 2)

    ref = TideSystem(cfg, params, TideConfig(**_TCFG), dparams=dparams)
    ref_events = []
    ref_done = []
    for wave in waves:
        reqs = [Request(prompt=list(p), domain=d, max_new_tokens=24)
                for d, p in wave]
        ref.engine.serve_wave(reqs)
        ref_done.extend(reqs)
        _legacy_maybe_train(ref, ref_events)

    new = TideSystem(cfg, params, TideConfig(**_TCFG), dparams=dparams)
    new_done = new.run(iter(waves), max_new_tokens=24)

    assert [r.generated for r in new_done] == \
        [r.generated for r in ref_done]
    assert len(ref_events) >= 1, "scenario never trained"
    assert _strip(new.events) == ref_events
    assert new.gate.version == ref.gate.version
    assert new.summary()["train_cycles"] == len(ref_events)


def test_reset_adaptation_reproduces_run(pretrained):
    """reset_adaptation must restore the post-construction adaptive
    state exactly: a re-run emits identical events and streams."""
    cfg, params, dcfg, dparams, domains = pretrained
    waves = _waves(domains, 3, 2)
    sys_ = TideSystem(cfg, params, TideConfig(**_TCFG), dparams=dparams)
    a = sys_.run(iter(waves))
    ev_a = _strip(sys_.events)
    assert len(ev_a) >= 1
    sys_.reset_adaptation()
    b = sys_.run(iter(waves))
    assert [r.generated for r in b] == [r.generated for r in a]
    assert _strip(sys_.events) == ev_a


# ====================================== async service end-to-end
def test_async_service_trains_and_streams_match(pretrained):
    """Async mode: identical greedy token streams, training happens on
    the background thread, deploys version monotonically, shutdown is
    clean (no dangling thread)."""
    cfg, params, dcfg, dparams, domains = pretrained
    waves = _waves(domains, 4, 2)
    reqs_of = lambda: iter([Request(prompt=list(p), domain=d,
                                    max_new_tokens=24)
                            for wave in waves for d, p in wave])

    sync = TideSystem(cfg, params, TideConfig(**_TCFG), dparams=dparams)
    done_sync = sync.run_stream(reqs_of())

    tc = TideConfig(**_TCFG, async_train=True, reseed_window=16)
    asy = TideSystem(cfg, params, tc, dparams=dparams)
    assert asy.service.running
    done_asy = asy.run_stream(reqs_of())
    # settle whatever the thread had not consumed by stream end
    asy.service.drain()
    assert asy.service.cycles >= 1, "async service never trained"
    assert asy.gate.version >= 1
    # per-request greedy streams are training-schedule-invariant
    # (completion *order* may differ — deploys change round counts)
    assert sorted((tuple(r.prompt), tuple(r.generated))
                  for r in done_asy) == \
        sorted((tuple(r.prompt), tuple(r.generated))
               for r in done_sync)
    thread = asy.service._thread
    asy.close()
    assert not asy.service.running
    assert thread is None or not thread.is_alive(), \
        "service thread still alive after close()"
    asy.close()          # idempotent


# ====================================== deploy re-seed (capture ring)
def _engine(pretrained, **kw):
    cfg, params, dcfg, dparams, domains = pretrained
    kw.setdefault("batch_size", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("superstep_rounds", 8)
    dp = kw.pop("dparams", dparams)
    return ServingEngine(cfg, params, dcfg, dp, gamma=3, seed=5, **kw)


def _reqs(pretrained, budgets, seed=0):
    domains = pretrained[4]
    rng = np.random.default_rng(seed)
    return [Request(prompt=domains["science"].sample_prompt(rng),
                    max_new_tokens=m) for m in budgets]


def test_reseed_idempotent_same_draft(pretrained):
    """Re-seeding with the *same* draft params must leave the draft
    cache bit-identical on the window (the re-seed recomputes exactly
    what serving computed)."""
    cfg, params, dcfg, dparams, domains = pretrained
    eng = _engine(pretrained, reseed_window=16,
                  deploy_source=lambda: None)
    reqs = _reqs(pretrained, (40, 40))
    sched = Scheduler(2, reqs)
    adm = sched.admit()
    eng._assign_sids(adm)
    cache, dcache, carry, first = eng._prologue(reqs)
    state = spec.init_superstep_state(carry, first, eng._base_key,
                                      sids=eng._slot_sids(reqs),
                                      capture_window=eng.reseed_window)
    mx = jnp.asarray([40, 40], jnp.int32)
    out = eng._superstep_fn(eng.params, eng.dparams, cache, dcache,
                            state, mx)
    dcache, state = out["dcache"], out["state"]
    assert int(np.asarray(state.cap_count).min()) > 0
    keep = {k: jnp.array(v) for k, v in dcache.items()}
    dc2 = eng._reseed_fn(eng.dparams, keep, state)
    np.testing.assert_array_equal(np.asarray(dc2["k"]),
                                  np.asarray(dcache["k"]))
    np.testing.assert_array_equal(np.asarray(dc2["v"]),
                                  np.asarray(dcache["v"]))


def test_reseed_matches_new_draft_serving(pretrained):
    """Re-seed-on-deploy acceptance semantics: after deploying draft B
    onto lanes served so far by draft A, the re-seeded window of the
    draft cache must equal — position for position — the cache an
    engine serving with draft B *from the start* holds.  (Greedy
    commits are draft-invariant, so both engines ingest the identical
    (feature, token) pair sequence; draft K/V is a pure per-position
    function of pair and position.)  The new draft's acceptance on
    resident lanes is then exactly its from-scratch acceptance over the
    window."""
    cfg, params, dcfg, dparams, domains = pretrained
    draft_b = eagle.draft_init(dcfg, jax.random.key(99))

    def _drive(dp, window):
        eng = _engine(pretrained, reseed_window=window, dparams=dp,
                      deploy_source=lambda: None)
        reqs = _reqs(pretrained, (64, 64), seed=4)
        sched = Scheduler(2, reqs)
        eng._assign_sids(sched.admit())
        cache, dcache, carry, first = eng._prologue(reqs)
        state = spec.init_superstep_state(
            carry, first, eng._base_key, sids=eng._slot_sids(reqs),
            capture_window=window)
        mx = jnp.asarray([64, 64], jnp.int32)
        for _ in range(3):
            out = eng._superstep_fn(eng.params, eng.dparams, cache,
                                    dcache, state, mx)
            cache, dcache, state = (out["cache"], out["dcache"],
                                    out["state"])
        return eng, dcache, state

    eng_a, dcache_a, state_a = _drive(dparams, 24)     # served by A
    eng_b, dcache_b, state_b = _drive(draft_b, 24)     # served by B

    # snapshot before the re-seed donates (consumes) A's cache buffers
    k_a = np.array(dcache_a["k"])
    # deploy B onto A's lanes and re-seed from the ring
    reseeded = eng_a._reseed_fn(draft_b, dcache_a, state_a)

    k_r, v_r = np.asarray(reseeded["k"]), np.asarray(reseeded["v"])
    k_b, v_b = np.asarray(dcache_b["k"]), np.asarray(dcache_b["v"])
    len_a = np.asarray(reseeded["lengths"])
    len_b = np.asarray(dcache_b["lengths"])
    n = np.minimum(np.asarray(state_a.cap_count), 24)
    assert (n > 0).all(), "capture ring never filled"
    changed = False
    for lane in range(2):
        lo = int(len_a[lane] - n[lane])
        hi = int(min(len_a[lane], len_b[lane]))
        assert hi > lo, "no overlapping re-seeded region to compare"
        # ULP-level tolerance: serving built these entries in (γ+1)-wide
        # extends, the re-seed in one W-wide pass — XLA may tile the
        # projection differently per width
        np.testing.assert_allclose(k_r[lane, lo:hi], k_b[lane, lo:hi],
                                   rtol=2e-5, atol=1e-5)
        np.testing.assert_allclose(v_r[lane, lo:hi], v_b[lane, lo:hi],
                                   rtol=2e-5, atol=1e-5)
        changed |= bool(np.max(np.abs(k_r[lane, lo:hi]
                                      - k_a[lane, lo:hi])) > 1e-2)
    assert changed, "re-seed was a no-op (drafts differ, K/V must too)"


def test_reseed_deploy_stream_invariant(pretrained):
    """End-to-end: a mid-stream deploy with re-seed leaves greedy token
    streams byte-identical (the target verifies every draft) while the
    engine records the deploy and the re-seed dispatch."""
    cfg, params, dcfg, dparams, domains = pretrained
    draft_b = eagle.draft_init(dcfg, jax.random.key(99))

    class _AfterN:
        def __init__(self, n):
            self.n, self.polls = n, 0

        def __call__(self):
            self.polls += 1
            return (DraftVersion(1, draft_b, 0.9)
                    if self.polls >= self.n else None)

    ref = _engine(pretrained)
    r_ref = _reqs(pretrained, (40, 40), seed=4)
    ref.serve_stream(r_ref)

    eng = _engine(pretrained, reseed_window=24, deploy_source=_AfterN(3))
    r_new = _reqs(pretrained, (40, 40), seed=4)
    eng.serve_stream(r_new)
    assert eng.stats.deploys == 1 and eng.stats.reseeds == 1
    assert [r.generated for r in r_new] == [r.generated for r in r_ref]


# ====================================== arrival gating + idle supersteps
def test_scheduler_arrival_gating_fake_clock():
    now = {"t": 0.0}
    clock = lambda: now["t"]
    reqs = [Request(prompt=[1, 2], max_new_tokens=4, arrives_at=t)
            for t in (0.0, 0.5, 1.5)]
    s = Scheduler(2, reqs, gate_arrivals=True, clock=clock)
    assert s.has_pending()
    assert [slot for slot, _ in s.admit()] == [0]
    assert not s.has_pending()           # t=0.5 not arrived yet
    assert s.more_coming()
    assert s.next_arrival_in() == pytest.approx(0.5)
    now["t"] = 0.6
    assert s.has_pending()
    assert [slot for slot, _ in s.admit()] == [1]
    now["t"] = 0.7
    assert s.next_arrival_in() == pytest.approx(0.8)
    s.slots[0].finish()
    s.release_finished()
    assert s.admit() == []               # third still in the future
    now["t"] = 2.0
    assert [slot for slot, _ in s.admit()] == [0]
    # next_arrival_in probes the (lazy) iterator and discovers exhaustion
    assert s.next_arrival_in() is None
    assert not s.more_coming()


def test_engine_idle_supersteps_and_gated_serving(pretrained):
    """Arrival gaps produce idle supersteps (no dispatch), every request
    is still served exactly, and token streams match the ungated run."""
    budgets = (6, 9, 5, 8)
    base = _reqs(pretrained, budgets, seed=2)
    ref_eng = _engine(pretrained)
    ref = [Request(prompt=list(r.prompt), max_new_tokens=r.max_new_tokens)
           for r in base]
    ref_eng.serve_stream(ref)

    gated = [Request(prompt=list(r.prompt),
                     max_new_tokens=r.max_new_tokens,
                     arrives_at=[0.0, 0.0, 0.35, 0.55][i])
             for i, r in enumerate(base)]
    eng = _engine(pretrained, gate_arrivals=True)
    # warm the jits first: a cold compile inside the gated serve would
    # swallow the arrival gaps and leave nothing to idle on
    warm = [Request(prompt=list(r.prompt),
                    max_new_tokens=r.max_new_tokens) for r in base]
    eng.serve_stream(warm)
    eng.stats = type(eng.stats)()
    done = eng.serve_stream(gated)
    assert len(done) == 4
    assert [r.generated for r in gated] == [r.generated for r in ref]
    assert eng.stats.idle_supersteps > 0, \
        "arrival gaps must surface as idle supersteps"
    for r in gated[2:]:
        # latency clock re-anchored to the gated arrival instant
        assert r.ttft is not None and r.ttft < 10.0


# ====================================== bounded stats + completion sink
def test_ring_and_p2_sketch():
    r = Ring(maxlen=8)
    for i in range(20):
        r.append(i)
    assert list(r) == list(range(12, 20))
    assert r[:3] == [12, 13, 14]        # slicing still works

    rng = np.random.default_rng(0)
    xs = rng.exponential(1.0, size=5000)
    for q in (0.5, 0.95):
        sk = P2Quantile(q)
        for x in xs:
            sk.add(float(x))
        exact = float(np.quantile(xs, q))
        assert abs(sk.value - exact) / exact < 0.08, \
            f"P2 q={q}: {sk.value:.3f} vs exact {exact:.3f}"
    # exact for small n
    sk = P2Quantile(0.5)
    for x in (5.0, 1.0, 3.0):
        sk.add(x)
    assert sk.value == pytest.approx(3.0)


def test_stats_retention_bounded_and_sketch_percentiles(pretrained):
    from repro.serving.engine import ServingStats

    st = ServingStats(retain=16)
    rng = np.random.default_rng(1)
    lats = rng.uniform(0.1, 2.0, size=400)
    for x in lats:
        st.record_latency(float(x))
        st.record_ttft(float(x) / 2)
    assert len(st.latencies) == 16 and len(st.ttfts) == 16
    assert st.timeline.maxlen == 16
    p95 = float(np.quantile(lats, 0.95))
    assert abs(st.latency_p95 - p95) / p95 < 0.15
    assert st.latency_p50 <= st.latency_p95


def test_completion_sink_bounds_scheduler(pretrained):
    sunk = []
    eng = _engine(pretrained, completion_sink=sunk.append)
    reqs = _reqs(pretrained, (5, 7, 4, 6), seed=3)
    out = eng.serve_stream(reqs)
    assert out == [], "sink mode must not retain completions"
    assert sorted(r.rid for r in sunk) == sorted(r.rid for r in reqs)
    assert all(r.finish_t is not None for r in sunk)


def test_serve_launcher_fails_a_run_with_trainer_failures(capsys):
    """Serving survives a failed train cycle; the launcher's exit code
    does not hide it."""
    import types

    from repro.launch.serve import _exit_on_trainer_failures
    svc = types.SimpleNamespace(last_error="RuntimeError: boom")
    _exit_on_trainer_failures({"trainer_failures": 0}, svc)
    with pytest.raises(SystemExit, match="2 time"):
        _exit_on_trainer_failures({"trainer_failures": 2}, svc)
    assert "RuntimeError: boom" in capsys.readouterr().out
