"""Zero-sync observability layer (repro/obs): tracer, metrics registry,
flight recorder, and their engine/system integration.

The load-bearing contract is **inertness**: observability-on serving
must emit byte-identical token streams and the exact same device
dispatch count as observability-off (hooks are host-side, at existing
telemetry boundaries), and the default tracer with no profiler session
and the null recorder must make the disabled path one check.  Under a
JAX profiler session the default tracer records the spans that tile
the serving loop and the sync train cycle, in its ring and in the
profile's host plane.  On top of that: exported Chrome trace JSON
must be loadable and well-nested, ``metrics.snapshot()`` must agree
with the legacy ``ServingStats`` / ``TideSystem.summary()`` counters,
and the flight recorder must tell each request's whole story
(admit -> chunks -> first token -> commits -> finish).

Unit tests run weight-free; the engine tests use randomly initialized
weights (inertness is a property of the computation, not the model) so
the file stays in the fast tier.  The train-cycle trace test needs a
pretrained target and is slow-marked.
"""
import json
import threading
import time

from bench import devtrace

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.core import eagle
from repro.core.tide import TideConfig, TideSystem
from repro.models import transformer as T
from repro.obs import ObsConfig
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.recorder import NULL_RECORDER, FlightRecorder
from repro.obs import trace as obs_trace
from repro.obs.trace import DEFAULT_TRACER, Tracer
from repro.serving.engine import ServingEngine, ServingStats
from repro.serving.request import Request


# ================================================================ tracer
def test_tracer_spans_nest_and_export(tmp_path):
    tr = Tracer()
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
        tr.instant("tick", n=3)
    tr.counter("depth", queue=2)
    path = tmp_path / "trace.json"
    doc = tr.export(str(path))
    # the written file is valid JSON and identical to the return value
    assert json.loads(path.read_text()) == doc
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == "X" and inner["ph"] == "X"
    # nesting: inner lies within [outer.ts, outer.ts + outer.dur]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"] == {"k": 1}
    assert by_name["tick"]["ph"] == "i" and by_name["tick"]["s"] == "t"
    assert by_name["depth"]["ph"] == "C"
    # thread metadata row present, same tid as the spans
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "thread_name"
    assert {e["tid"] for e in (outer, inner)} <= {m["tid"] for m in meta}


def test_tracer_ring_bounded():
    tr = Tracer(capacity=8)
    for i in range(100):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 8
    names = [e[1] for e in tr.events()]
    assert names == [f"e{i}" for i in range(92, 100)]


def test_tracer_thread_safe_spans():
    tr = Tracer()
    barrier = threading.Barrier(4)   # all 4 alive together -> distinct
    #                                  native thread ids on the spans

    def worker(tag):
        barrier.wait()
        for _ in range(50):
            with tr.span(tag):
                pass

    ts = [threading.Thread(target=worker, args=(f"w{i}",))
          for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = tr.export()["traceEvents"]
    assert sum(e["ph"] == "X" for e in evs) == 200
    # per-thread rows carry distinct tids
    assert len({e["tid"] for e in evs if e["ph"] == "X"}) == 4


def test_null_tracer_is_inert():
    """With no profiler session the default tracer records nothing and
    hands out the shared no-op span."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    before = len(DEFAULT_TRACER.events())
    assert not DEFAULT_TRACER.enabled
    assert DEFAULT_TRACER.span("x", a=1) is obs_trace._NULL_SPAN
    with DEFAULT_TRACER.span("x", a=1):
        DEFAULT_TRACER.instant("y")
        DEFAULT_TRACER.counter("z", n=1)
    assert len(DEFAULT_TRACER.events()) == before


def test_long_span_kept_when_session_starts_inside_it(tmp_path):
    """Spans under way at a profiler session's edges: a long span opened
    before the session and one open when it stops are kept whole, as is
    an ordinary span open when it stops; an ordinary span opened before
    the session is not, nor anything after it."""
    tr = Tracer(always=False)
    t_open = time.perf_counter_ns()
    cycle = tr.long_span("cycle")
    cycle.__enter__()
    with tr.span("early"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("inside"):
            pass
        cycle.__exit__(None, None, None)
        late, late_cycle = tr.span("late"), tr.long_span("late_cycle")
        late.__enter__()
        late_cycle.__enter__()
    finally:
        jax.profiler.stop_trace()
    late.__exit__(None, None, None)
    late_cycle.__exit__(None, None, None)
    t_close = time.perf_counter_ns()
    with tr.long_span("after"):
        pass
    with tr.span("after"):
        pass
    evs = {e[1]: e for e in tr.events()}
    assert set(evs) == {"cycle", "inside", "late", "late_cycle"}
    assert t_open <= evs["cycle"][2] < evs["inside"][2]
    for name in ("late", "late_cycle"):
        assert evs[name][2] + evs[name][3] <= t_close
        assert evs[name][3] > 0


# ============================================================== registry
def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("serving.tokens_out")
    c.inc(5)
    c.inc()
    assert reg.counter("serving.tokens_out") is c       # get-or-create
    g = reg.gauge("spec.gamma")
    g.set(3)
    reg.gauge("train.cycles", fn=lambda: 7)             # callback gauge
    h = reg.histogram("serving.ttft_s", quantiles=(0.5,))
    for x in (0.1, 0.2, 0.3):
        h.observe(x)
    snap = reg.snapshot()
    assert snap["serving.tokens_out"] == 6
    assert snap["spec.gamma"] == 3
    assert snap["train.cycles"] == 7
    assert snap["serving.ttft_s.count"] == 3
    assert abs(snap["serving.ttft_s.p50"] - 0.2) < 1e-9
    assert abs(snap["serving.ttft_s.max"] - 0.3) < 1e-9
    assert set(reg.namespaces()) == {"serving", "spec", "train"}


def test_registry_gauge_fn_rebind():
    """A fresh ServingStats must be able to re-register its derived
    gauges against a long-lived registry: gauge(fn=...) rebinds."""
    reg = MetricsRegistry()
    reg.gauge("serving.throughput", fn=lambda: 1.0)
    reg.gauge("serving.throughput", fn=lambda: 2.0)
    assert reg.snapshot()["serving.throughput"] == 2.0


def test_registry_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("serving.tokens_out").inc(9)
    h = reg.histogram("serving.latency_s", quantiles=(0.5, 0.95))
    h.observe(1.0)
    text = reg.to_prometheus()
    assert "# TYPE serving_tokens_out counter" in text
    assert "serving_tokens_out 9" in text
    assert 'serving_latency_s{quantile="0.5"}' in text
    assert "serving_latency_s_count 1" in text


def test_registry_to_json(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a.b").inc(2)
    p = tmp_path / "m.json"
    text = reg.to_json(str(p))
    assert json.loads(p.read_text()) == json.loads(text) == {"a.b": 2}


# ======================================================= flight recorder
def _req(prompt=(1, 2, 3), **kw):
    r = Request(prompt=list(prompt), max_new_tokens=8, **kw)
    r.rid = kw.get("rid", r.rid)
    return r


def test_recorder_lifecycle():
    rec = FlightRecorder()
    r = Request(prompt=[1, 2], max_new_tokens=4, domain="science")
    r.sid = 0
    rec.admit(r, round_=2)
    rec.note(r.rid, "first_token", round_=3)
    rec.note(r.rid, "commit", round_=4, n=3, spec=True)
    r.generated = [5, 6, 7]
    r.arrival_t, r.admit_t = 1.0, 1.0
    r.first_token_t, r.finish_t = 1.5, 2.0
    rec.finish(r, round_=5)
    tl = rec.timeline(r.rid)
    assert tl["domain"] == "science" and tl["prompt_len"] == 2
    kinds = [e["kind"] for e in tl["events"]]
    assert kinds == ["admit", "first_token", "commit", "finish"]
    assert tl["events"][2]["n"] == 3 and tl["events"][2]["spec"] is True
    assert tl["events"][-1]["tokens"] == 3
    assert tl["ttft_s"] == pytest.approx(0.5)
    assert tl["latency_s"] == pytest.approx(1.0)
    doc = rec.export()
    assert doc["requests"] == [tl]


def test_recorder_notes_for_unknown_rid_are_dropped():
    rec = FlightRecorder()
    rec.note("nope", "commit", round_=1, n=2)   # must not raise
    assert rec.timeline("nope") is None


def test_null_recorder_is_inert():
    assert not NULL_RECORDER.enabled
    r = Request(prompt=[1], max_new_tokens=1)
    NULL_RECORDER.admit(r, 0)
    NULL_RECORDER.note(r.rid, "commit", 1, n=1)
    NULL_RECORDER.finish(r, 2)
    assert NULL_RECORDER.timelines() == []
    assert NULL_RECORDER.export() == {"requests": [], "events": []}


# =============================================== ServingStats <-> registry
def test_serving_stats_is_registry_backed():
    reg = MetricsRegistry()
    st = ServingStats(registry=reg)
    st.tokens_out += 10
    st.steps += 2
    st.wall_s += 0.5
    st.record_ttft(0.1)
    st.record_latency(0.9)
    snap = reg.snapshot()
    assert snap["serving.tokens_out"] == 10
    assert snap["serving.steps"] == 2
    assert snap["serving.wall_s"] == 0.5
    assert snap["serving.throughput_tok_s"] == st.throughput == 20.0
    assert snap["serving.ttft_s.count"] == 1
    assert snap["serving.latency_s.count"] == 1
    assert st.ttft_p50 == pytest.approx(0.1)
    # a fresh stats object over the same registry re-zeroes serving.*
    st2 = ServingStats(registry=reg)
    snap2 = reg.snapshot()
    assert snap2["serving.tokens_out"] == 0
    assert snap2["serving.ttft_s.count"] == 0
    assert snap2["serving.throughput_tok_s"] == st2.throughput == 0.0


def test_serving_stats_private_registry_default():
    a, b = ServingStats(), ServingStats()
    a.tokens_out += 3
    assert b.tokens_out == 0            # no shared hidden state


# ========================================================== engine parity
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


def _serve(eng, *, waves=2, batch=2, max_new=12, seed=0):
    rng = np.random.default_rng(seed)
    gens = []
    for _ in range(waves):
        reqs = [Request(prompt=list(rng.integers(1, 50, 7)),
                        max_new_tokens=max_new) for _ in range(batch)]
        eng.serve_wave(reqs)
        gens.extend(list(r.generated) for r in reqs)
    return gens


def test_engine_obs_on_streams_byte_identical():
    cfg, params, dcfg, dparams = _get_model()
    kw = dict(batch_size=2, max_len=96, gamma=3, seed=5,
              superstep_rounds=8)
    off = ServingEngine(cfg, params, dcfg, dparams, **kw)
    on = ServingEngine(cfg, params, dcfg, dparams, **kw,
                       tracer=Tracer(), recorder=FlightRecorder(),
                       metrics=MetricsRegistry())
    s_off = _serve(off)
    s_on = _serve(on)
    assert s_on == s_off
    assert on.stats.dispatches == off.stats.dispatches
    assert on.stats.tokens_out == off.stats.tokens_out
    # the trace covers the loop
    names = {e[1] for e in on.tracer.events()}
    assert {"superstep.dispatch", "superstep.unpack"} <= names
    # the registry agrees with the stats view
    snap = on.metrics.snapshot()
    assert snap["serving.tokens_out"] == on.stats.tokens_out
    assert snap["serving.dispatches"] == on.stats.dispatches
    # spec/paging namespaces are registered (zero gauges on dense)
    assert {"serving", "spec", "paging"} <= set(on.metrics.namespaces())
    # every request has a full flight timeline
    tls = on.recorder.timelines()
    assert len(tls) == 4
    for tl in tls:
        kinds = [e["kind"] for e in tl["events"]]
        assert kinds[0] == "admit" and kinds[-1] == "finish"
        assert "first_token" in kinds and "commit" in kinds
        # commit notes account for every token except (at most) the
        # first, which the prefill prologue emits outside the unpack
        committed = sum(e.get("n", 0) for e in tl["events"]
                        if e["kind"] == "commit")
        assert tl["events"][-1]["tokens"] - committed in (0, 1)


def test_engine_recorder_covers_chunked_prefill():
    cfg, params, dcfg, dparams = _get_model()
    eng = ServingEngine(cfg, params, dcfg, dparams, batch_size=2,
                        max_len=96, gamma=3, seed=5, superstep_rounds=8,
                        prefill_chunk=8, recorder=FlightRecorder(),
                        tracer=Tracer())
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=list(rng.integers(1, 50, 20)),
                    max_new_tokens=8) for _ in range(2)]
    list(eng.serve_stream(iter(reqs)))
    for tl in eng.recorder.timelines():
        kinds = [e["kind"] for e in tl["events"]]
        assert "prefill_chunk" in kinds
    names = {e[1] for e in eng.tracer.events()}
    assert "prefill.chunk" in names
    # every chunk is dispatched inside a prefill.advance span, which
    # counts the gap's chunks and row-tokens
    adv = [e[5] for e in eng.tracer.events() if e[1] == "prefill.advance"]
    assert sum(a["chunks"] for a in adv) == eng.stats.prefill_chunks
    assert sum(a["row_tokens"] for a in adv) \
        == eng.stats.prefill_row_tokens


# ======================================================== system parity
_SYS_TCFG = dict(gamma=3, batch_size=2, max_len=96, adaptive_spec=False,
                 selective_training=True, signal_window=8,
                 n_threshold=4, train_epochs=1, train_min_steps=6,
                 seed=0)


def _waves(n_waves=2, batch=2, seed=1):
    rng = np.random.default_rng(seed)
    return [[("science", list(rng.integers(1, 50, 7)))
             for _ in range(batch)] for _ in range(n_waves)]


@pytest.mark.slow
def test_system_snapshot_matches_summary():
    """`metrics.snapshot()` must agree with every counter the legacy
    ``summary()`` dict reports, across all four namespaces."""
    cfg, params, dcfg, dparams = _get_model()
    tc = TideConfig(**_SYS_TCFG,
                    obs=ObsConfig(trace=True, record=True))
    sys_ = TideSystem(cfg, params, tc, dparams=dparams)
    off = TideSystem(cfg, params, TideConfig(**_SYS_TCFG),
                     dparams=dparams)
    waves = _waves()
    a = sys_.run(iter(waves), max_new_tokens=12)
    b = off.run(iter(waves), max_new_tokens=12)
    assert [r.generated for r in a] == [r.generated for r in b]

    s, snap = sys_.summary(), sys_.snapshot()
    for summary_key, metric in [
            ("tokens", "serving.tokens_out"),
            ("steps", "serving.steps"),
            ("spec_steps", "serving.spec_steps"),
            ("refills", "serving.refills"),
            ("idle_supersteps", "serving.idle_supersteps"),
            ("deploys", "serving.deploys"),
            ("reseeds", "serving.reseeds"),
            ("spec_parks", "spec.parks"),
            ("spec_resumes", "spec.resumes"),
            ("train_cycles", "train.cycles"),
            ("deployed", "train.deploy_version"),
            ("signals_collected", "train.signals_pushed"),
            ("signal_bytes", "train.signal_bytes"),
            ("signals_dropped", "train.signals_dropped"),
    ]:
        assert snap[metric] == s[summary_key], (summary_key, metric)
    assert snap["serving.throughput_tok_s"] == s["throughput_tok_s"]
    assert snap["serving.accept_len"] == s["accept_len"]
    assert snap["serving.occupancy"] == s["occupancy"]
    # obs-off system has null instruments
    assert not off.tracer.enabled and not off.recorder.enabled
    assert sys_.tracer.enabled and sys_.recorder.enabled


@pytest.mark.slow
def test_system_trace_covers_training(tmp_path):
    """A stream that actually trains must leave train.cycle spans,
    train.publish + deploy instants, and matching train.* gauges."""
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

    tc = TideConfig(**_SYS_TCFG, obs=ObsConfig(trace=True))
    sys_ = TideSystem(cfg, params, tc, dparams=dparams)
    rng = np.random.default_rng(1)
    waves = [[("science", domains["science"].sample_prompt(rng))
              for _ in range(2)] for _ in range(4)]
    sys_.run(iter(waves), max_new_tokens=24)
    assert sys_.summary()["train_cycles"] >= 1, "scenario never trained"

    path = tmp_path / "trace.json"
    doc = sys_.export_trace(str(path))
    assert json.loads(path.read_text()) == doc
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"superstep.dispatch", "superstep.unpack",
            "train.cycle", "train.publish", "deploy"} <= names
    # train.cycle runs on the service side, publish nested within a run
    cyc = next(e for e in evs if e["name"] == "train.cycle")
    assert cyc["ph"] == "X" and cyc["dur"] > 0
    snap = sys_.snapshot()
    assert snap["train.cycles"] == sys_.summary()["train_cycles"]
    assert snap["train.deploy_version"] == sys_.gate.version


# ============================================= profiler-session tracing
# the spans this layer adds to tile the serving loop and the sync train
# cycle (beside superstep.dispatch/unpack, prefill.*, refill, idle, ...)
NEW_SPANS = {"serve.start", "superstep.wait", "signals.fetch",
             "refill.first_wait", "serve.boundary", "serve.deliver",
             "prefill.advance", "signals.flush", "train.stack",
             "train.feed", "train.step", "train.read", "train.eval"}
_PROF_TCFG = dict(gamma=3, batch_size=2, max_len=64, adaptive_spec=False,
                  selective_training=False, signal_window=8,
                  n_threshold=4, train_epochs=1, train_min_steps=6,
                  prefill_chunk=8, seed=0)


def _prof_requests():
    rng = np.random.default_rng(4)
    return [Request(prompt=list(rng.integers(1, 50, int(n))),
                    max_new_tokens=20)
            for n in rng.integers(9, 20, 6)]


def _union_s(intervals):
    return sum(e - s for s, e in devtrace.union(intervals))


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One stream with sync training served with no profiler session,
    and the same stream on a fresh system under one: their requests and
    dispatch counts, the default tracer's events of the traced stream,
    its host time span, and the profile's planes."""
    cfg, params, dcfg, dparams = _get_model()
    runs = {}
    for mode in ("off", "on"):
        sys_ = TideSystem(cfg, params, TideConfig(**_PROF_TCFG),
                          dparams=dparams)
        reqs = _prof_requests()
        if mode == "off":
            sys_.run_stream(reqs)
        else:
            tdir = tmp_path_factory.mktemp("profile")
            with jax.profiler.trace(str(tdir)):
                t_a = time.perf_counter_ns()
                sys_.run_stream(reqs)
                t_b = time.perf_counter_ns()
        runs[mode] = (reqs, sys_.engine.stats.dispatches,
                      sys_.summary()["train_cycles"])
        sys_.close()
    evs = [e for e in DEFAULT_TRACER.events()
           if t_a <= e[2] and e[2] + e[3] <= t_b]
    planes = devtrace.planes_of(devtrace.find_xplane(str(tdir)))
    return {"runs": runs, "events": evs, "window": (t_a, t_b),
            "planes": planes}


def test_profiled_stream_records_new_spans(profiled):
    assert profiled["runs"]["on"][2] >= 1, "the stream never trained"
    names = {e[1] for e in profiled["events"]}
    assert NEW_SPANS | {"superstep.dispatch", "superstep.unpack",
                        "train.cycle", "prefill.chunk"} <= names
    deliveries = [e for e in profiled["events"] if e[1] == "serve.deliver"]
    assert all(e[5]["rids"] for e in deliveries)


def test_profiled_spans_in_profile_host_plane(profiled):
    host = {ev[0] for p in profiled["planes"]
            if p["name"].startswith("/host:")
            for ln in p["lines"] for ev in ln["events"]}
    assert NEW_SPANS | {"superstep.dispatch", "train.cycle"} <= host


def test_profiler_session_leaves_streams_identical(profiled):
    off, on = profiled["runs"]["off"], profiled["runs"]["on"]
    assert [r.generated for r in on[0]] == [r.generated for r in off[0]]
    assert on[1] == off[1]
    assert on[2] == off[2]


def test_spans_tile_serving_loop_and_train_cycle(profiled):
    """The serving thread's spans cover at least 95% of the stream's
    wall time, and the spans inside each train cycle at least 95% of
    the cycle."""
    evs = [e for e in profiled["events"] if e[0] == "X"]
    t_a, t_b = profiled["window"]
    serving = {e[4] for e in evs if e[1] == "superstep.dispatch"}
    assert len(serving) == 1
    tid = serving.pop()
    mine = [(s, s + d) for _, _, s, d, t, _ in evs if t == tid]
    assert _union_s(mine) >= 0.95 * (t_b - t_a)
    cycles = [e for e in evs if e[1] == "train.cycle"]
    assert cycles
    for _, _, c0, cd, ctid, _ in cycles:
        inner = [(s, s + d) for _, name, s, d, t, _ in evs
                 if t == ctid and name.startswith("train.")
                 and name != "train.cycle" and c0 <= s and s + d <= c0 + cd]
        assert _union_s(inner) >= 0.95 * cd
