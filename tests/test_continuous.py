"""Continuous batching (serve_stream + in-flight slot refill).

Greedy decoding makes per-request token streams scheduling-invariant:
whatever slots a request shares a batch with, and whenever it is
admitted, its stream must be byte-identical.  That is the core oracle
here — wave scheduling, the superstep stream, the stepwise stream, and
serving a request alone must all agree token for token, and a saturated
single batch must reproduce ``serve_wave`` exactly (streams, SignalStore
contents, stats).

Also covers the satellite fixes: partial waves (inert slot padding),
``_unpack_superstep`` edge cases (zero valid rounds, wave done at entry,
EOS landing on the last round of a superstep, first-token EOS), and the
``ServingStats`` TTFT / completion-latency / occupancy accounting.
"""
import time

import jax
import numpy as np
import pytest

# Pretrained-fixture-heavy end-to-end parity suite: slow tier (the
# fast smoke loop runs `pytest -m "not slow"`; see ROADMAP.md).
pytestmark = pytest.mark.slow

import repro.configs as C
from repro.core import eagle
from repro.core.signals import SignalExtractor, SignalStore
from repro.data.workloads import arrival_trace, make_domains, training_corpus
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.policy import ServingConfig
from repro.serving.request import Request, inert_request
from repro.serving.scheduler import Scheduler
from repro.training.trainer import pretrain_target


@pytest.fixture(scope="module")
def pretrained():
    cfg = C.get("tide-tiny")
    params = T.init(cfg, jax.random.key(0))
    domains = make_domains(cfg.vocab_size, ["science"], branchings=[2],
                           seed=3)
    corpus = training_corpus(domains["science"], 64, 40, 1)
    params, _ = pretrain_target(cfg, params, corpus, steps=80, lr=3e-3)
    dcfg = eagle.draft_config(cfg)
    dparams = eagle.draft_init(dcfg, jax.random.key(7))
    return cfg, params, dcfg, dparams, domains


def _engine(pretrained, rounds, *, batch=4, extractor=True, eos_id=None,
            max_len=96, greedy=True, tree_width=0):
    cfg, params, dcfg, dparams, domains = pretrained
    store = SignalStore()
    ext = SignalExtractor(store, window=16) if extractor else None
    config = ServingConfig(batch_size=batch, max_len=max_len, gamma=3,
                           seed=5, greedy=greedy, superstep_rounds=rounds,
                           eos_id=eos_id, tree_width=tree_width)
    eng = ServingEngine(cfg, params, dcfg, dparams, extractor=ext,
                        config=config)
    return eng, store


def _requests(pretrained, budgets, seed=0):
    domains = pretrained[4]
    rng = np.random.default_rng(seed)
    return [Request(prompt=domains["science"].sample_prompt(rng),
                    max_new_tokens=m) for m in budgets]


def _signals(store):
    return [(b.tokens.tobytes(), b.feats.tobytes()) for b in store.drain()]


# ------------------------------------------------- saturated-batch parity
@pytest.mark.parametrize("rounds", [0, 8])
def test_saturated_stream_matches_wave(pretrained, rounds):
    """A saturated same-arrival batch through serve_stream must be
    byte-identical to serve_wave: streams, SignalStore, stats."""
    e_wave, s_wave = _engine(pretrained, rounds)
    r_wave = _requests(pretrained, (9, 24, 24, 15))
    e_wave.serve_wave(r_wave)

    e_str, s_str = _engine(pretrained, rounds)
    r_str = _requests(pretrained, (9, 24, 24, 15))
    done = e_str.serve_stream(r_str)

    assert [r.generated for r in r_str] == [r.generated for r in r_wave]
    assert _signals(s_str) == _signals(s_wave)
    assert len(done) == 4 and all(r.finish_t is not None for r in r_str)
    for attr in ("tokens_out", "steps", "spec_steps", "dispatches",
                 "refills"):
        assert getattr(e_str.stats, attr) == getattr(e_wave.stats, attr)
    assert e_str.accept_ema == e_wave.accept_ema
    assert e_wave.stats.tokens_out == sum(
        len(r.generated) for r in r_wave)


# --------------------------------------------------- refill-stream parity
def test_refill_stream_parity_and_alone(pretrained):
    """A ragged stream through both engine modes and through wave
    chunks: per-request streams identical everywhere, and every
    *refilled* request matches serving it alone on a fresh engine."""
    budgets = (5, 18, 7, 12, 16, 4, 9, 20, 6, 11)
    r_ss = _requests(pretrained, budgets)
    e_ss, _ = _engine(pretrained, 8)
    e_ss.serve_stream(list(r_ss))
    assert e_ss.stats.refills == len(budgets) - e_ss.batch
    assert all(r.done and r.finish_t is not None for r in r_ss)
    assert e_ss.stats.tokens_out == sum(len(r.generated) for r in r_ss)

    r_st = _requests(pretrained, budgets)
    e_st, _ = _engine(pretrained, 0)
    e_st.serve_stream(list(r_st))
    assert [r.generated for r in r_st] == [r.generated for r in r_ss]

    r_wv = _requests(pretrained, budgets)
    e_wv, _ = _engine(pretrained, 8)
    for i in range(0, len(r_wv), 4):
        e_wv.serve_wave(r_wv[i:i + 4])
    assert [r.generated for r in r_wv] == [r.generated for r in r_ss]

    # refilled slots (everything admitted after the initial batch)
    e_alone, _ = _engine(pretrained, 8, batch=1)
    for req in r_ss[e_ss.batch:]:
        solo = Request(prompt=list(req.prompt),
                       max_new_tokens=req.max_new_tokens)
        e_alone.serve_wave([solo])
        assert solo.generated == req.generated, \
            "refilled slot diverged from serving the request alone"


def test_sampled_stream_scheduling_invariant(pretrained):
    """Per-request PRNG streams (fold-in on the admission ordinal) make
    *sampled* decoding scheduling-invariant too: a ragged stream with
    in-flight refills must emit byte-identical per-request streams
    through the superstep engine, the per-step reference loop, and
    wave-chunked serving — including across the refill-timing skew that
    previously forced the sampled-parity caveat."""
    budgets = (5, 18, 7, 12, 16, 4, 9, 20, 6, 11)
    r_ss = _requests(pretrained, budgets)
    e_ss, _ = _engine(pretrained, 8, greedy=False)
    e_ss.serve_stream(list(r_ss))
    assert e_ss.stats.refills == len(budgets) - e_ss.batch

    r_st = _requests(pretrained, budgets)
    e_st, _ = _engine(pretrained, 0, greedy=False)
    e_st.serve_stream(list(r_st))
    assert [r.generated for r in r_st] == [r.generated for r in r_ss], \
        "sampled superstep stream diverged from the per-step loop"

    r_wv = _requests(pretrained, budgets)
    e_wv, _ = _engine(pretrained, 8, greedy=False)
    for i in range(0, len(r_wv), 4):
        e_wv.serve_wave(r_wv[i:i + 4])
    assert [r.generated for r in r_wv] == [r.generated for r in r_ss], \
        "sampled streams depend on scheduling (wave vs continuous)"

    # tree-sampled decoding rides the same per-lane streams: branch
    # r >= 1 folds r into the lane's acceptance key, so a width=2 tree
    # must stay refill-order-invariant across the same three schedules
    r_tr = _requests(pretrained, budgets)
    e_tr, _ = _engine(pretrained, 8, greedy=False, tree_width=2)
    e_tr.serve_stream(list(r_tr))
    assert e_tr.stats.refills == len(budgets) - e_tr.batch

    r_ts = _requests(pretrained, budgets)
    e_ts, _ = _engine(pretrained, 0, greedy=False, tree_width=2)
    e_ts.serve_stream(list(r_ts))
    assert [r.generated for r in r_ts] == [r.generated for r in r_tr], \
        "tree-sampled superstep stream diverged from the per-step loop"

    r_tw = _requests(pretrained, budgets)
    e_tw, _ = _engine(pretrained, 8, greedy=False, tree_width=2)
    for i in range(0, len(r_tw), 4):
        e_tw.serve_wave(r_tw[i:i + 4])
    assert [r.generated for r in r_tw] == [r.generated for r in r_tr], \
        "tree-sampled streams depend on scheduling (wave vs continuous)"


def test_stream_stats_and_latency(pretrained):
    """ServingStats: TTFT/latency recorded per request, occupancy in
    (0, 1], lane accounting consistent."""
    budgets = (6, 15, 8, 10, 12, 5)
    reqs = _requests(pretrained, budgets)
    eng, _ = _engine(pretrained, 8)
    eng.serve_stream(list(reqs))
    st = eng.stats
    assert st.completed == len(budgets)
    assert len(st.ttfts) == len(budgets)
    assert len(st.latencies) == len(budgets)
    assert all(t >= 0 for t in st.ttfts)
    assert st.latency_p50 <= st.latency_p95
    assert 0.0 < st.occupancy <= 1.0
    assert st.busy_lane_rounds <= st.lane_rounds
    assert st.lane_rounds == st.steps * eng.batch
    for r in reqs:
        assert r.ttft is not None and r.latency is not None
        assert r.ttft <= r.latency
    # timeline rounds carry lane-occupancy telemetry
    assert all("busy_lanes" in e for e in st.timeline)


# ----------------------------------------------------------- partial waves
@pytest.mark.parametrize("rounds", [0, 8])
def test_partial_wave(pretrained, rounds):
    """serve_wave accepts waves smaller than the engine batch: inert
    zero-budget slots pad the batch and leak nothing."""
    reqs = _requests(pretrained, (7, 11))
    eng, store = _engine(pretrained, rounds, batch=4)
    eng.serve_wave(reqs)
    assert all(r.done and len(r.generated) == r.max_new_tokens
               for r in reqs)
    assert eng.stats.tokens_out == sum(len(r.generated) for r in reqs)

    # parity: the same two requests on a batch-2 engine
    ref = _requests(pretrained, (7, 11))
    e2, _ = _engine(pretrained, rounds, batch=2)
    e2.serve_wave(ref)
    assert [r.generated for r in ref] == [r.generated for r in reqs]


def test_zero_budget_request(pretrained):
    """A zero-budget request completes immediately with no tokens."""
    reqs = _requests(pretrained, (0, 8))
    eng, _ = _engine(pretrained, 8)
    eng.serve_wave(reqs)
    assert reqs[0].generated == [] and reqs[0].finish_t is not None
    assert len(reqs[1].generated) == 8
    assert eng.stats.tokens_out == 8


# ----------------------------------------------------------- EOS handling
def test_first_token_eos_stream(pretrained):
    """EOS as the very first sampled token: one-token stream, immediate
    finish, identical across modes, and the slot is refilled."""
    probe = _requests(pretrained, (12, 12, 12, 12, 12, 12))
    ref = [Request(prompt=list(r.prompt), max_new_tokens=12)
           for r in probe]
    e1, _ = _engine(pretrained, 8)
    e1.serve_stream(ref)
    # request 0's first sampled token as EOS: its stream collapses to a
    # single token, freeing the slot for an immediate refill
    eos = ref[0].generated[0]

    outs = {}
    for rounds in (0, 8):
        reqs = [Request(prompt=list(r.prompt), max_new_tokens=12)
                for r in probe]
        eng, _ = _engine(pretrained, rounds, eos_id=eos)
        eng.serve_stream(reqs)
        outs[rounds] = [list(r.generated) for r in reqs]
        assert reqs[0].generated == [eos], \
            "first-token EOS must cut the stream to one token"
        assert reqs[0].finish_t is not None
        for r in reqs:
            assert eos not in r.generated[:-1], "tokens emitted past EOS"
            assert r.done
    assert outs[0] == outs[8]


# ----------------------------------------- _unpack_superstep edge cases
def _bare_engine(pretrained):
    eng, _ = _engine(pretrained, 8, batch=2, extractor=False)
    return eng


def _ys(valid, n_eff, tokens, active_after, K, B, gp1):
    """Craft a superstep telemetry dict as _materialize would return."""
    return {
        "valid": np.asarray(valid, bool),
        "use_spec": np.ones((K,), bool),
        "ell": np.full((K,), 2.0, np.float32),
        "alpha": np.full((K,), 0.5, np.float32),
        "n_eff": np.asarray(n_eff, np.int32),
        "n_commit": np.asarray(n_eff, np.int32),
        "tokens": np.asarray(tokens, np.int32),
        "active_after": np.asarray(active_after, bool),
        "n_sig": np.zeros((K,), np.int32),
        "ema": np.full((K,), 1.5, np.float32),
    }


def test_unpack_zero_valid_rounds(pretrained):
    """A superstep dispatched after the wave finished: every round is
    skipped; nothing may change host-side."""
    eng = _bare_engine(pretrained)
    reqs = [Request(prompt=[1, 2], max_new_tokens=4) for _ in range(2)]
    K, B, gp1 = 3, 2, 4
    ys = _ys([False] * K, np.zeros((K, B)), np.zeros((K, B, gp1)),
             np.ones((K, B)), K, B, gp1)
    progressed = eng._unpack_superstep(ys, reqs, [r.rid for r in reqs], 0.0)
    assert progressed is False
    assert eng.stats.steps == 0 and eng.stats.tokens_out == 0
    assert all(r.generated == [] and r.finish_t is None for r in reqs)


def test_unpack_wave_done_at_entry_engine_level(pretrained):
    """Budgets small enough that the wave completes inside the first
    superstep: the pipelined second superstep must contribute zero
    rounds (valid=False throughout)."""
    eng, _ = _engine(pretrained, 8)
    reqs = _requests(pretrained, (3, 3, 3, 3))
    eng.serve_wave(reqs)
    assert all(len(r.generated) == 3 for r in reqs)
    # every *valid* round committed tokens; the trailing all-done
    # superstep contributed none
    assert eng.stats.steps < 8
    assert eng.stats.dispatches >= 2


def test_unpack_eos_on_last_round(pretrained):
    """EOS cut landing on the final round of a superstep: truncation and
    finish must apply on that very round, not the next superstep."""
    eng = _bare_engine(pretrained)
    reqs = [Request(prompt=[1, 2], max_new_tokens=10) for _ in range(2)]
    for r in reqs:
        # decoding requests always have their first token committed
        # before any drained telemetry mentions them; an unset
        # first_token_t marks a mid-chunk-prefill lane, which decode
        # telemetry must never retire
        r.first_token_t = time.perf_counter()
    K, B, gp1 = 2, 2, 4
    n_eff = [[2, 2], [1, 3]]
    tokens = np.arange(K * B * gp1).reshape(K, B, gp1) % 97
    active_after = [[True, True], [False, True]]   # req0 EOS-cut on last
    ys = _ys([True, True], n_eff, tokens, active_after, K, B, gp1)
    progressed = eng._unpack_superstep(ys, reqs, [r.rid for r in reqs], 0.0)
    assert progressed is True
    assert eng.stats.steps == 2
    assert len(reqs[0].generated) == 3 and reqs[0].finish_t is not None
    assert len(reqs[1].generated) == 5 and reqs[1].finish_t is None
    assert eng.stats.tokens_out == 8
    assert eng.stats.completed == 1


def test_unpack_free_slot_rows_ignored(pretrained):
    """Telemetry rows of free lanes (None residency snapshot) must not
    be attributed to anyone."""
    eng = _bare_engine(pretrained)
    req = Request(prompt=[1, 2], max_new_tokens=10)
    K, B, gp1 = 1, 2, 4
    # a free lane is inactive on device, so its n_eff is always 0
    ys = _ys([True], [[2, 0]], np.ones((K, B, gp1)), [[True, False]],
             K, B, gp1)
    eng._unpack_superstep(ys, [req, None], [req.rid, -1], 0.0)
    assert len(req.generated) == 2
    assert eng.stats.tokens_out == 2


# ------------------------------------------------- chunked refill prefill
def _chunk_requests(pretrained, n=10, seed=11):
    """Bimodal long-tail *prompt* trace: short-chat bulk + long prompts
    that would stall every resident lane for their full prefill under
    one-shot refill."""
    domains = pretrained[4]
    trace = arrival_trace(domains, n, mode="bursty", burst_size=4,
                          max_new_range=(5, 14), prompt_len=(8, 16),
                          long_prompt_frac=0.3, long_prompt_range=(48, 80),
                          seed=seed)
    return [Request(prompt=list(ev.prompt),
                    max_new_tokens=ev.max_new_tokens) for ev in trace]


def _chunk_engine(pretrained, rounds, *, chunk, batch=4, greedy=True):
    cfg, params, dcfg, dparams, _ = pretrained
    return ServingEngine(cfg, params, dcfg, dparams, batch_size=batch,
                         max_len=160, gamma=3, seed=5, greedy=greedy,
                         superstep_rounds=rounds, prefill_chunk=chunk)


def test_chunked_long_prompt_stream_invariance(pretrained):
    """Long-prompt bimodal trace with chunking on: the superstep
    stream, the per-step stream, wave chunks, serving each refill
    alone, AND the unchunked engine all emit byte-identical per-request
    streams — chunking changes when prefill work happens, never what is
    decoded.  The chunked engines' longest uninterruptible prefill op is
    bounded by the chunk width; the one-shot engine's is the long-tail
    prompt.  (Finer-grained chunk edge cases — prompt shorter than one
    chunk, exact chunk multiples, first-token EOS, zero-budget
    admission mid-chunk, deploy/reseed mid-prefill — are pinned in
    tests/test_chunked_prefill.py.)"""
    chunk = 32
    r_ss = _chunk_requests(pretrained)
    e_ss = _chunk_engine(pretrained, 8, chunk=chunk)
    e_ss.serve_stream(list(r_ss))
    assert all(r.done and r.finish_t is not None for r in r_ss)
    assert e_ss.stats.tokens_out == sum(len(r.generated) for r in r_ss)
    assert e_ss.stats.prefill_op_width.max <= chunk
    assert e_ss.stats.prefill_chunks >= len(r_ss)
    # the per-gap prefill budget: no gap past batch x chunk row-tokens
    assert e_ss.stats.prefill_gap_tokens.max <= e_ss.batch * chunk
    assert e_ss.stats.lane_rounds == e_ss.stats.steps * e_ss.batch

    r_one = _chunk_requests(pretrained)
    e_one = _chunk_engine(pretrained, 8, chunk=0)
    e_one.serve_stream(list(r_one))
    assert [r.generated for r in r_one] == [r.generated for r in r_ss], \
        "chunked stream diverged from one-shot refill"
    assert e_one.stats.prefill_op_width.max >= 48   # the long-tail stall

    # one chunk per pipeline per gap (no budget beyond the floor): the
    # same streams, and the budgeted engine's prefill ran in fewer gaps
    r_pc = _chunk_requests(pretrained)
    e_pc = _chunk_engine(pretrained, 8, chunk=chunk)
    e_pc._gap_budget = 0
    e_pc.serve_stream(list(r_pc))
    assert [r.generated for r in r_pc] == [r.generated for r in r_ss], \
        "budgeted chunk plan diverged from one chunk per gap"
    assert e_ss.stats.prefill_gap_tokens.n < e_pc.stats.prefill_gap_tokens.n
    # mid-prefill lanes were accounted separately, not as idle capacity:
    # at one chunk per gap they sit inert across supersteps, and the
    # budget commits them sooner
    assert e_pc.stats.prefill_lane_rounds > 0
    assert e_pc.stats.lane_rounds == e_pc.stats.steps * e_pc.batch
    assert e_ss.stats.prefill_lane_rounds < e_pc.stats.prefill_lane_rounds

    r_st = _chunk_requests(pretrained)
    e_st = _chunk_engine(pretrained, 0, chunk=chunk)
    e_st.serve_stream(list(r_st))
    assert [r.generated for r in r_st] == [r.generated for r in r_ss], \
        "chunked per-step loop diverged from the chunked superstep"

    r_wv = _chunk_requests(pretrained)
    e_wv = _chunk_engine(pretrained, 8, chunk=chunk)
    for i in range(0, len(r_wv), 4):
        e_wv.serve_wave(r_wv[i:i + 4])
    assert [r.generated for r in r_wv] == [r.generated for r in r_ss], \
        "chunked serve_wave diverged (compat wrapper bypassed chunking?)"
    assert e_wv.stats.prefill_op_width.max <= chunk

    e_alone = _chunk_engine(pretrained, 8, chunk=chunk, batch=1)
    for req in r_ss[e_ss.batch:]:
        solo = Request(prompt=list(req.prompt),
                       max_new_tokens=req.max_new_tokens)
        e_alone.serve_wave([solo])
        assert solo.generated == req.generated, \
            "chunk-refilled slot diverged from serving the request alone"


# -------------------------------------------------------------- scheduler
def test_scheduler_fifo_and_lazy_pull():
    pulled = []

    def gen():
        for i in range(6):
            pulled.append(i)
            yield Request(prompt=[1, 2], max_new_tokens=4)

    s = Scheduler(2, gen())
    first = s.admit()
    assert [slot for slot, _ in first] == [0, 1]
    assert len(pulled) == 2, "scheduler must pull lazily"
    assert s.has_work()
    # nothing free -> no admission
    assert s.admit() == []
    # finish slot 1 -> exactly one refill, FIFO order
    s.slots[1].finish()
    freed = s.release_finished()
    assert len(freed) == 1
    nxt = s.admit()
    assert [slot for slot, _ in nxt] == [1]
    assert len(pulled) <= 4


def test_inert_request():
    r = inert_request()
    assert r.done and r.finish_t is not None and r.generated == []
    assert r.max_new_tokens == 0
