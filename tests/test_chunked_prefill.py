"""Chunked refill prefill: the parity/property test tier.

The chunk pipeline's load-bearing invariant is **byte parity**: splitting
a refill's prompt prefill into chunks that interleave with resident
supersteps must change *when* work happens, never *what* is computed —
chunked == one-shot bitwise on the target KV cache lanes, the draft
cache lanes, the first sampled token, and the full emitted stream
(greedy and per-request-keyed sampled).  Two engine-design choices make
this exact rather than approximate, both pinned here:

  * continuation chunks run through the decode path, whose per-position
    projections/attention are bitwise width-stable on this backend (the
    one-shot prefill computes the identical values at a different
    sequence width), and
  * the draft's 3D→D capture fuse is computed as a sum of three
    D-contraction matmuls (``eagle._fuse_inputs``) because a single
    3D-wide contraction tiles differently per row count and would break
    draft-cache parity in ulps.

Batch width is *not* bitwise-stable (ulp-level), so op-level tests
compare at equal refill-batch width — the same robustness contract the
existing refill==serving-alone tests already rely on for argmax /
per-request-keyed categorical sampling.

All tests here run on randomly initialized weights (parity is a property
of the computation, not the model), so the file stays in the fast tier.
"""
import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.configs as C
from repro.core import eagle
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.policy import ServingConfig
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler
from repro.serving.stats import Peak


_MODEL = None


def _get_model():
    """Lazily-built module model (plain function, not a fixture, so the
    hypothesis property tests, which take no fixtures, can reach it
    too)."""
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


def _engine(model, *, rounds=8, chunk=0, greedy=True, batch=4, max_len=96,
            seed=5, **kw):
    cfg, params, dcfg, dparams = model
    return ServingEngine(cfg, params, dcfg, dparams, batch_size=batch,
                         max_len=max_len, gamma=3, seed=seed, greedy=greedy,
                         superstep_rounds=rounds, prefill_chunk=chunk, **kw)


_ENGINES = {}


def _cached_engine(**kw):
    """Engines are shared across tests (jit caches stay warm — compile
    time dominates this file otherwise); ``reset_adaptation`` restores
    the post-construction serving state between uses."""
    key = tuple(sorted(kw.items()))
    eng = _ENGINES.get(key)
    if eng is None:
        eng = _ENGINES[key] = _engine(_get_model(), **kw)
    eng.reset_adaptation(eng.dparams)
    eng.deploy_source = None
    return eng


def _requests(cfg, lens, budgets, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(prompt=list(rng.integers(1, cfg.vocab_size, L)),
                    max_new_tokens=m) for L, m in zip(lens, budgets)]


def _run_pipeline(eng, admitted):
    """Drive one chunk pipeline to completion, chunk by chunk, as the
    stream loop would, and return its staging state."""
    pl = eng._make_pipeline(admitted)
    while not pl.done:
        eng._advance_pipeline(pl)
    return pl


def _valid_region_equal(one_shot, chunked, pad, lengths, seq_axis):
    """Bitwise equality on the per-lane valid region [pad_b, lengths_b)
    along ``seq_axis`` (the masked left-pad region holds
    width-dependent garbage by design — it is never read)."""
    # buffer widths may differ (staging caches are prompt-width, the
    # one-shot reference max_len-width); only the valid region matters
    a, b = np.asarray(one_shot), np.asarray(chunked)
    pos = np.arange(min(a.shape[seq_axis], b.shape[seq_axis]))
    for lane in range(len(pad)):
        sel = np.nonzero((pos >= pad[lane]) & (pos < lengths[lane]))[0]
        av = np.take(np.take(a, sel, axis=seq_axis), lane,
                     axis=seq_axis - 1)
        bv = np.take(np.take(b, sel, axis=seq_axis), lane,
                     axis=seq_axis - 1)
        if not np.array_equal(av, bv):
            return False
    return True


# ------------------------------------------------------ op-level parity
@pytest.mark.slow
@settings(max_examples=5, deadline=None)
@given(st.integers(1, 4), st.integers(0, 10 ** 6))
def test_chunked_refill_op_parity(chunk_idx, seed):
    """Property: for random prompt lengths and chunk sizes, the chunk
    pipeline's staging caches, last-position logits, and first token
    (greedy *and* per-request-keyed sampled) are bitwise identical to a
    one-shot refill prefill of the same batch."""
    model = _get_model()
    cfg, params, dcfg, dparams = model
    chunk = 8 * chunk_idx
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(2, 57)) for _ in range(4)]
    reqs = _requests(cfg, lens, [8] * 4, seed=seed)
    for i, r in enumerate(reqs):
        r.sid = i
    admitted = list(enumerate(reqs))

    eng = _cached_engine(chunk=chunk)
    pl = _run_pipeline(eng, admitted)

    # one-shot reference: same padded shapes (_refill_arrays), the
    # prefill + draft-seed exactly as the legacy _refill_core runs them
    toks, pad, _, _, _, sids = eng._refill_arrays(admitted)
    pre = eng._prefill_fn(params, toks, pad)
    rdc = jax.jit(lambda c, t, p: eagle.seed_refill_cache(
        dcfg, dparams, params["embed"], c, t, p, eng.max_len))(
            pre["captures"], toks, pad)

    pad_np = np.asarray(pad)
    width = toks.shape[1]
    assert pl.width == width
    # target KV lanes, all stacked layer groups (leaves are (R, B, S, ...))
    for key in ("k", "v"):
        assert _valid_region_equal(
            pre["cache"]["body"]["pos0"][key],
            pl.cache["body"]["pos0"][key],
            pad_np, [width] * 4, seq_axis=2), \
            f"target {key} lanes diverged (chunk={chunk}, lens={lens})"
    assert np.array_equal(np.asarray(pre["cache"]["lengths"]),
                          np.asarray(pl.cache["lengths"]))
    # draft cache lanes (batch at axis 0, seq at axis 1)
    dlen = np.asarray(rdc["lengths"])
    assert np.array_equal(dlen, np.asarray(pl.dcache["lengths"]))
    for key in ("k", "v"):
        assert _valid_region_equal(rdc[key], pl.dcache[key], pad_np, dlen,
                                   seq_axis=1), \
            f"draft {key} lanes diverged (chunk={chunk}, lens={lens})"
    # last-position logits and both first-token flavours
    assert np.array_equal(np.asarray(pre["logits"]), np.asarray(pl.logits))
    assert np.array_equal(np.asarray(pre["captures"][:, -1]),
                          np.asarray(pl.caps_last))
    assert np.array_equal(np.asarray(pre["logits"].argmax(-1)),
                          np.asarray(pl.logits.argmax(-1)))
    s1 = eng._pick_sampled_fn(pre["logits"], sids)
    s2 = eng._pick_sampled_fn(pl.logits, sids)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


def test_chunk_sizes_agree_bitwise(model):
    """Any two chunk sizes produce bitwise-identical staging state (both
    equal the one-shot values; this pins them against each other
    directly, including the ragged-first-chunk alignment)."""
    cfg, params, dcfg, dparams = model
    lens = [40, 9, 22, 13]
    reqs = _requests(cfg, lens, [8] * 4)
    for i, r in enumerate(reqs):
        r.sid = i
    admitted = list(enumerate(reqs))
    pls = {}
    for chunk in (8, 16, 32):
        pls[chunk] = _run_pipeline(_cached_engine(chunk=chunk), admitted)
    ref = pls[8]
    for chunk in (16, 32):
        pl = pls[chunk]
        assert np.array_equal(np.asarray(ref.logits),
                              np.asarray(pl.logits))
        pad_np = np.asarray(ref.pad)
        dlen = np.asarray(ref.dcache["lengths"])
        for key in ("k", "v"):
            assert _valid_region_equal(
                ref.cache["body"]["pos0"][key],
                pl.cache["body"]["pos0"][key],
                pad_np, [ref.width] * 4, seq_axis=2)
            assert _valid_region_equal(ref.dcache[key], pl.dcache[key],
                                       pad_np, dlen, seq_axis=1)


# --------------------------------------------------- stream-level parity
BUDGETS = (5, 12, 7, 9, 11, 4, 8, 6)
LENS = (40, 9, 22, 13, 55, 8, 17, 30)   # covers < chunk, == chunk
#                                         multiple, and multi-chunk


def _serve(model, *, rounds, chunk, greedy, budgets=BUDGETS, lens=LENS,
           wave=None, deploy_source=None, **kw):
    cfg = model[0]
    reqs = _requests(cfg, lens, budgets)
    eng = _cached_engine(rounds=rounds, chunk=chunk, greedy=greedy, **kw)
    if deploy_source is not None:
        eng.deploy_source = deploy_source
    if wave:
        for i in range(0, len(reqs), wave):
            eng.serve_wave(reqs[i:i + wave])
    else:
        eng.serve_stream(list(reqs))
    return [list(r.generated) for r in reqs], eng, reqs


@pytest.mark.slow
@pytest.mark.parametrize("greedy", [True, False])
def test_chunked_stream_matches_one_shot(model, greedy):
    """Full emitted streams, chunked vs legacy one-shot refill: byte
    identical — greedy and per-request-keyed sampled.  (chunk=32
    engine-level streams are additionally pinned by the slow-tier
    long-prompt invariance test in test_continuous.py.)"""
    ref, e_ref, _ = _serve(model, rounds=8, chunk=0, greedy=greedy)
    for chunk in ((16,) if greedy else (16, 32)):
        out, eng, reqs = _serve(model, rounds=8, chunk=chunk, greedy=greedy)
        assert out == ref, f"chunk={chunk} greedy={greedy} diverged"
        assert all(r.finish_t is not None for r in reqs)
        assert eng.stats.tokens_out == sum(len(g) for g in out)
        # the pipeline bounded every prefill op by the chunk width
        assert eng.stats.prefill_op_width.max <= chunk
        assert eng.stats.prefill_chunks > eng.stats.refills / 2
    assert e_ref.stats.prefill_op_width.max >= max(LENS)


@pytest.mark.slow
def test_chunked_stepwise_matches_superstep(model):
    """The per-step reference loop with chunking emits the same streams
    as the fused superstep with chunking."""
    ss, _, _ = _serve(model, rounds=8, chunk=16, greedy=True)
    step, _, _ = _serve(model, rounds=0, chunk=16, greedy=True)
    assert step == ss


def test_chunked_wave_matches_stream_with_stats(model):
    """``serve_wave`` on a chunked engine routes through the same chunk
    pipelines (legacy callers cannot silently bypass chunking): same
    streams AND the same serving stats as the equivalent stream."""
    out_w, e_w, _ = _serve(model, rounds=8, chunk=16, greedy=True,
                           budgets=BUDGETS[:4], lens=LENS[:4], wave=4)
    out_s, e_s, _ = _serve(model, rounds=8, chunk=16, greedy=True,
                           budgets=BUDGETS[:4], lens=LENS[:4])
    assert out_w == out_s
    for attr in ("tokens_out", "steps", "dispatches", "refills",
                 "prefill_chunks", "prefill_row_tokens", "completed"):
        assert getattr(e_w.stats, attr) == getattr(e_s.stats, attr), attr
    assert e_w.stats.prefill_op_width.max == e_s.stats.prefill_op_width.max
    # chunking engaged for the wave prologue too
    assert e_w.stats.prefill_op_width.max <= 16
    assert e_w.stats.prefill_chunks > 0


@pytest.mark.slow
def test_chunked_serving_alone_parity(model):
    """Every refilled request under chunking matches serving it alone on
    a fresh chunked batch-1 engine (greedy scheduling invariance)."""
    out, eng, reqs = _serve(model, rounds=8, chunk=16, greedy=True)
    alone = _cached_engine(chunk=16, batch=1)
    for req in reqs[eng.batch:]:
        solo = Request(prompt=list(req.prompt),
                       max_new_tokens=req.max_new_tokens)
        alone.serve_wave([solo])
        assert solo.generated == req.generated


# ------------------------------------------------------------ edge cases
def test_zero_budget_admitted_mid_chunk(model):
    """A zero-budget request admitted while a long prompt is mid-chunk:
    finishes with no tokens, without disturbing neighbouring streams."""
    lens = (55, 8, 8, 8, 9, 10)
    budgets = (12, 3, 4, 3, 0, 6)
    out, eng, reqs = _serve(model, rounds=8, chunk=16, greedy=True,
                            budgets=budgets, lens=lens)
    assert reqs[4].generated == [] and reqs[4].finish_t is not None
    ref, _, _ = _serve(model, rounds=8, chunk=0, greedy=True,
                       budgets=budgets, lens=lens)
    assert out == ref


@pytest.mark.slow
def test_eos_on_first_post_prefill_token(model):
    """EOS as the first token sampled at a pipeline commit: one-token
    stream, immediate finish, slot refilled — chunked == one-shot."""
    lens, budgets = LENS[:6], (6,) * 6
    probe, _, _ = _serve(model, rounds=8, chunk=16, greedy=True,
                         budgets=budgets, lens=lens)
    eos = probe[4][0]   # request 4 is a refill (batch=4): its first
    #                     token commits at a pipeline commit mid-stream
    outs = {}
    for chunk in (0, 16):
        out, eng, reqs = _serve(model, rounds=8, chunk=chunk, greedy=True,
                                budgets=budgets, lens=lens, eos_id=eos)
        outs[chunk] = out
        for r in reqs:
            assert r.done and eos not in r.generated[:-1]
        assert eng.stats.tokens_out == sum(len(g) for g in out)
    assert outs[16] == outs[0]
    assert any(g == [eos] for g in outs[16]), \
        "expected at least one first-token-EOS stream in the probe set"


@pytest.mark.slow
def test_deploy_reseed_lands_mid_prefill(model):
    """A draft deploy (with reseed ring) arriving while lanes are
    mid-prefill must neither crash nor change greedy streams (greedy
    speculative decoding is draft-invariant)."""
    cfg, params, dcfg, dparams = model

    class _Ver:
        def __init__(self, seq, dparams):
            self.seq, self.dparams, self.eval_acc = seq, dparams, 0.0

    new_draft = eagle.draft_init(dcfg, jax.random.key(99))
    calls = {"n": 0}

    def deploy_source():
        calls["n"] += 1
        # publish once, early — while the first long-prompt pipeline is
        # still chunking
        return _Ver(1, new_draft) if calls["n"] >= 2 else None

    ref, _, _ = _serve(model, rounds=8, chunk=16, greedy=True)
    out, eng, _ = _serve(model, rounds=8, chunk=16, greedy=True,
                         deploy_source=deploy_source, reseed_window=12)
    assert out == ref, "deploy mid-prefill changed greedy streams"
    assert eng.stats.deploys == 1 and eng.stats.reseeds == 1


# ---------------------------------------------------- scheduler grouping
def test_refill_groups_partition():
    reqs = [Request(prompt=[1] * n, max_new_tokens=4)
            for n in (3, 9, 40, 12, 33)]
    admitted = list(enumerate(reqs))
    groups = Scheduler.refill_groups(admitted, 16)
    # buckets: 8, 16, 40, 16, 40 -> three groups, FIFO order kept inside
    assert sorted(len(g) for g in groups) == [1, 2, 2]
    flat = [slot for g in groups for slot, _ in g]
    assert sorted(flat) == [0, 1, 2, 3, 4]
    for g in groups:
        widths = {max(8, -(-len(r.prompt) // 8) * 8) for _, r in g}
        assert len(widths) == 1, "group mixes padded-width buckets"
    # disabled chunking: one legacy group
    assert Scheduler.refill_groups(admitted, 0) == [admitted]
    assert Scheduler.refill_groups([], 16) == []


def test_peak_tracker():
    p = Peak()
    assert p.max == 0 and p.mean == 0 and p.n == 0
    for x in (4, 9, 2):
        p.add(x)
    assert p.max == 9 and p.n == 3 and abs(p.mean - 5.0) < 1e-9


def test_ttft_clock_starts_at_admission(model):
    """Admission stamps ``admit_t``; TTFT is measured from it (>= 0 and
    never larger than the arrival-based latency)."""
    out, eng, reqs = _serve(model, rounds=8, chunk=16, greedy=True)
    for r in reqs:
        assert r.admit_t is not None and r.admit_t >= r.arrival_t
        assert r.ttft is not None and r.ttft >= 0.0
        assert r.ttft <= r.latency


# ------------------------------------------------------ per-gap chunk plan
# a 16-lane engine with 8-wide chunks: the per-gap prefill budget is
# 16 x 8 = 128 row-tokens
PLAN_BATCH, PLAN_CHUNK = 16, 8


def _record_gaps(eng, monkeypatch):
    """Log each gap of the engine's chunk pipelines: one entry per
    advance call (either loop), holding the pipelines live at its start
    with their start columns, the chunks it dispatched as (pipeline,
    start column, width), and the pipelines it committed."""
    gaps = []
    obs_chunk = eng._obs_chunk

    def _obs(pl, w):
        gaps[-1]["chunks"].append((pl, pl.pos - w, w))
        obs_chunk(pl, w)

    monkeypatch.setattr(eng, "_obs_chunk", _obs)
    for name in ("_advance_pipelines_ss", "_advance_pipelines_step"):
        def _advance(*a, _fn=getattr(eng, name), **kw):
            before = list(eng._pipelines)
            gap = {"live": [(pl, pl.pos) for pl in before if not pl.ready],
                   "chunks": []}
            gaps.append(gap)
            out = _fn(*a, **kw)
            gap["committed"] = [pl for pl in before
                                if pl not in eng._pipelines]
            return out
        monkeypatch.setattr(eng, name, _advance)
    return gaps


def _plan_engine(rounds=8, commit="cohort"):
    key = ("plan", rounds, commit)
    eng = _ENGINES.get(key)
    if eng is None:
        cfg, params, dcfg, dparams = _get_model()
        eng = _ENGINES[key] = ServingEngine(
            cfg, params, dcfg, dparams, config=ServingConfig(
                batch_size=PLAN_BATCH, max_len=96, gamma=3, seed=5,
                superstep_rounds=rounds, prefill_chunk=PLAN_CHUNK,
                commit=commit))
    eng.reset_adaptation(eng.dparams)
    return eng


@pytest.mark.parametrize("rounds,widths", [
    (8, (64,)),           # one row, 8 chunks: superstep loop
    (0, (64,)),           # the same in the per-step loop
    (8, (16, 40, 64)),    # a cohort of three widths, 120 row-tokens
], ids=["one-row", "one-row-stepwise", "cohort"])
def test_gap_plan_finishes_a_covered_admission_in_one_gap(
        model, monkeypatch, rounds, widths):
    """An admission whose chunks all fit the per-gap budget, made while
    resident lanes decode, dispatches every chunk of every member in the
    gap that admits it, and commits there: fused with the final chunk
    when alone, with its cohort when not."""
    cfg = model[0]
    k = len(widths)
    # k residents retire after their first superstep, freeing k lanes
    # for the admission; the rest keep decoding through it
    reqs = _requests(cfg, [8] * PLAN_BATCH + list(widths),
                     [2] * k + [40] * (PLAN_BATCH - k) + [4] * k)
    eng = _plan_engine(rounds)
    gaps = _record_gaps(eng, monkeypatch)
    eng.serve_stream(list(reqs))
    assert all(r.finish_t is not None for r in reqs)
    late = reqs[PLAN_BATCH:]
    rids = {r.rid for r in late}
    mine = [g for g in gaps
            if any(r.rid in rids for pl, _, _ in g["chunks"]
                   for _, r in pl.admitted)]
    assert len(mine) == 1, "the admission's chunks spread over gaps"
    gap = mine[0]
    assert sum(pl.rows * w for pl, _, w in gap["chunks"]) \
        <= PLAN_BATCH * PLAN_CHUNK
    assert len(gap["chunks"]) == sum(-(-w // PLAN_CHUNK) for w in widths)
    assert sorted(r.rid for pl in gap["committed"]
                  for _, r in pl.admitted) == sorted(rids)
    # residents decoded on across the admission: not the cold start
    assert gap is not gaps[0]
    assert max(r.first_token_round for r in late) \
        < min(r.finish_round for r in reqs[k:PLAN_BATCH])


_PLAN_RNG = np.random.default_rng(17)
_PLAN_LENS = [int(x) for x in _PLAN_RNG.integers(8, 73, 32)]
_PLAN_BUDGETS = [int(x) for x in _PLAN_RNG.integers(1, 14, 32)]


@pytest.mark.parametrize("rounds,commit", [
    (8, "cohort"), (8, "eager"), (0, "cohort")])
def test_gap_plan_budget_and_floor(model, monkeypatch, rounds, commit):
    """In every gap each live pipeline advances at least one chunk (the
    floor), and chunks beyond the floor only while the gap stays within
    ``batch x chunk`` row-tokens.  Mixed widths admitted mid-stream, so
    some gaps do spend budget beyond the floor; every op stays one chunk
    wide and every request gets its full budget."""
    cfg = model[0]
    lens, budgets = _PLAN_LENS, _PLAN_BUDGETS
    reqs = _requests(cfg, lens, budgets)
    eng = _plan_engine(rounds, commit)
    gaps = _record_gaps(eng, monkeypatch)
    eng.serve_stream(list(reqs))
    budget = PLAN_BATCH * PLAN_CHUNK
    extra = 0
    for gap in gaps:
        floor = sum(pl.rows * pl.width_at(pos) for pl, pos in gap["live"])
        spent = sum(pl.rows * w for pl, _, w in gap["chunks"])
        assert {id(pl) for pl, _ in gap["live"]} \
            <= {id(pl) for pl, _, _ in gap["chunks"]}, "a pipeline starved"
        assert spent <= max(budget, floor)
        extra += len(gap["chunks"]) - len(gap["live"])
    assert extra > 0, "no gap spent its budget beyond the floor"
    assert eng.stats.prefill_op_width.max <= PLAN_CHUNK
    assert eng.stats.prefill_row_tokens == sum(
        pl.rows * w for g in gaps for pl, _, w in g["chunks"])
    assert all(len(r.generated) == r.max_new_tokens for r in reqs)


def test_gap_plan_same_in_both_loops(model, monkeypatch):
    """The per-step and superstep loops dispatch the same chunk sequence,
    gap by gap, for the same admission: both take the one plan."""
    cfg = model[0]
    lens = (72, 9, 40, 64, 17, 33, 56, 8, 72, 24)
    seqs = {}
    for rounds in (8, 0):
        reqs = _requests(cfg, lens, [6] * len(lens))
        eng = _plan_engine(rounds)
        gaps = _record_gaps(eng, monkeypatch)
        eng.serve_stream(list(reqs))
        seqs[rounds] = [[(pl.order, a, w) for pl, a, w in g["chunks"]]
                        for g in gaps if g["chunks"]]
    assert len(seqs[8]) > 1, "the admission fit one gap; widen it"
    assert seqs[0] == seqs[8]
