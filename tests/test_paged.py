"""Paged KV cache: allocator units, paged == dense property tier, and
paged kernel-vs-ref sweeps.

The paged memory model's load-bearing invariant is the same one the
chunk pipeline pins: changing *where* KV bytes live (fixed pages behind
a block table instead of a private dense lane) must never change *what*
is computed.  The engine achieves this by construction — paged decode
scatters through the table, gathers the dense per-lane view back, and
runs the identical attention dispatch — so paged serving is bitwise
equal to dense serving on full emitted streams, greedy and
per-request-keyed sampled, one-shot and chunked refill, superstep and
stepwise.  The property tier here pins exactly that, over random prompt
lengths, budgets, and chunk sizes.

The host-side allocator is plain numpy bookkeeping, so its invariants
(refcounts, reservation atomicity, registry eviction, COW forks, leak
freedom) are pinned by direct unit tests.  The Pallas paged kernels are
swept against their gather-densely oracles in interpret mode, the same
contract as tests/test_kernels.py.

All tests run on randomly initialized weights (parity is a property of
the computation, not the model), so the file stays in the fast tier.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.configs as C
from repro.core import eagle, paging
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.policy import ServingConfig
from repro.serving.request import Request


# ==================================================== allocator units
def _alloc(num_pages=16, page_size=8, batch=4, max_len=64, **kw):
    return paging.PageAllocator(num_pages, page_size, batch, max_len, **kw)


def test_allocator_reserve_free_roundtrip():
    a = _alloc()
    assert a.reserve(0, 20)                      # 3 pages of 8
    assert a.pages_in_use == 3 and a.peak_in_use == 3
    assert (a.table[0, :3] != a.trash).all()
    assert (a.table[0, 3:] == a.trash).all()
    a.free_lane(0)
    a.free_lane(0)                               # idempotent
    a.assert_clean()


def test_allocator_reserve_atomic_on_oom():
    a = _alloc(num_pages=4)
    assert a.reserve(0, 32)                      # takes the whole pool
    assert not a.can_reserve(8)
    assert not a.reserve(1, 8)                   # fails...
    assert (a.table[1] == a.trash).all()         # ...leaving lane 1 untouched
    a.free_lane(0)
    assert a.reserve(1, 8)                       # freed pages come back
    a.free_lane(1)
    a.assert_clean()


def test_allocator_double_reserve_raises():
    a = _alloc()
    assert a.reserve(0, 8)
    with pytest.raises(AssertionError):
        a.reserve(0, 8)
    a.free_lane(0)
    a.assert_clean()


def test_prefix_publish_lookup_adopt_refcounts():
    a = _alloc()
    assert a.reserve(0, 24)
    key = a.prefix_key(2, 24, 0, list(range(17)), 2)
    a.publish(key, 0, 2)
    donor = tuple(int(p) for p in a.table[0, :2])
    assert a.lookup(key) == donor
    assert [int(a.ref[p]) for p in donor] == [2, 2]   # lane + registry
    # a borrower with its own private reservation adopts: the duplicate
    # pages for the shared range return to the free list
    assert a.reserve(1, 24)
    free_before = a.free_pages
    a.adopt(1, donor)
    assert tuple(int(p) for p in a.table[1, :2]) == donor
    assert a.free_pages == free_before + 2
    assert a.prefix_hits == 1
    assert a.prefix_tokens_saved == 2 * a.page_size
    # the donor retires: shared pages survive through the registry ref
    a.free_lane(0)
    assert a.lookup(key) == donor
    a.free_lane(1)
    a.release_prefix_cache()
    a.assert_clean()


def test_prefix_key_covers_provenance():
    a = _alloc()
    toks = list(range(20))
    k1 = a.prefix_key(2, 24, 0, toks, 2)
    # tokens past column n_pages * P + 1 are outside the provenance
    assert k1 == a.prefix_key(2, 24, 0, toks[:17] + [99, 99, 99], 2)
    # everything the page bytes depend on changes the key
    assert k1 != a.prefix_key(4, 24, 0, toks, 2)          # refill rows
    assert k1 != a.prefix_key(2, 32, 0, toks, 2)          # op width
    assert k1 != a.prefix_key(2, 24, 1, toks, 2)          # left pad
    assert k1 != a.prefix_key(2, 24, 0, toks, 2, salt=1)  # deploy seq
    t2 = list(toks)
    t2[16] = 77                   # the draft's one-token lookahead column
    assert k1 != a.prefix_key(2, 24, 0, t2, 2)


def test_registry_lru_eviction_under_pressure():
    a = _alloc(num_pages=4)
    assert a.reserve(0, 16)
    key = a.prefix_key(1, 16, 0, list(range(17)), 2)
    a.publish(key, 0, 2)
    a.free_lane(0)                # registry is now the pages' sole owner
    assert a.free_pages == 2
    assert a.can_reserve(32)      # an eviction sweep covers the deficit
    assert a.reserve(1, 32)       # forces the sweep
    assert a.evictions == 1
    assert a.lookup(key) is None
    a.free_lane(1)
    a.assert_clean()


def test_eviction_skips_lane_mapped_entries():
    a = _alloc(num_pages=4)
    assert a.reserve(0, 16)
    key = a.prefix_key(1, 16, 0, list(range(17)), 2)
    a.publish(key, 0, 2)          # lane 0 still maps these pages
    assert not a.can_reserve(24)  # 3 pages wanted, 2 free, none evictable
    assert not a.reserve(1, 24)
    assert a.lookup(key) is not None   # the mapped entry survived
    a.free_lane(0)
    a.release_prefix_cache()
    a.assert_clean()


def test_cow_fork_and_copy_page():
    a = _alloc()
    assert a.reserve(0, 24)       # 3 pages; publish the first 2
    key = a.prefix_key(1, 24, 0, list(range(17)), 2)
    a.publish(key, 0, 2)
    # exclusively-owned page: write in place
    assert a.fork_for_write(0, 2) is None
    # shared page (ref 2): fork repoints the lane at a fresh page
    src, dst = a.fork_for_write(0, 0)
    assert int(a.table[0, 0]) == dst and src != dst
    assert a.cow_forks == 1 and int(a.ref[src]) == 1
    # the device half duplicates the bytes
    pool = jnp.arange(17 * 8, dtype=jnp.float32).reshape(17, 8, 1, 1)
    pool = paging.copy_page(pool, src, dst)
    assert np.array_equal(np.asarray(pool[dst]), np.asarray(pool[src]))
    a.free_lane(0)
    a.release_prefix_cache()
    a.assert_clean()


# ================================================= device page helpers
def test_write_gather_roundtrip_and_mask():
    pool = jnp.zeros((7, 4, 2, 3))                       # 6 pages + trash
    tbl = jnp.array([[0, 1, 2], [3, 4, 5]], jnp.int32)   # max_len 12
    rows = jnp.arange(2 * 10 * 2 * 3, dtype=jnp.float32
                      ).reshape(2, 10, 2, 3) + 1.0
    pool = paging.write_rows_paged(pool, tbl, rows,
                                   jnp.array([True, True]))
    view = paging.gather_view(pool, tbl)
    assert view.shape == (2, 12, 2, 3)
    assert np.array_equal(np.asarray(view[:, :10]), np.asarray(rows))
    # masked lanes write to the trash page; mapped pages stay untouched
    pool2 = paging.write_rows_paged(pool, tbl, rows * 7.0,
                                    jnp.array([False, True]))
    view2 = paging.gather_view(pool2, tbl)
    assert np.array_equal(np.asarray(view2[0, :10]), np.asarray(rows[0]))
    assert np.array_equal(np.asarray(view2[1, :10]),
                          np.asarray(rows[1] * 7.0))
    # explicit-row gather (the prefix-resume read path)
    got = paging.gather_rows_paged(pool, jnp.array([[3, 4]], jnp.int32), 6)
    assert np.array_equal(np.asarray(got[0]), np.asarray(view[1, :6]))


def test_scatter_kv_paged_drops_out_of_bounds():
    pool = jnp.zeros((3, 4, 1, 1))                        # 2 pages + trash
    tbl = jnp.array([[0, 1]], jnp.int32)                  # max_len 8
    new = jnp.ones((1, 4, 1, 1))
    out = paging.scatter_kv_paged(pool, tbl, new,
                                  jnp.array([6], jnp.int32))
    lane = np.asarray(paging.gather_view(out, tbl))[0, :, 0, 0]
    # positions 6, 7 land; 8, 9 overflow the lane window -> trash page,
    # exactly where dense scatter's clamped writes get dropped
    assert lane.tolist() == [0, 0, 0, 0, 0, 0, 1, 1]
    assert np.asarray(out[:2]).sum() == 2.0               # real pages clean


# =============================================== paged kernels vs refs
def test_flash_attn_paged_kernel_vs_ref():
    from repro.kernels.flash_attn.kernel import flash_attention_paged
    from repro.kernels.flash_attn.ref import flash_attention_paged_ref
    rng = np.random.default_rng(11)
    b, s, hq, hk, d, p = 2, 64, 4, 2, 64, 16
    n_pg = s // p
    pool_shape = (b * n_pg + 1, p, hk, d)
    k_pool = jnp.asarray(rng.normal(size=pool_shape), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=pool_shape), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    perm = rng.permutation(b * n_pg)             # non-contiguous mapping
    tbl = jnp.asarray(perm.reshape(b, n_pg), jnp.int32)
    out = flash_attention_paged(q, k_pool, v_pool, tbl, causal=True,
                                block_q=32, interpret=True)
    ref = flash_attention_paged_ref(q, k_pool, v_pool, tbl, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_verify_attn_paged_kernel_vs_ref():
    from repro.kernels.verify_attn.kernel import verify_attention_paged
    from repro.kernels.verify_attn.ref import verify_attention_paged_ref
    rng = np.random.default_rng(13)
    b, t, hq, hk, d, p, n_tbl = 2, 4, 4, 2, 64, 16, 8
    trash = b * n_tbl
    k_pool = jnp.asarray(rng.normal(size=(trash + 1, p, hk, d)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(trash + 1, p, hk, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, t, hq, d)), jnp.float32)
    lengths = jnp.asarray(rng.integers(t + 1, 90, size=(b,)), jnp.int32)
    pad = jnp.minimum(jnp.asarray(rng.integers(0, 16, size=(b,)),
                                  jnp.int32), lengths - 1)
    # map pages covering [0, lengths + t); point the rest at trash (the
    # allocator's reservation invariant — trash keys are masked anyway)
    perm = rng.permutation(trash)
    tbl = np.full((b, n_tbl), trash, np.int32)
    for lane in range(b):
        need = -(-int(lengths[lane] + t) // p)
        tbl[lane, :need] = perm[lane * n_tbl:lane * n_tbl + need]
    tbl = jnp.asarray(tbl)
    out = verify_attention_paged(q, k_pool, v_pool, tbl, lengths, pad,
                                 interpret=True)
    ref = verify_attention_paged_ref(q, k_pool, v_pool, tbl, lengths, pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_ops_dispatch():
    """CPU dispatch goes to the oracle; force_kernel runs interpret."""
    from repro.kernels.flash_attn.ops import flash_attn_paged
    from repro.kernels.verify_attn.ops import verify_attn_paged
    rng = np.random.default_rng(17)
    k_pool = jnp.asarray(rng.normal(size=(9, 16, 2, 64)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(9, 16, 2, 64)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 64)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(8).reshape(1, 8), jnp.int32)
    a = flash_attn_paged(q, k_pool, v_pool, tbl[:, :4])
    b = flash_attn_paged(q, k_pool, v_pool, tbl[:, :4], force_kernel=True,
                         block_q=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    lengths = jnp.array([100], jnp.int32)
    a = verify_attn_paged(q[:, :4], k_pool, v_pool, tbl, lengths)
    b = verify_attn_paged(q[:, :4], k_pool, v_pool, tbl, lengths,
                          force_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ====================================== engine: paged == dense streams
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


_ENGINES = {}


def _cached_engine(**kw):
    """Engines are shared across tests (jit caches stay warm — compile
    time dominates this file otherwise); ``reset_adaptation`` restores
    the post-construction serving state between uses."""
    key = tuple(sorted(kw.items()))
    eng = _ENGINES.get(key)
    if eng is None:
        cfg, params, dcfg, dparams = _get_model()
        config = ServingConfig(batch_size=2, max_len=96, gamma=3, seed=5,
                               **dict({"superstep_rounds": 4}, **kw))
        eng = _ENGINES[key] = ServingEngine(cfg, params, dcfg, dparams,
                                            config=config)
    eng.reset_adaptation(eng.dparams)
    eng.deploy_source = None
    return eng


def _requests(cfg, lens, budgets, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(prompt=list(rng.integers(1, cfg.vocab_size, L)),
                    max_new_tokens=m) for L, m in zip(lens, budgets)]


def _streams(eng, reqs):
    """Serve, leak-check, and key streams by creation index (request ids
    are globally monotonic, so dense/paged runs would never collide)."""
    eng.serve_stream(list(reqs))
    if eng.allocator is not None:
        eng.release_prefix_cache()
        eng.allocator.assert_clean()
    return {i: list(r.generated) for i, r in enumerate(reqs)}


def _parity_case(lens, budgets, seed, *, chunk=0, greedy=True, rounds=4,
                 **paged_kw):
    cfg, *_ = _get_model()
    dense = _streams(
        _cached_engine(greedy=greedy, superstep_rounds=rounds,
                       prefill_chunk=chunk),
        _requests(cfg, lens, budgets, seed=seed))
    eng = _cached_engine(greedy=greedy, superstep_rounds=rounds,
                         prefill_chunk=chunk, page_size=8, **paged_kw)
    paged = _streams(eng, _requests(cfg, lens, budgets, seed=seed))
    assert dense == paged
    return eng


@pytest.mark.slow
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 1), st.integers(0, 1), st.integers(0, 10 ** 6))
def test_paged_stream_parity_property(chunk_idx, greedy_idx, seed):
    """Property: for random prompt lengths, budgets, chunk modes, and
    greedy/per-request-keyed sampled decoding, a paged engine emits
    byte-identical streams to the dense engine and returns every page
    to the free list at drain."""
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(2, 40)) for _ in range(6)]
    budgets = [int(rng.integers(2, 9)) for _ in range(6)]
    _parity_case(lens, budgets, seed, chunk=8 * chunk_idx,
                 greedy=bool(greedy_idx))


@pytest.mark.slow
def test_paged_stream_parity_stepwise():
    """The per-step reference loop (superstep_rounds=0) takes the
    stepwise dispatch path — same parity contract."""
    _parity_case([5, 30, 11, 23], [6, 4, 8, 5], seed=21, rounds=0)


def test_paged_admission_defers_under_page_pressure():
    """A pool too small for two concurrent reservations serves the
    same trace by deferring admissions (never by corrupting lanes):
    streams stay byte-identical, every request completes, and the
    deferral counter records the backpressure."""
    lens = [int(x) for x in
            np.random.default_rng(9).integers(3, 13, size=8)]
    budgets = [int(x) for x in
               np.random.default_rng(10).integers(3, 9, size=8)]
    # P=8, num_pages=4: one lane's reservation (width + budget + gamma
    # + 1 <= 28 tokens = 4 pages) fills the pool, so lanes serialize
    eng = _parity_case(lens, budgets, seed=33, num_pages=4)
    assert eng.stats.admission_deferrals > 0
    assert eng.stats.completed == 8
    assert eng.stats.pages_peak <= 4


@pytest.mark.slow
def test_paged_prefix_sharing_hits_and_parity():
    """Requests sharing a long system prompt: chunked paged serving
    adopts the published prefix pages (registry hits, prefill row-token
    work saved) while streams stay byte-identical to dense."""
    prefix = [7] * 20

    def reqs():
        rng = np.random.default_rng(3)
        return [Request(prompt=prefix + [int(t) for t in
                                         rng.integers(1, 500, 3)],
                        max_new_tokens=6 + (i % 3))
                for i in range(8)]


    dense = _streams(_cached_engine(greedy=True, superstep_rounds=4,
                                    prefill_chunk=8), reqs())
    eng = _cached_engine(greedy=True, superstep_rounds=4, prefill_chunk=8,
                         page_size=8)
    paged = _streams(eng, reqs())
    assert dense == paged
    assert eng.stats.prefix_hits > 0
    assert eng.stats.prefix_tokens_saved > 0


# ============================================= paged deploy re-seed
@pytest.mark.slow
def test_paged_reseed_deploy_stream_parity():
    """reseed_window + paged serving compose (the old exclusivity is
    lifted): the paged re-seed op rewrites resident lanes' draft rows
    through their block-table rows in place.  A mid-stream deploy with
    re-seed on a paged engine leaves greedy streams byte-identical to
    the same deploy on a dense engine (and both to the deploy-free
    run, since the target verifies every draft)."""
    cfg, params, dcfg, dparams = _get_model()
    draft_b = eagle.draft_init(dcfg, jax.random.key(99))

    class _AfterN:
        def __init__(self, n):
            self.n, self.polls = n, 0

        def __call__(self):
            from repro.training.service import DraftVersion
            self.polls += 1
            return (DraftVersion(1, draft_b, 0.9)
                    if self.polls >= self.n else None)

    lens, budgets = [6, 9, 5, 8], [16, 12, 14, 10]
    dense = _streams(_cached_engine(greedy=True, reseed_window=12),
                     _requests(cfg, lens, budgets))

    eng = _cached_engine(greedy=True, reseed_window=12, page_size=8)
    eng.deploy_source = _AfterN(3)
    paged = _streams(eng, _requests(cfg, lens, budgets))
    assert eng.stats.deploys == 1 and eng.stats.reseeds == 1
    assert paged == dense


# ======================================================= config guards


def test_paged_rejects_indivisible_max_len():
    cfg, params, dcfg, dparams = _get_model()
    with pytest.raises(ValueError):
        ServingEngine(cfg, params, dcfg, dparams,
                      config=ServingConfig(batch_size=2, max_len=96,
                                           page_size=7))


def test_tide_config_mirrors_paging_knobs():
    from repro.core.tide import TideConfig
    tc = TideConfig(page_size=8, num_pages=40)
    assert tc.serving.page_size == 8 and tc.serving.num_pages == 40
    tc2 = TideConfig(serving=ServingConfig(page_size=16, num_pages=24,
                                           share_prefix=False))
    assert (tc2.page_size, tc2.num_pages, tc2.share_prefix) == (16, 24,
                                                                False)
