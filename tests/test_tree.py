"""Tree speculation: tree-masked kernels vs oracles, tree acceptance
units, and the width=1 == chain bitwise-parity property tier.

The tree engine's load-bearing invariant is the degenerate-shape
contract: ``tree_width=1`` IS the linear gamma-chain — branch 0 drafts
with the chain's exact randomness, the 1-branch tree mask reduces to
the causal chain mask, depth-1 acceptance consumes the chain's uniform
stream against the unmasked target density, and ``compact_tree_cache``
at sel == 0 is a byte-preserving same-position copy.  So width=1 must
be *bitwise* identical to the chain engine on full emitted streams —
greedy and per-request-keyed sampled, superstep and stepwise, dense
and paged.  The property tier here pins exactly that over random
prompt lengths, budgets, and seeds.

Wider trees change WHAT is accepted (longest root path instead of one
chain prefix) but not WHERE bytes land: paged tree serving must stay
byte-identical to dense tree serving, and every page (including the
scratch rows the rejected branches wrote through the trash page) must
be back on the free list after drain.

All tests run on randomly initialized weights (parity is a property of
the computation, not the model), so the file stays in the fast tier.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

import repro.configs as C
from repro.core import eagle, speculative as spec
from repro.models import transformer as T
from repro.serving.engine import ServingEngine
from repro.serving.policy import ServingConfig, SpeculationPolicy
from repro.serving.request import Request

from conftest import MIXER_CFGS


@pytest.fixture(scope="module", autouse=True)
def _fresh_compile_state():
    """Drop every executable the preceding ~200 tests compiled before
    this module's engine compiles run.  Late in the full-tier session
    the accumulated LLVM-JIT state makes ``backend_compile`` segfault
    on this host when the stream-superstep program compiles; the same
    compiles are rock-solid from a fresh process, and clearing the jit
    caches here reproduces those standalone conditions."""
    import gc
    jax.clear_caches()
    gc.collect()
    yield


# ========================================== tree kernels vs CPU oracles
TREE_SHAPES = [(1, 3, 0), (2, 3, 0), (3, 2, 0), (2, 4, 6), (4, 2, 5)]


@pytest.mark.parametrize("w,g,window", TREE_SHAPES)
def test_verify_attn_tree_kernel_vs_ref(w, g, window):
    """The tree-masked Pallas kernel (interpret mode) against the dense
    gather oracle, including sliding-window shapes."""
    from repro.kernels.verify_attn import ops
    from repro.kernels.verify_attn.ref import verify_attention_tree_ref

    t = w * g + 1
    b, hq, hk, d, s = 2, 4, 2, 16, 64
    ks = jax.random.split(jax.random.fold_in(jax.random.key(0),
                                             w * 10 + g), 3)
    q = jax.random.normal(ks[0], (b, t, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hk, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hk, d), jnp.float32)
    lengths = jnp.array([17, 30], jnp.int32)
    pad = jnp.array([3, 0], jnp.int32)
    ref = verify_attention_tree_ref(q, k, v, lengths, pad, tree=(w, g),
                                    window=window)
    out = ops.verify_attn(q, k, v, lengths, pad, window=window,
                          force_kernel=True, tree=(w, g), block_kv=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("w,g,window", TREE_SHAPES)
def test_verify_attn_tree_paged_kernel_vs_ref(w, g, window):
    """Paged tree kernel: same bytes behind a block table + trash page."""
    from repro.kernels.verify_attn import ops
    from repro.kernels.verify_attn.ref import (
        verify_attention_tree_paged_ref)

    t = w * g + 1
    b, hq, hk, d, s, p = 2, 4, 2, 16, 64, 16
    n_pg = s // p
    ks = jax.random.split(jax.random.fold_in(jax.random.key(1),
                                             w * 10 + g), 3)
    q = jax.random.normal(ks[0], (b, t, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hk, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hk, d), jnp.float32)
    k_pool = jnp.concatenate([k.reshape(b * n_pg, p, hk, d),
                              jnp.zeros((1, p, hk, d), jnp.float32)], 0)
    v_pool = jnp.concatenate([v.reshape(b * n_pg, p, hk, d),
                              jnp.zeros((1, p, hk, d), jnp.float32)], 0)
    tbl = jnp.arange(b * n_pg, dtype=jnp.int32).reshape(b, n_pg)
    lengths = jnp.array([17, 30], jnp.int32)
    pad = jnp.array([3, 0], jnp.int32)
    ref = verify_attention_tree_paged_ref(q, k_pool, v_pool, tbl, lengths,
                                          pad, tree=(w, g), window=window)
    out = ops.verify_attn_paged(q, k_pool, v_pool, tbl, lengths, pad,
                                window=window, force_kernel=True,
                                tree=(w, g))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# =============================================== tree acceptance units
def _onehot_logits(ids, v=8):
    """(..., V) logits whose argmax/softmax mass sits on ``ids``."""
    return 10.0 * jax.nn.one_hot(jnp.asarray(ids), v, dtype=jnp.float32)


def test_tree_path_slots_layout():
    """Root at slot 0; branch sel's depth-j node at 1 + sel*γ + (j-1)."""
    slots = spec.tree_path_slots(jnp.array([0, 1], jnp.int32), 3)
    assert slots.tolist() == [[0, 1, 2, 3], [0, 4, 5, 6]]
    # width=1 trees only have branch 0: the identity chain layout
    one = spec.tree_path_slots(jnp.zeros((4,), jnp.int32), 3)
    assert (np.asarray(one) == np.arange(4)).all()


def test_verify_tree_greedy_accepts_longest_branch():
    """The target's greedy walk rejects branch 0 at depth 1 but matches
    branch 1 to the leaf: full accept on branch 1 with the leaf-slot
    bonus."""
    draft = jnp.asarray([[[1, 2], [3, 4]]], jnp.int32)   # (1, w=2, γ=2)
    # slots: 0=root, 1-2=branch0, 3-4=branch1
    tgt = _onehot_logits([[3, 7, 7, 4, 6]])              # (1, 5, V)
    n_acc, sel, bonus = spec.verify_tree_greedy(tgt, draft)
    assert (int(n_acc[0]), int(sel[0]), int(bonus[0])) == (2, 1, 6)


def test_verify_tree_greedy_rejects_all_branches():
    """No sibling matches the root argmax: n_acc=0, the bonus is the
    target's root correction (chain semantics)."""
    draft = jnp.asarray([[[1, 2], [3, 4]]], jnp.int32)
    tgt = _onehot_logits([[5, 7, 7, 7, 7]])
    n_acc, sel, bonus = spec.verify_tree_greedy(tgt, draft)
    assert (int(n_acc[0]), int(bonus[0])) == (0, 5)


def test_verify_tree_greedy_partial_depth():
    """Branch 0 matches depth 1 only: accept 1, bonus from its slot."""
    draft = jnp.asarray([[[1, 2], [3, 4]]], jnp.int32)
    tgt = _onehot_logits([[1, 6, 7, 7, 7]])   # slot1 argmax 6 != 2
    n_acc, sel, bonus = spec.verify_tree_greedy(tgt, draft)
    assert (int(n_acc[0]), int(sel[0]), int(bonus[0])) == (1, 0, 6)


def test_verify_tree_width1_matches_chain_rules():
    """width=1 tree acceptance == the chain verifiers, greedy and
    sampled, on random logits (op-for-op reduction)."""
    ks = jax.random.split(jax.random.key(3), 4)
    b, g, v = 4, 3, 32
    tgt = jax.random.normal(ks[0], (b, g + 1, v), jnp.float32)
    dlog = jax.random.normal(ks[1], (b, g, v), jnp.float32)
    dtok = jax.random.randint(ks[2], (b, g), 0, v, jnp.int32)
    n_c, bonus_c = spec.verify_greedy(tgt, dtok)
    n_t, sel, bonus_t = spec.verify_tree_greedy(tgt, dtok[:, None, :])
    assert (np.asarray(n_c) == np.asarray(n_t)).all()
    assert (np.asarray(bonus_c) == np.asarray(bonus_t)).all()
    assert (np.asarray(sel) == 0).all()
    n_c, bonus_c = spec.verify_sample(ks[3], tgt, dlog, dtok)
    n_t, _, bonus_t = spec.verify_tree_sample(
        ks[3], tgt, dlog[:, None], dtok[:, None, :])
    assert (np.asarray(n_c) == np.asarray(n_t)).all()
    assert (np.asarray(bonus_c) == np.asarray(bonus_t)).all()


# ============================================= draft tree + step level
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


def _spec_start(b=3, s=12, g=3, max_len=96):
    cfg, params, dcfg, dparams = _get_model()
    toks = jax.random.randint(jax.random.key(2), (b, s), 0,
                              cfg.vocab_size)
    pre = T.prefill(cfg, params, toks, max_len=max_len)
    first = pre["logits"].argmax(-1).astype(jnp.int32)
    dcache = eagle.init_draft_cache(dcfg, b, max_len)
    dcache = jax.jit(lambda dc, p, t: spec.seed_draft_cache(
        cfg, dcfg, params, dparams, dc, p, t))(dcache, pre, toks)
    carry = spec.init_carry(cfg, dcfg, pre, first, g)
    return pre["cache"], dcache, carry


def _propose_inputs(g=3):
    """(h_last, first_logits, dcache) at the post-extend frontier."""
    cfg, params, dcfg, dparams = _get_model()
    cache, dcache, carry = _spec_start(g=g)
    ext_logits, ext_h, dcache = jax.jit(
        lambda dc, f, t, a: eagle.draft_extend(
            dcfg, dparams, params["embed"], dc, f, t, a))(
        dcache, carry.feats, carry.tokens, carry.advance)
    last = (carry.advance - 1)[:, None, None]
    h_last = jnp.take_along_axis(ext_h, last, axis=1)[:, 0]
    first_logits = jnp.take_along_axis(ext_logits, last, axis=1)[:, 0]
    return h_last, first_logits, dcache


def _propose_fn(width=0, gamma=3):
    """Jitted propose entry point (the compile path the engine uses —
    eager scan compiles proved flaky on this host's 8MB-stack LLVM)."""
    cfg, params, dcfg, dparams = _get_model()
    if width:
        return jax.jit(lambda dc, h, fl: eagle.draft_propose_tree(
            dcfg, dparams, params["embed"], dc, h, fl, gamma, width))
    return jax.jit(lambda dc, h, fl: eagle.draft_propose(
        dcfg, dparams, params["embed"], dc, h, fl, gamma))


def test_draft_propose_tree_width1_is_chain():
    h, fl, dc = _propose_inputs()
    ct, cl, cc = _propose_fn()(dc, h, fl)
    tt, tl, tc = _propose_fn(width=1)(dc, h, fl)
    assert (np.asarray(ct) == np.asarray(tt[:, 0])).all()
    assert (np.asarray(cl) == np.asarray(tl[:, 0])).all()
    eq = jax.tree.map(lambda x, y: bool((np.asarray(x)
                                         == np.asarray(y)).all()), cc, tc)
    assert all(jax.tree.leaves(eq))


def test_draft_propose_tree_sibling_roots_distinct():
    """Sibling depth-1 tokens are distinct per lane (top-k first
    continuations, not k copies of the argmax)."""
    h, fl, dc = _propose_inputs()
    toks, _, _ = _propose_fn(width=4)(dc, h, fl)
    first = np.asarray(toks[:, :, 0])                       # (B, w)
    for lane in first:
        assert len(set(lane.tolist())) == len(lane), lane


def _step_fns(greedy, width):
    cfg, params, dcfg, dparams = _get_model()
    chain = jax.jit(lambda c, dc, cr, k: spec.spec_decode_step(
        cfg, dcfg, params, dparams, c, dc, cr, gamma=3, greedy=greedy,
        keys=k))
    tree = jax.jit(lambda c, dc, cr, k: spec.tree_decode_step(
        cfg, dcfg, params, dparams, c, dc, cr, gamma=3, width=width,
        greedy=greedy, keys=k))
    return chain, tree


@pytest.mark.parametrize("greedy", [True, False])
def test_tree_step_width1_bitwise_chain(greedy):
    """Multi-round step-level parity: width=1 ``tree_decode_step``
    produces byte-identical caches, carries, and commits to
    ``spec_decode_step`` under per-lane keys."""
    start = _spec_start()
    sa, sb = start, start
    b = start[2].tokens.shape[0]
    chain_fn, tree_fn = _step_fns(greedy, 1)
    for i in range(4):
        keys = jax.vmap(lambda s, _i=i: jax.random.fold_in(
            jax.random.fold_in(jax.random.key(7), s), _i))(jnp.arange(b))
        oa = chain_fn(*sa, keys)
        ob = tree_fn(*sb, keys)
        for field in ("tokens", "n_commit", "n_acc", "target_logits",
                      "captures"):
            np.testing.assert_array_equal(
                np.asarray(oa[field]), np.asarray(ob[field]),
                err_msg=f"round {i} field {field}")
        for part in ("cache", "dcache"):
            eq = jax.tree.map(
                lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()),
                oa[part], ob[part])
            assert all(jax.tree.leaves(eq)), (i, part, eq)
        sa = (oa["cache"], oa["dcache"], oa["carry"])
        sb = (ob["cache"], ob["dcache"], ob["carry"])


def test_tree_step_wider_never_shorter_greedy():
    """A wider greedy tree can only add accepted tokens: branch 0 IS
    the chain draft, so the longest root path is >= the chain prefix,
    round for round from the same state."""
    sa = sb = _spec_start()
    b = sa[2].tokens.shape[0]
    chain_fn, tree_fn = _step_fns(True, 3)
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.key(7), s))(
        jnp.arange(b))
    for _ in range(3):
        oa = chain_fn(*sa, keys)
        ob = tree_fn(*sb, keys)
        assert (np.asarray(ob["n_acc"]) >= np.asarray(oa["n_acc"])).all()
        sa = (oa["cache"], oa["dcache"], oa["carry"])
        sb = (ob["cache"], ob["dcache"], ob["carry"])


# ================================== engine: tree streams == chain/dense
_ENGINES = {}


def _cached_engine(**kw):
    """Engines shared across tests (compile time dominates otherwise);
    ``reset_adaptation`` restores post-construction serving state."""
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
    eng.serve_stream(list(reqs))
    if eng.allocator is not None:
        eng.release_prefix_cache()
        eng.allocator.assert_clean()
    return {i: list(r.generated) for i, r in enumerate(reqs)}


def _parity_case(lens, budgets, seed, *, greedy=True, rounds=4,
                 page_size=0):
    cfg, *_ = _get_model()
    base_kw = dict(greedy=greedy, superstep_rounds=rounds,
                   page_size=page_size)
    chain = _streams(_cached_engine(**base_kw),
                     _requests(cfg, lens, budgets, seed=seed))
    tree = _streams(_cached_engine(tree_width=1, **base_kw),
                    _requests(cfg, lens, budgets, seed=seed))
    assert chain == tree


@pytest.mark.slow
@settings(max_examples=5, deadline=None)
@given(st.integers(0, 1), st.integers(0, 1), st.integers(0, 10 ** 6))
def test_tree_width1_stream_parity_property(greedy_idx, paged_idx, seed):
    """Property: for random prompt lengths, budgets, and decode modes,
    a width=1 tree engine emits byte-identical full streams to the
    chain engine, dense and paged."""
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(2, 40)) for _ in range(6)]
    budgets = [int(rng.integers(2, 9)) for _ in range(6)]
    _parity_case(lens, budgets, seed, greedy=bool(greedy_idx),
                 page_size=8 * paged_idx)


@pytest.mark.slow
@pytest.mark.parametrize("greedy", [True, False])
def test_tree_width1_stream_parity_stepwise(greedy):
    """The per-step reference loop (superstep_rounds=0) takes the
    stepwise dispatch path — same width=1 parity contract."""
    _parity_case([5, 30, 11, 23], [6, 4, 8, 5], seed=21, greedy=greedy,
                 rounds=0)


@pytest.mark.slow
@pytest.mark.parametrize("greedy", [True, False])
def test_tree_width2_paged_equals_dense(greedy):
    """Wider trees: paged streams byte-identical to dense, zero pages
    leaked after drain (rejected-branch scratch rows route through the
    trash page and never pin allocations)."""
    cfg, *_ = _get_model()
    lens, budgets = [5, 30, 11, 23, 8, 17], [6, 4, 8, 5, 7, 6]
    dense = _streams(_cached_engine(greedy=greedy, tree_width=2),
                     _requests(cfg, lens, budgets))
    paged = _streams(_cached_engine(greedy=greedy, tree_width=2,
                                    page_size=8),
                     _requests(cfg, lens, budgets))
    assert dense == paged
    assert [len(v) for v in dense.values()] == budgets


# ======================================================= config guards
def test_tree_check_rejects_non_attention_mixers():
    """Tree verification needs the tree-causal attention mask; linear
    recurrences (mamba) have no per-row mask to thread it through."""
    cfg = MIXER_CFGS["mamba"]
    with pytest.raises(ValueError, match="tree"):
        T.tree_check(cfg)
    params = T.init(cfg, jax.random.key(0))
    dcfg = eagle.draft_config(cfg)
    dparams = eagle.draft_init(dcfg, jax.random.key(1))
    with pytest.raises(ValueError, match="tree"):
        ServingEngine(cfg, params, dcfg, dparams,
                      config=ServingConfig(batch_size=2, max_len=96,
                                           tree_width=2))


def test_policy_owns_tree_shape():
    """The tree shape is a speculation-policy knob: the config seeds it
    through ``make_policy``, and an explicit policy wins over the
    config field (the learned-controller extension seam)."""
    assert ServingConfig(tree_width=3).make_policy().speculation \
        .tree_width == 3
    assert SpeculationPolicy(tree_width=2).tree_width == 2
    cfg, params, dcfg, dparams = _get_model()
    eng = _cached_engine(tree_width=2)
    assert eng.tree_width == 2
    assert eng.policy.speculation.tree_width == 2
