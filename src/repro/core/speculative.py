"""Speculative decoding: draft-propose + target-verify + acceptance.

Implements both greedy (exact-match) verification and Leviathan-style
stochastic speculative sampling (accept w.p. min(1, p/q), residual
resample), plus the fused ``spec_decode_step`` used by the serving engine
and lowered by the dry-run (the paper's serve step).

Token/position bookkeeping (see core/eagle.py for the draft side):
the verify block fed to the target is ``[t0, d1, …, dγ]`` where t0 is the
last committed token; target logits at block index j give the distribution
of the token after block[j].  ``n_acc`` drafts are accepted and one
bonus/correction token is sampled from logits[n_acc], so each step commits
``n_acc + 1`` tokens.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import eagle
from repro.models import transformer as T
from repro.models.config import ModelConfig


# ------------------------------------------------------------ verification
def verify_greedy(target_logits, draft_tokens):
    """target_logits: (B, γ+1, V); draft_tokens: (B, γ).
    Returns (n_acc (B,), bonus_token (B,))."""
    b, t, _ = target_logits.shape
    gamma = t - 1
    tgt = target_logits[:, :gamma].argmax(-1).astype(jnp.int32)   # (B, γ)
    match = tgt == draft_tokens
    # accepted = longest matching prefix
    n_acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    # bonus/correction token from logits[n_acc]
    bonus_logits = jnp.take_along_axis(
        target_logits, n_acc[:, None, None], axis=1)[:, 0]
    bonus = bonus_logits.argmax(-1).astype(jnp.int32)
    return n_acc, bonus


def verify_sample(key, target_logits, draft_logits, draft_tokens,
                  temperature: float = 1.0, keys=None):
    """Stochastic speculative sampling (Leviathan et al. 2023).

    target_logits: (B, γ+1, V); draft_logits: (B, γ, V);
    draft_tokens: (B, γ).  Returns (n_acc, bonus) with the guarantee that
    committed tokens are distributed exactly as target samples.

    ``keys`` — optional (B,) per-lane key array (per-request PRNG
    streams): every random draw for lane b derives from ``keys[b]``
    only, so a request's acceptance/resample randomness is independent
    of which lanes it shares a batch with.  When omitted, the scalar
    ``key`` is consumed batch-globally (legacy behaviour).
    """
    b, gp1, v = target_logits.shape
    gamma = gp1 - 1
    p = jax.nn.softmax(target_logits[:, :gamma] / temperature, axis=-1)
    q = jax.nn.softmax(draft_logits / temperature, axis=-1)
    p_tok = jnp.take_along_axis(p, draft_tokens[..., None], axis=-1)[..., 0]
    q_tok = jnp.take_along_axis(q, draft_tokens[..., None], axis=-1)[..., 0]
    if keys is None:
        k_acc, k_res = jax.random.split(key)
        u = jax.random.uniform(k_acc, (b, gamma))
    else:
        k_acc = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys)
        k_res = jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys)
        u = jax.vmap(lambda k: jax.random.uniform(k, (gamma,)))(k_acc)
    ok = u < jnp.minimum(1.0, p_tok / jnp.maximum(q_tok, 1e-20))
    n_acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
    # residual distribution at the first rejected slot (or plain target
    # sample at slot γ when everything was accepted)
    sel = jnp.minimum(n_acc, gamma)
    p_rej = jax.nn.softmax(
        jnp.take_along_axis(target_logits, sel[:, None, None], axis=1)[:, 0]
        / temperature, axis=-1)
    q_rej = jnp.take_along_axis(
        jnp.pad(q, ((0, 0), (0, 1), (0, 0))),   # dummy row for the all-acc case
        sel[:, None, None], axis=1)[:, 0]
    residual = jnp.maximum(p_rej - q_rej, 0.0)
    use_residual = (n_acc < gamma)[:, None]
    dist = jnp.where(use_residual, residual, p_rej)
    dist = dist / jnp.maximum(dist.sum(-1, keepdims=True), 1e-20)
    logd = jnp.log(dist + 1e-20)
    if keys is None:
        bonus = jax.random.categorical(k_res, logd).astype(jnp.int32)
    else:
        bonus = jax.vmap(jax.random.categorical)(k_res, logd
                                                 ).astype(jnp.int32)
    return n_acc, bonus


# ------------------------------------------------------ tree verification
def tree_path_slots(sel, gamma: int):
    """Block slots of the accepted root path: position 0 is the root
    (slot 0), position j >= 1 is branch ``sel``'s depth-j node at slot
    1 + sel*gamma + (j-1).  sel: (B,).  Returns (B, γ+1) int32."""
    j = jnp.arange(gamma + 1)[None, :]
    return jnp.where(j == 0, 0,
                     1 + sel[:, None] * gamma + (j - 1)).astype(jnp.int32)


def verify_tree_greedy(target_logits, draft_tokens):
    """Greedy tree acceptance: walk every branch's exact-match prefix
    under the tree-scored logits and keep the longest root path.

    target_logits: (B, width*γ + 1, V) from the tree-masked verify pass
    (slot layout of ``tree_path_slots``); draft_tokens: (B, width, γ).
    Returns (n_acc (B,), sel (B,) winning branch, bonus (B,)).  Sibling
    roots are distinct, so at most one branch survives depth 1 and the
    walk is exactly "descend the matching child".  width == 1 computes
    ``verify_greedy`` op-for-op."""
    b, w, gamma = draft_tokens.shape
    r = jnp.arange(w)[:, None]
    # parent slot of node (r, j): the root for j=1, else (r, j-1)
    pslots = jnp.concatenate(
        [jnp.zeros((w, 1), jnp.int32),
         (1 + r * gamma + jnp.arange(max(gamma - 1, 0))[None, :]
          ).astype(jnp.int32)], axis=1)                       # (w, γ)
    tgt = target_logits[:, pslots.reshape(-1)].argmax(-1).astype(
        jnp.int32).reshape(b, w, gamma)
    match = tgt == draft_tokens
    n_branch = jnp.cumprod(match.astype(jnp.int32), axis=2).sum(axis=2)
    n_acc = n_branch.max(axis=1)
    sel = n_branch.argmax(axis=1).astype(jnp.int32)
    last_slot = jnp.where(n_acc == 0, 0, 1 + sel * gamma + (n_acc - 1))
    bonus_logits = jnp.take_along_axis(
        target_logits, last_slot[:, None, None], axis=1)[:, 0]
    bonus = bonus_logits.argmax(-1).astype(jnp.int32)
    return n_acc, sel, bonus


def verify_tree_sample(key, target_logits, draft_logits, draft_tokens,
                       temperature: float = 1.0, keys=None):
    """Stochastic tree acceptance: sequential sibling tests with residual
    updates at depth 1 (SpecInfer-style k-sequential verification), then
    the per-chain Leviathan rule down the selected branch.

    target_logits: (B, width*γ + 1, V); draft_logits: (B, width, γ, V)
    where branch r's depth-1 row is the sibling-masked proposal density
    ``draft_propose_tree`` actually sampled from; draft_tokens:
    (B, width, γ).  Depth-1 walk: test branch r with
    u_r < min(1, p(x_r)/q_r(x_r)) against the running residual
    p ← max(p − q_r, 0) of the previously rejected siblings, so
    committed tokens stay distributed exactly as target samples.  The
    bonus draws from the residual at the first failing depth (or the
    target at the last path slot on full accept).  Randomness: branch 0
    consumes the chain's exact uniform stream; branch r >= 1 folds r
    into the acceptance key — width == 1 is bit-for-bit
    ``verify_sample``.  Returns (n_acc, sel, bonus)."""
    b, t, v = target_logits.shape
    _, w, gamma = draft_tokens.shape
    q = jax.nn.softmax(draft_logits / temperature, axis=-1)   # (B,w,γ,V)
    if keys is None:
        k_acc, k_res = jax.random.split(key)
        u = jnp.stack(
            [jax.random.uniform(k_acc, (b, gamma)) if r == 0 else
             jax.random.uniform(jax.random.fold_in(k_acc, r), (b, gamma))
             for r in range(w)], axis=1)                      # (B,w,γ)
    else:
        k_acc = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys)
        k_res = jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys)
        u = jnp.stack(
            [jax.vmap(lambda k: jax.random.uniform(k, (gamma,)))(k_acc)
             if r == 0 else
             jax.vmap(lambda k, _r=r: jax.random.uniform(
                 jax.random.fold_in(k, _r), (gamma,)))(k_acc)
             for r in range(w)], axis=1)

    # --- depth 1: sequential sibling tests with residual updates
    p_root = jax.nn.softmax(target_logits[:, 0] / temperature, axis=-1)
    p_cur = p_root
    found = jnp.zeros((b,), bool)
    sel = jnp.zeros((b,), jnp.int32)
    for r in range(w):
        x_r = draft_tokens[:, r, 0]
        q_r = q[:, r, 0]
        q_x = jnp.take_along_axis(q_r, x_r[:, None], axis=-1)[:, 0]
        if r == 0:
            p_test = p_cur          # exactly the chain's first test
        else:
            p_test = p_cur / jnp.maximum(p_cur.sum(-1, keepdims=True),
                                         1e-20)
        p_x = jnp.take_along_axis(p_test, x_r[:, None], axis=-1)[:, 0]
        ok_r = u[:, r, 0] < jnp.minimum(1.0, p_x / jnp.maximum(q_x, 1e-20))
        sel = jnp.where(ok_r & ~found, r, sel)
        upd = ~(found | ok_r)
        p_cur = jnp.where(upd[:, None], jnp.maximum(p_cur - q_r, 0.0),
                          p_cur)
        found = found | ok_r

    # --- depths 2..γ: per-chain rule down the selected branch
    tok_sel = jnp.take_along_axis(draft_tokens, sel[:, None, None],
                                  axis=1)[:, 0]               # (B, γ)
    q_sel = jnp.take_along_axis(q, sel[:, None, None, None], axis=1)[:, 0]
    u_sel = jnp.take_along_axis(u, sel[:, None, None], axis=1)[:, 0]
    if gamma > 1:
        deep_slots = (1 + sel[:, None] * gamma
                      + jnp.arange(gamma - 1)[None, :])       # (B, γ-1)
        p_deep = jax.nn.softmax(
            jnp.take_along_axis(target_logits, deep_slots[..., None],
                                axis=1) / temperature, axis=-1)
        p_tok = jnp.take_along_axis(p_deep, tok_sel[:, 1:, None],
                                    axis=-1)[..., 0]
        q_tok = jnp.take_along_axis(q_sel[:, 1:], tok_sel[:, 1:, None],
                                    axis=-1)[..., 0]
        ok_deep = u_sel[:, 1:] < jnp.minimum(
            1.0, p_tok / jnp.maximum(q_tok, 1e-20))
        ok_full = jnp.concatenate([found[:, None], ok_deep], axis=1)
    else:
        ok_full = found[:, None]
    n_acc = jnp.cumprod(ok_full.astype(jnp.int32), axis=1).sum(axis=1)

    # --- bonus: residual at the first failing depth, or the target at
    # the last path slot on full accept; n_acc == 0 uses the depth-1
    # residual accumulated over every rejected sibling
    bslot = jnp.where(n_acc == 0, 0, 1 + sel * gamma + (n_acc - 1))
    p_rej = jax.nn.softmax(
        jnp.take_along_axis(target_logits, bslot[:, None, None],
                            axis=1)[:, 0] / temperature, axis=-1)
    sel_depth = jnp.minimum(n_acc, gamma)
    q_rej = jnp.take_along_axis(
        jnp.pad(q_sel, ((0, 0), (0, 1), (0, 0))),
        sel_depth[:, None, None], axis=1)[:, 0]
    residual = jnp.maximum(p_rej - q_rej, 0.0)
    residual = jnp.where((n_acc == 0)[:, None], p_cur, residual)
    use_residual = (n_acc < gamma)[:, None]
    dist = jnp.where(use_residual, residual, p_rej)
    dist = dist / jnp.maximum(dist.sum(-1, keepdims=True), 1e-20)
    logd = jnp.log(dist + 1e-20)
    if keys is None:
        bonus = jax.random.categorical(k_res, logd).astype(jnp.int32)
    else:
        bonus = jax.vmap(jax.random.categorical)(k_res, logd
                                                 ).astype(jnp.int32)
    return n_acc, sel, bonus


def compact_tree_cache(cache, sel, gamma: int):
    """Rewrite the accepted branch's K/V rows into chain order before
    ``commit_cache``: the tree verify pass wrote width*γ + 1 rows at
    cache positions lengths + [0..T); the accepted path's rows (slots
    1 + sel*γ + [0..γ)) move to positions lengths + [1..γ], after which
    the cache looks exactly like a linear-chain verify block and the
    ordinary commit applies.  Rows past the path are stale-but-masked
    (same contract as the chain's uncommitted tail).  sel == 0 is a
    same-position copy — the width == 1 path is byte-preserving.

    Paged caches move rows *through* the block table: positions resolve
    via ``paging.page_slot``, so unreserved/inactive lanes route to the
    trash page and allocator invariants hold."""
    lengths = cache["lengths"]
    b = lengths.shape[0]
    src = lengths[:, None] + 1 + sel[:, None] * gamma \
        + jnp.arange(gamma)[None, :]                           # (B, γ)
    dst = lengths[:, None] + 1 + jnp.arange(gamma)[None, :]    # (B, γ)
    page_tbl = cache.get("page_tbl")
    if page_tbl is not None:
        from repro.core import paging

        def move(pool):
            # pool: (repeats, num_pages + 1, P, Hk, D)
            p = pool.shape[2]
            trash = pool.shape[1] - 1
            pg_s, sl_s = paging.page_slot(page_tbl, p, src, trash)
            pg_d, sl_d = paging.page_slot(page_tbl, p, dst, trash)
            rows = pool[:, pg_s, sl_s]
            return pool.at[:, pg_d, sl_d].set(rows)
    else:
        bidx = jnp.arange(b)[:, None]

        def move(leaf):
            # leaf: (repeats, B, Smax, ...)
            rows = leaf[:, bidx, src]
            return leaf.at[:, bidx, dst].set(rows)

    out = {}
    for k, v in cache.items():
        if k in ("lengths", "pad", "page_tbl"):
            out[k] = v
        else:
            out[k] = jax.tree.map(move, v)
    return out


# --------------------------------------------------------------- carry
class SpecCarry(NamedTuple):
    """Pending (feature, token) pairs the draft must ingest next round.

    Pair j is (feats[:, j], tokens[:, j]): the target capture at a
    committed position and the token that *followed* it.  Only the first
    ``advance[b]`` pairs are valid per request (tail entries are scratch
    and get overwritten in the draft cache)."""
    feats: jnp.ndarray      # (B, γ+1, 3D)
    tokens: jnp.ndarray     # (B, γ+1)
    advance: jnp.ndarray    # (B,)


def init_carry_from_caps(last_caps, first_token,
                         gamma: int = 3) -> SpecCarry:
    """Carry after target prefill, from the capture of the last prompt
    position: one pending pair (last_caps, first_token).  The chunked
    refill pipeline builds its commit carry from the final chunk's last
    capture column through here — same recipe as the one-shot path."""
    b = first_token.shape[0]
    feats = jnp.zeros((b, gamma + 1, last_caps.shape[-1]), last_caps.dtype
                      ).at[:, 0].set(last_caps)
    tokens = jnp.zeros((b, gamma + 1), jnp.int32
                       ).at[:, 0].set(first_token.astype(jnp.int32))
    return SpecCarry(feats, tokens, jnp.ones((b,), jnp.int32))


def init_carry(cfg: ModelConfig, dcfg: ModelConfig, prefill_out,
               first_token, gamma: int = 3) -> SpecCarry:
    """Carry after target prefill: one pending pair — the capture of the
    last prompt position with the first sampled token."""
    return init_carry_from_caps(prefill_out["captures"][:, -1], first_token,
                                gamma)


def seed_draft_cache(cfg: ModelConfig, dcfg: ModelConfig, tparams, dparams,
                     dcache, prefill_out, prompt_tokens):
    """Draft 'prefill': ingest the prompt pairs (caps[i], t_{i+1}) for
    i < S-1 so the draft has full context before the first propose
    (delegates to the shared ``eagle.seed_prompt_pairs`` recipe)."""
    return eagle.seed_prompt_pairs(
        dcfg, dparams, tparams["embed"], dcache,
        prefill_out["captures"], prompt_tokens, dcache["pad"])


# ------------------------------------------------------------ fused step
def spec_decode_step(cfg: ModelConfig, dcfg: ModelConfig, tparams, dparams,
                     cache, dcache, carry: SpecCarry, *, gamma: int = 3,
                     greedy: bool = True, key=None, keys=None,
                     moe_impl: str = "sort"):
    """One full speculative serving step (paper Fig. 2 inner loop).

    1. draft-extend with the pairs committed last round (true features),
    2. chain-draft γ tokens from the last valid position,
    3. target verify block [t0, d1..dγ],
    4. accept, commit caches, emit training-signal captures.

    ``keys`` — optional (B,) per-lane key array; all sampling for lane b
    (draft picks, acceptance, resample) derives from ``keys[b]``, making
    sampled streams per-request deterministic regardless of batch
    composition.  ``key`` is the legacy batch-global scalar chain.

    Returns dict(tokens (B, γ+1) committed tokens (scratch beyond
    n_commit), n_commit (B,), cache, dcache, carry, captures, accept_mask).
    """
    b = carry.tokens.shape[0]
    if keys is not None:
        k_draft = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys)
        k_ver = jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys)
    else:
        if key is None:
            key = jax.random.key(0)
        k_draft, k_ver = jax.random.split(key)

    with jax.named_scope("draft_propose"):
        # 1) draft catches up on everything committed last round
        ext_logits, ext_h, dcache = eagle.draft_extend(
            dcfg, dparams, tparams["embed"], dcache,
            carry.feats, carry.tokens, carry.advance)
        last = (carry.advance - 1)[:, None, None]
        h_last = jnp.take_along_axis(ext_h, last, axis=1)[:, 0]
        first_logits = jnp.take_along_axis(ext_logits, last, axis=1)[:, 0]

        # 2) chain-draft γ tokens
        draft_tokens, draft_logits, dcache = eagle.draft_propose(
            dcfg, dparams, tparams["embed"], dcache, h_last, first_logits,
            gamma, greedy=greedy,
            key=None if keys is not None else k_draft,
            keys=k_draft if keys is not None else None)

    with jax.named_scope("target_verify"):
        # 3) target verify: t0 = last committed token (pair index advance-1)
        t0 = jnp.take_along_axis(carry.tokens, (carry.advance - 1)[:, None],
                                 axis=1)
        block = jnp.concatenate([t0, draft_tokens], axis=1)
        out = T.decode_step(cfg, tparams, cache, block, moe_impl=moe_impl)
        tl = out["logits"]                                     # (B, γ+1, V)

        # 4) acceptance
        if greedy:
            n_acc, bonus = verify_greedy(tl, draft_tokens)
        elif keys is not None:
            n_acc, bonus = verify_sample(None, tl, draft_logits, draft_tokens,
                                         keys=k_ver)
        else:
            n_acc, bonus = verify_sample(k_ver, tl, draft_logits, draft_tokens)
        n_commit = n_acc + 1

    with jax.named_scope("commit"):
        # commit target cache (per-request rollback for SSM states)
        cache = T.commit_cache(cfg, out["cache"], n_commit)
        # draft cache: roll the speculative lengths back (stale entries get
        # overwritten by next round's extend)
        dcache = eagle.reset_propose(dcache, gamma)

        # committed tokens this round: [d1..d_{n_acc}, bonus, scratch...]
        idx = jnp.arange(gamma + 1)[None, :]
        accept_mask = idx < n_commit[:, None]
        committed = jnp.where(idx < n_acc[:, None],
                              jnp.pad(draft_tokens, ((0, 0), (0, 1))),
                              bonus[:, None])
        committed = jnp.where(accept_mask, committed, 0)
        # next round's pending pairs: (caps[j], committed[j]) for j < n_commit
        caps = out["captures"]                                  # (B, γ+1, 3D)
        carry = SpecCarry(caps, committed, n_commit)

    return {"tokens": committed, "n_commit": n_commit, "cache": cache,
            "dcache": dcache, "carry": carry, "captures": caps,
            "accept_mask": accept_mask, "n_acc": n_acc, "block": block,
            "target_logits": tl}


def tree_decode_step(cfg: ModelConfig, dcfg: ModelConfig, tparams, dparams,
                     cache, dcache, carry: SpecCarry, *, gamma: int = 3,
                     width: int = 1, greedy: bool = True, key=None,
                     keys=None, moe_impl: str = "sort"):
    """One speculative serving step over a draft token *tree*.

    Identical contract to ``spec_decode_step`` — same carry/telemetry
    shapes (γ+1), same key discipline — but the draft proposes ``width``
    sibling chains sharing the root, the target scores all of them in
    one tree-masked verify pass (T = width*γ + 1 rows), acceptance
    walks the tree and keeps the longest root path, and only that
    path's K/V rows are compacted into chain order and committed
    (``compact_tree_cache``).  Captures/carry hold accepted-path
    features only, so SignalStore semantics are unchanged.  width == 1
    runs the chain computation op-for-op (bitwise parity pinned by
    tests/test_tree.py).

    Returns the ``spec_decode_step`` dict plus ``sel`` (winning
    branch); ``block`` is the full flattened tree block (B, T) and
    ``target_logits`` the path-gathered (B, γ+1, V) rows.
    """
    b = carry.tokens.shape[0]
    if keys is not None:
        k_draft = jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys)
        k_ver = jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys)
    else:
        if key is None:
            key = jax.random.key(0)
        k_draft, k_ver = jax.random.split(key)

    with jax.named_scope("draft_propose"):
        # 1) draft catches up on everything committed last round
        ext_logits, ext_h, dcache = eagle.draft_extend(
            dcfg, dparams, tparams["embed"], dcache,
            carry.feats, carry.tokens, carry.advance)
        last = (carry.advance - 1)[:, None, None]
        h_last = jnp.take_along_axis(ext_h, last, axis=1)[:, 0]
        first_logits = jnp.take_along_axis(ext_logits, last, axis=1)[:, 0]

        # 2) draft the token tree (branch 0 == the chain proposal)
        toks_tree, logits_tree, dcache = eagle.draft_propose_tree(
            dcfg, dparams, tparams["embed"], dcache, h_last, first_logits,
            gamma, width, greedy=greedy,
            key=None if keys is not None else k_draft,
            keys=k_draft if keys is not None else None)

    with jax.named_scope("target_verify"):
        # 3) one tree-masked target pass over [t0, flat nodes]
        t0 = jnp.take_along_axis(carry.tokens, (carry.advance - 1)[:, None],
                                 axis=1)
        block = jnp.concatenate([t0, toks_tree.reshape(b, width * gamma)],
                                axis=1)
        out = T.decode_step(cfg, tparams, cache, block, moe_impl=moe_impl,
                            tree=(width, gamma))
        tl = out["logits"]                                     # (B, T, V)

        # 4) tree acceptance: longest root path
        if greedy:
            n_acc, sel, bonus = verify_tree_greedy(tl, toks_tree)
        elif keys is not None:
            n_acc, sel, bonus = verify_tree_sample(None, tl, logits_tree,
                                                   toks_tree, keys=k_ver)
        else:
            n_acc, sel, bonus = verify_tree_sample(k_ver, tl, logits_tree,
                                                   toks_tree)
        n_commit = n_acc + 1

    with jax.named_scope("commit"):
        # 5) compact the accepted path into chain slots, then commit
        cache = T.commit_cache(cfg, compact_tree_cache(out["cache"], sel,
                                                       gamma), n_commit)
        dcache = eagle.reset_propose(dcache, gamma)

        # 6) committed tokens / carry from the accepted path only
        path = tree_path_slots(sel, gamma)                     # (B, γ+1)
        tok_sel = jnp.take_along_axis(toks_tree, sel[:, None, None],
                                      axis=1)[:, 0]            # (B, γ)
        idx = jnp.arange(gamma + 1)[None, :]
        accept_mask = idx < n_commit[:, None]
        committed = jnp.where(idx < n_acc[:, None],
                              jnp.pad(tok_sel, ((0, 0), (0, 1))),
                              bonus[:, None])
        committed = jnp.where(accept_mask, committed, 0)
        caps = jnp.take_along_axis(out["captures"], path[..., None], axis=1)
        tl_path = jnp.take_along_axis(tl, path[..., None], axis=1)
        carry = SpecCarry(caps, committed, n_commit)

    return {"tokens": committed, "n_commit": n_commit, "cache": cache,
            "dcache": dcache, "carry": carry, "captures": caps,
            "accept_mask": accept_mask, "n_acc": n_acc, "sel": sel,
            "block": block, "target_logits": tl_path}


def plain_decode_step(cfg: ModelConfig, tparams, cache, carry_token, *,
                      greedy: bool = True, key=None, keys=None,
                      moe_impl: str = "sort"):
    """Baseline autoregressive step (speculation disabled — the TIDE
    Adaptive Drafter falls back to this when Eq. 5 predicts no gain).
    ``keys``: optional (B,) per-lane keys (see ``spec_decode_step``)."""
    out = T.decode_step(cfg, tparams, cache, carry_token[:, None],
                        moe_impl=moe_impl)
    logits = out["logits"][:, 0]
    if greedy:
        nxt = logits.argmax(-1).astype(jnp.int32)
    elif keys is not None:
        nxt = jax.vmap(jax.random.categorical)(keys, logits
                                               ).astype(jnp.int32)
    else:
        nxt = jax.random.categorical(key, logits).astype(jnp.int32)
    cache = T.commit_cache(cfg, out["cache"],
                           jnp.ones(carry_token.shape, jnp.int32))
    return {"token": nxt, "cache": cache, "captures": out["captures"],
            "logits": logits}


def plain_step_from_carry(cfg: ModelConfig, tparams, cache,
                          carry: SpecCarry, *, gamma: int = 3,
                          greedy: bool = True, key=None, keys=None,
                          moe_impl: str = "sort"):
    """Plain decode step driven by the spec carry (not a separate
    last-token variable): t0 is pair index ``advance-1`` of the carry, so
    the step is correct even directly after a speculative round (where a
    separately-tracked plain token would be stale).  Returns the same
    pytree layout as ``spec_decode_step`` so the two are `lax.cond`-
    compatible inside the fused superstep."""
    b, gp1 = carry.tokens.shape
    t0 = jnp.take_along_axis(carry.tokens, (carry.advance - 1)[:, None],
                             axis=1)[:, 0]
    out = plain_decode_step(cfg, tparams, cache, t0, greedy=greedy,
                            key=key, keys=keys, moe_impl=moe_impl)
    nxt, caps1 = out["token"], out["captures"]            # (B,), (B,1,3D)
    feats = jnp.zeros((b, gp1, caps1.shape[-1]), caps1.dtype
                      ).at[:, 0].set(caps1[:, 0])
    tokens = jnp.zeros((b, gp1), jnp.int32).at[:, 0].set(nxt)
    n_commit = jnp.ones((b,), jnp.int32)
    accept_mask = jnp.arange(gp1)[None, :] < n_commit[:, None]
    new_carry = SpecCarry(feats, tokens, n_commit)
    return {"tokens": tokens, "n_commit": n_commit, "cache": out["cache"],
            "carry": new_carry, "captures": feats,
            "accept_mask": accept_mask}


# ===================================================== fused superstep
class SuperstepState(NamedTuple):
    """Device-resident serving state threaded across fused supersteps.

    Everything the per-step host loop used to keep in Python lives here
    so K speculative rounds run inside one compiled function with zero
    host syncs.

    PRNG: ``key_data`` holds the engine's *base* key (constant — never
    split); lane b's sampling key for a round is
    ``fold_in(fold_in(base, sid[b]), step_idx[b])``, so a request's
    sampled stream depends only on its admission ordinal and per-request
    step count, never on batch composition or refill timing.

    ``cap_*`` (present only when deploy re-seeding is enabled) is a
    rolling per-lane ring of the (feature, token) pairs the draft cache
    ingested — enough to rebuild the last-window draft K/V rows under a
    freshly deployed draft (``eagle.reseed_draft_rows_from_ring``)."""
    carry: SpecCarry
    active: jnp.ndarray       # (B,) bool — request still generating
    gen_count: jnp.ndarray    # (B,) int32 — committed tokens (incl. first)
    accept_ema: jnp.ndarray   # () f32 — EMA of acceptance length E[l]
    key_data: jnp.ndarray     # raw base-key data (per-request streams)
    sid: jnp.ndarray          # (B,) int32 — sampling-stream id per lane
    step_idx: jnp.ndarray     # (B,) int32 — per-lane decode-step counter
    cap_feats: Optional[jnp.ndarray] = None   # (B, W, F) ring of pair feats
    cap_toks: Optional[jnp.ndarray] = None    # (B, W) ring of pair tokens
    cap_count: Optional[jnp.ndarray] = None   # (B,) pairs ingested


def init_superstep_state(carry: SpecCarry, first_token, key, *,
                         accept_ema: float = 1.0,
                         eos_id: Optional[int] = None,
                         active0=None, sids=None,
                         capture_window: int = 0) -> SuperstepState:
    """``active0`` (B,) bool masks slots that are born finished (inert
    padding of a partial wave, pre-finished requests); default all-on.
    ``sids``: per-lane sampling-stream ids (default ``arange(B)``);
    ``capture_window`` > 0 allocates the deploy-re-seed capture ring."""
    b = first_token.shape[0]
    active = jnp.ones((b,), bool) if active0 is None else \
        jnp.asarray(active0, bool)
    if eos_id is not None:
        active = active & (first_token != eos_id)
    sid = (jnp.arange(b, dtype=jnp.int32) if sids is None
           else jnp.asarray(sids, jnp.int32))
    ring = {}
    if capture_window:
        ring = dict(
            cap_feats=jnp.zeros((b, capture_window, carry.feats.shape[-1]),
                                carry.feats.dtype),
            cap_toks=jnp.zeros((b, capture_window), jnp.int32),
            cap_count=jnp.zeros((b,), jnp.int32))
    return SuperstepState(
        carry=carry, active=active,
        gen_count=jnp.ones((b,), jnp.int32),
        accept_ema=jnp.float32(accept_ema),
        # copy: the engine donates the state buffers per dispatch, and
        # the caller's key (a long-lived engine attribute) must survive
        key_data=jnp.array(jax.random.key_data(key)),
        sid=sid, step_idx=jnp.ones((b,), jnp.int32), **ring)


# ============================================== slot refill (continuous)
# the masked row-replace primitive lives in eagle (this module already
# depends on it); re-exported here for the target-cache/carry scatters
scatter_rows = eagle.scatter_batch_rows


def pad_target_cache(cache, ref):
    """Zero-pad a staging prefill cache (allocated at the refill's
    padded prompt width) out to the live cache geometry described by the
    abstract pytree ``ref`` (``transformer.cache_abstract``).

    The chunked-refill pipeline keeps its staging cache at prompt width
    so continuation chunks attend over exactly the key width the
    one-shot prefill reduces over — attention reductions are *not*
    bitwise stable across buffer widths once enough keys are live, so
    attending over a max_len buffer mid-prefill would break the
    chunked == one-shot byte-parity invariant.  The pad to max_len
    happens here, at commit time, exactly where the one-shot path's
    ``_place`` pads — zero padding is exact.

    Paged path: pass ``ref=None`` — a paged commit writes the staging
    rows *through* the block table (``scatter_target_cache_paged``), so
    repadding the staging to max_len would be a pure wasted copy; this
    is an explicit no-op passthrough instead of a silent full-width
    repad.  On the dense path the shapes are asserted: staging must be
    elementwise coverable by the live geometry."""
    if ref is None:
        return cache

    def pad(leaf, r):
        if leaf.ndim != len(r.shape) or any(
                ls > rs for ls, rs in zip(leaf.shape, r.shape)):
            raise ValueError(
                f"staging leaf {leaf.shape} does not embed in live "
                f"cache geometry {r.shape}")
        pads = [(0, rs - ls) for ls, rs in zip(leaf.shape, r.shape)]
        if any(hi for _, hi in pads):
            return jnp.pad(leaf, pads)
        return leaf

    return jax.tree.map(pad, cache, ref)


def scatter_target_cache(cache, new, mask, src):
    """Replace the masked batch lanes of a live target decode cache with
    lanes from a freshly prefilled cache (same max_len).  ``lengths`` /
    ``pad`` carry batch at axis 0; stacked layer-group leaves at axis 1
    (leaves are (repeats, B, ...))."""
    out = {}
    for k, v in cache.items():
        if k in ("lengths", "pad"):
            out[k] = scatter_rows(v, new[k], mask, src, axis=0)
        else:
            out[k] = jax.tree.map(
                lambda l, n: scatter_rows(l, n, mask, src, axis=1),
                v, new[k])
    return out


def scatter_target_cache_paged(cache, new, mask, src):
    """Paged twin of ``scatter_target_cache``: ``cache`` is a paged live
    cache (page-pool leaves (repeats, num_pages + 1, P, Hk, D) plus the
    shared ``page_tbl``), ``new`` is a dense staging prefill cache with
    leaves (repeats, R, W, Hk, D).  Masked lanes' rows are written
    through the block table (the allocator has already mapped their
    reservations; positions past a lane's reservation route to the
    trash page exactly like dense scatter's dropped OOB writes);
    unmasked lanes write nothing (trash-routed)."""
    from repro.core import paging
    tbl = cache["page_tbl"]

    def write(pool, staged):
        rows = jnp.take(staged, src, axis=1)        # (repeats, B, W, ...)
        return jax.vmap(
            lambda p, r: paging.write_rows_paged(p, tbl, r, mask)
        )(pool, rows)

    out = {}
    for k, v in cache.items():
        if k in ("lengths", "pad"):
            out[k] = scatter_rows(v, new[k], mask, src, axis=0)
        elif k == "page_tbl":
            out[k] = v
        else:
            out[k] = jax.tree.map(write, v, new[k])
    return out


def scatter_carry(live: SpecCarry, new: SpecCarry, mask, src) -> SpecCarry:
    """Replace the masked lanes of the spec carry (batch at axis 0)."""
    return SpecCarry(*(scatter_rows(l, n, mask, src, axis=0)
                       for l, n in zip(live, new)))


def refill_superstep_state(state: SuperstepState, carry_new: SpecCarry,
                           first_token, budgets, mask, src, *,
                           eos_id: Optional[int] = None,
                           sids=None) -> SuperstepState:
    """Reset the masked slots of the superstep state for freshly admitted
    requests: carry ← prefill carry, gen_count ← 1 (the first sampled
    token), active ← alive unless first token is EOS or the budget is
    zero, sampling stream ← (sid, step 1), capture ring ← empty.  The
    acceptance EMA and the base PRNG key are engine-global and pass
    through untouched."""
    carry = scatter_carry(state.carry, carry_new, mask, src)
    alive = budgets >= 1
    if eos_id is not None:
        alive = alive & (first_token != eos_id)
    active = jnp.where(mask, jnp.take(alive, src), state.active)
    gen_count = jnp.where(mask, 1, state.gen_count)
    repl = dict(carry=carry, active=active, gen_count=gen_count,
                step_idx=jnp.where(mask, 1, state.step_idx))
    if sids is not None:
        repl["sid"] = jnp.where(mask, jnp.take(jnp.asarray(sids, jnp.int32),
                                               src), state.sid)
    if state.cap_count is not None:
        # ring content is garbage once count resets — never gathered
        repl["cap_count"] = jnp.where(mask, 0, state.cap_count)
    return state._replace(**repl)


def decode_superstep(cfg: ModelConfig, dcfg: ModelConfig, tparams, dparams,
                     cache, dcache, state: SuperstepState, max_new,
                     threshold_table=None, *, rounds: int = 8,
                     gamma: int = 3, greedy: bool = True,
                     ema_decay: float = 0.9, eos_id: Optional[int] = None,
                     collect_signals: bool = True, moe_impl: str = "sort",
                     tree_width: int = 0):
    """K speculative rounds fused into one compiled function.

    ``tree_width`` >= 1 swaps the speculative arm for
    ``tree_decode_step`` (a ``tree_width``-branch token tree verified in
    one tree-masked pass) — carry, telemetry and signal shapes are all
    γ+1 either way, so nothing downstream changes; 0 is the linear
    chain.

    ``lax.scan`` over ``rounds``; each round
      1. decides speculate-vs-plain in-graph (Eq. 5 threshold table +
         acceptance-EMA, ``lax.cond``) — no host round-trip,
      2. runs the selected step (``spec_decode_step`` or
         ``plain_step_from_carry``),
      3. commits tokens on device: per-request max-token clamp, optional
         EOS cut, active-mask update,
      4. compacts accepted-position training signals with the
         ``extract_pack`` kernel so one (counts, feats, tokens) buffer
         per round crosses to the host per *superstep*, not per step.

    Rounds after all requests finish are skipped via an outer
    ``lax.cond`` (state, caches and the PRNG chain pass through
    untouched, so host-side key accounting matches the per-step loop).

    max_new: (B,) int32 per-request budgets; threshold_table: (B+1,) f32
    from ``adaptive.accept_threshold_table`` or None (always speculate).
    Returns dict(cache, dcache, state, rounds) where ``rounds`` holds
    (K, ...)-stacked per-round telemetry + packed signal buffers.
    """
    from repro.kernels.extract_pack.ops import pack_signals

    gp1 = gamma + 1

    def _round(carry_in, _):
        cache, dcache, st = carry_in

        def _skip(op):
            cache, dcache, st = op
            b = st.active.shape[0]
            f = st.carry.feats.shape[-1]
            ys = {
                "tokens": jnp.zeros((b, gp1), jnp.int32),
                "n_commit": jnp.zeros((b,), jnp.int32),
                "n_eff": jnp.zeros((b,), jnp.int32),
                "active_after": st.active,
                "use_spec": jnp.bool_(False),
                "alpha": jnp.float32(0.0),
                "ell": jnp.float32(0.0),
                "n_sig": jnp.int32(0),
                "ema": st.accept_ema,
            }
            if collect_signals:
                ys["sig_feats"] = jnp.zeros((b, gp1, f), st.carry.feats.dtype)
                ys["sig_tokens"] = jnp.zeros((b, gp1), jnp.int32)
                ys["sig_counts"] = jnp.zeros((b,), jnp.int32)
            return (cache, dcache, st), ys

        def _run(op):
            cache, dcache, st = op
            n_active = st.active.sum().astype(jnp.int32)
            if greedy:
                keys = None
            else:
                # per-request streams: fold the constant base key by
                # (sid, per-lane step counter) — identical to the
                # per-step loop's host-side derivation, bit for bit
                base = jax.random.wrap_key_data(st.key_data)
                keys = jax.vmap(
                    lambda s, c: jax.random.fold_in(
                        jax.random.fold_in(base, s), c))(st.sid,
                                                         st.step_idx)

            def _spec(args):
                cache, dcache, carry = args
                if tree_width:
                    out = tree_decode_step(cfg, dcfg, tparams, dparams,
                                           cache, dcache, carry,
                                           gamma=gamma, width=tree_width,
                                           greedy=greedy, keys=keys,
                                           moe_impl=moe_impl)
                else:
                    out = spec_decode_step(cfg, dcfg, tparams, dparams,
                                           cache, dcache, carry,
                                           gamma=gamma, greedy=greedy,
                                           keys=keys, moe_impl=moe_impl)
                return (out["cache"], out["dcache"], out["carry"],
                        out["tokens"], out["n_commit"], out["captures"],
                        out["accept_mask"])

            def _plain(args):
                cache, dcache, carry = args
                out = plain_step_from_carry(cfg, tparams, cache, carry,
                                            gamma=gamma, greedy=greedy,
                                            keys=keys, moe_impl=moe_impl)
                return (out["cache"], dcache, out["carry"], out["tokens"],
                        out["n_commit"], out["captures"],
                        out["accept_mask"])

            if threshold_table is None:
                use_spec = jnp.bool_(True)
                sel = _spec((cache, dcache, st.carry))
            else:
                from repro.core.adaptive import drafter_decide
                use_spec = drafter_decide(threshold_table, n_active,
                                          st.accept_ema)
                sel = jax.lax.cond(use_spec, _spec, _plain,
                                   (cache, dcache, st.carry))
            cache, dcache, carry, tokens, n_commit, captures, accept_mask \
                = sel

            # rolling capture ring (deploy re-seed): mirror the pairs the
            # draft cache just ingested (spec rounds run draft_extend on
            # the previous round's carry; plain rounds ingest nothing)
            cap_feats, cap_toks, cap_count = (st.cap_feats, st.cap_toks,
                                              st.cap_count)
            if cap_feats is not None:
                w = cap_toks.shape[1]
                bsz = cap_toks.shape[0]
                adv = jnp.where(use_spec, st.carry.advance, 0)
                j = jnp.arange(gp1)[None, :]
                slot = (cap_count[:, None] + j) % w
                slot = jnp.where(j < adv[:, None], slot, w)  # OOB → drop
                bidx = jnp.arange(bsz)[:, None]
                cap_feats = cap_feats.at[bidx, slot].set(
                    st.carry.feats.astype(cap_feats.dtype), mode="drop")
                cap_toks = cap_toks.at[bidx, slot].set(
                    st.carry.tokens, mode="drop")
                cap_count = cap_count + adv

            act = st.active
            n_act_f = jnp.maximum(n_active.astype(jnp.float32), 1.0)
            ncf = n_commit.astype(jnp.float32)
            ell = jnp.where(act, ncf, 0.0).sum() / n_act_f
            alpha = jnp.where(act, ncf - 1.0, 0.0).sum() / n_act_f / gamma
            # EMA tracks acceptance of *speculative* rounds only (a plain
            # round's l=1 carries no draft-quality information)
            ema = jnp.where(use_spec,
                            ema_decay * st.accept_ema
                            + (1.0 - ema_decay) * ell,
                            st.accept_ema)

            with jax.named_scope("commit"):
                remaining = jnp.maximum(max_new - st.gen_count, 0)
                n_eff = jnp.where(act, jnp.minimum(n_commit, remaining), 0)
                if eos_id is not None:
                    pos = jnp.arange(gp1)[None, :]
                    is_eos = (tokens == eos_id) & (pos < n_eff[:, None])
                    has_eos = is_eos.any(axis=1)
                    n_eff = jnp.where(has_eos, is_eos.argmax(axis=1) + 1,
                                      n_eff)
                else:
                    has_eos = jnp.zeros_like(act)
                gen_new = st.gen_count + n_eff
                active_after = act & (gen_new < max_new) & ~has_eos
                n_sig = jnp.where(active_after, n_commit, 0).sum()

            ys = {"tokens": tokens, "n_commit": n_commit, "n_eff": n_eff,
                  "active_after": active_after, "use_spec": use_spec,
                  "alpha": alpha, "ell": ell,
                  "n_sig": n_sig.astype(jnp.int32), "ema": ema}
            if collect_signals:
                # only tokens actually kept (post EOS/budget cut) become
                # training signals — never continuations past the end
                with jax.named_scope("signal_pack"):
                    sig_mask = jnp.arange(gp1)[None, :] < n_eff[:, None]
                    pf, pt, cnt = pack_signals(captures, tokens, sig_mask)
                ys["sig_feats"], ys["sig_tokens"], ys["sig_counts"] = \
                    pf, pt, cnt
            st = SuperstepState(carry, active_after, gen_new, ema,
                                st.key_data, st.sid,
                                jnp.where(st.active, st.step_idx + 1,
                                          st.step_idx),
                                cap_feats, cap_toks, cap_count)
            return (cache, dcache, st), ys

        valid = st.active.any()
        (cache, dcache, st), ys = jax.lax.cond(valid, _run, _skip,
                                               (cache, dcache, st))
        ys["valid"] = valid
        return (cache, dcache, st), ys

    (cache, dcache, state), rounds_out = jax.lax.scan(
        _round, (cache, dcache, state), None, length=rounds)
    return {"cache": cache, "dcache": dcache, "state": state,
            "rounds": rounds_out}
