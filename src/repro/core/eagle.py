"""EAGLE-3 draft model (Li et al., arXiv:2503.01840), as used by TIDE §3.2.

One decoder layer + LM head.  The draft predicts the next token from the
*target model's* concatenated low/mid/high hidden states (3·D "capture
features") fused to D, combined with the embedding of the most recent
token.  During chain drafting the draft's own hidden state substitutes for
the target feature (EAGLE-3 "training-time test" behaviour), so training
includes a TTT step on self-generated features.

The draft shares the target's token embedding (read-only), so its own
parameters are just: fuse (3D→D), fc (2D→D), one decoder layer, head.
DeepSeek-V3's MTP head (``cfg.mtp_depth``) is this same structure trained
jointly — we expose it through the identical module.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.config import ATTN, FFN_SWIGLU, BlockDef, ModelConfig
from repro.models.layers import (EMBED, MLP, embed, ffn, ffn_specs, rmsnorm,
                                 rmsnorm_specs)
from repro.models.param import ParamSpec, init_params
from repro.models.transformer import BATCH, KV_SEQ


def draft_config(tcfg: ModelConfig) -> ModelConfig:
    """Draft architecture derived from the target: 1 decoder layer, same
    d_model/vocab, small GQA."""
    # pick a head count that divides d_model with head_dim >= 64
    bound = max(min(tcfg.num_heads, tcfg.d_model // 64), 1)
    heads = next(h for h in range(bound, 0, -1) if tcfg.d_model % h == 0)
    kv = min(tcfg.num_kv_heads, heads)
    while heads % kv:
        kv -= 1
    return dataclasses.replace(
        tcfg,
        name=tcfg.name + "-eagle3",
        family="dense",
        num_layers=1,
        prologue=(),
        pattern=(BlockDef(ATTN, FFN_SWIGLU),),
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=tcfg.d_model // heads,
        d_ff=2 * tcfg.d_model,
        num_experts=0,
        experts_per_tok=0,
        num_shared_experts=0,
        encoder_layers=0,
        num_image_tokens=0,
        q_lora_rank=0,
        kv_lora_rank=0,
        window=0,
        capture_layers=(0, 0, 0),
    )


def draft_specs(dcfg: ModelConfig) -> dict:
    d, v = dcfg.d_model, dcfg.vocab_size
    return {
        "fuse": ParamSpec((3 * d, d), (MLP, EMBED)),
        "fc": ParamSpec((2 * d, d), (MLP, EMBED)),
        "norm1": rmsnorm_specs(d),
        "attn": attn.attn_specs(dcfg),
        "norm2": rmsnorm_specs(d),
        "ffn": ffn_specs(dcfg, FFN_SWIGLU),
        "final_norm": rmsnorm_specs(d),
        "head": {"w": ParamSpec((d, v), (EMBED, "vocab"))},
    }


def draft_init(dcfg: ModelConfig, key):
    return init_params(key, draft_specs(dcfg), dcfg.weight_dtype)


def draft_param_count(dcfg: ModelConfig) -> int:
    from repro.models.param import count_params
    return count_params(draft_specs(dcfg))


# ------------------------------------------------------------ core layer
def _layer(dcfg: ModelConfig, p, x, k_cache, v_cache, lengths, pad,
           page_tbl=None):
    """One decoder layer over new positions (decode form, cache write).
    With ``page_tbl``, k_cache/v_cache are page pools (paged serving)."""
    h = rmsnorm(p["norm1"], x, dcfg.norm_eps)
    out, (kc, vc) = attn.self_attention_decode(
        dcfg, p["attn"], h, k_cache, v_cache, lengths, pad,
        page_tbl=page_tbl)
    x = x + out
    h2 = rmsnorm(p["norm2"], x, dcfg.norm_eps)
    x = x + ffn(p["ffn"], h2, FFN_SWIGLU)
    return x, kc, vc


def _layer_full(dcfg: ModelConfig, p, x):
    """Training form: full causal self-attention, no cache."""
    h = rmsnorm(p["norm1"], x, dcfg.norm_eps)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    out, _ = attn.self_attention_prefill(dcfg, p["attn"], h, positions)
    x = x + out
    h2 = rmsnorm(p["norm2"], x, dcfg.norm_eps)
    return x + ffn(p["ffn"], h2, FFN_SWIGLU)


def _head(dcfg, dparams, x):
    return (x @ dparams["head"]["w"].astype(x.dtype)).astype(jnp.float32)


def _fuse_inputs(dcfg, dparams, feats, tok_emb):
    """feats: (B,T,3D) target captures (or (B,T,D) self features pre-fused);
    tok_emb: (B,T,D). Returns fc([fused; emb]).

    The 3D→D fuse is computed as the sum of three D-contraction matmuls
    (one per capture level) instead of a single 3D-contraction dot: XLA's
    CPU tiling of a 3D-wide contraction depends on the row count, which
    would make the fused features — and so the draft K/V — differ in ulps
    between a chunked prompt ingestion and a one-shot one.  Splitting at
    the capture-level boundary keeps every contraction width-stable, so
    chunked draft seeding is bit-identical to one-shot seeding
    (tests/test_chunked_prefill.py pins this)."""
    dt = tok_emb.dtype
    d = dcfg.d_model
    if feats.shape[-1] == 3 * d:
        w = dparams["fuse"].astype(dt)
        f = feats.astype(dt)
        fused = sum(f[..., i * d:(i + 1) * d] @ w[i * d:(i + 1) * d]
                    for i in range(3))
    else:
        fused = feats.astype(dt)
    x = jnp.concatenate([fused, tok_emb], axis=-1)
    return x @ dparams["fc"].astype(dt)


# ------------------------------------------------------------- cache
def init_draft_cache(dcfg: ModelConfig, batch: int, max_len: int, *,
                     page_size: int = 0, num_pages: int = 0) -> dict:
    """Zeroed draft cache.  With ``page_size > 0`` the K/V leaves are
    page pools (num_pages + 1, P, Hk, D) plus a per-lane block table
    ``tbl`` — same layout and trash-page convention as the target
    cache's pools, but a *separate* device table copy so the engine can
    donate target and draft caches independently."""
    hk, hd = dcfg.num_kv_heads, dcfg.head_dim
    if page_size > 0:
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} % page_size {page_size}")
        return {
            "k": jnp.zeros((num_pages + 1, page_size, hk, hd),
                           dcfg.act_dtype),
            "v": jnp.zeros((num_pages + 1, page_size, hk, hd),
                           dcfg.act_dtype),
            "tbl": jnp.full((batch, max_len // page_size), num_pages,
                            jnp.int32),
            "lengths": jnp.zeros((batch,), jnp.int32),
            "pad": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, hk, hd), dcfg.act_dtype),
        "v": jnp.zeros((batch, max_len, hk, hd), dcfg.act_dtype),
        "lengths": jnp.zeros((batch,), jnp.int32),
        "pad": jnp.zeros((batch,), jnp.int32),
    }


def draft_cache_axes() -> dict:
    return {"k": (BATCH, KV_SEQ, "kv_heads", "qkv"),
            "v": (BATCH, KV_SEQ, "kv_heads", "qkv"),
            "lengths": (BATCH,), "pad": (BATCH,)}


def draft_cache_abstract(dcfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_draft_cache(dcfg, batch, max_len))


# ------------------------------------------------------- serving functions
def draft_extend(dcfg: ModelConfig, dparams, embed_params, dcache,
                 feats, tokens, advance):
    """Append ``T`` (feature, token) pairs to the draft cache.

    feats: (B, T, 3D) true target captures for the accepted positions;
    tokens: (B, T) the tokens *following* each feature position;
    advance: (B,) how many of the T entries are valid (cache lengths
    advance by this; trailing entries are scratch and get overwritten).

    Returns (logits (B,T,V), h (B,T,D), dcache').
    """
    dt = dcfg.act_dtype
    tok_emb = embed(embed_params, tokens, dt)
    x = _fuse_inputs(dcfg, dparams, feats, tok_emb)
    x, kc, vc = _layer(dcfg, dparams, x, dcache["k"], dcache["v"],
                       dcache["lengths"], dcache["pad"],
                       page_tbl=dcache.get("tbl"))
    h = rmsnorm(dparams["final_norm"], x, dcfg.norm_eps)
    logits = _head(dcfg, dparams, h)
    new_cache = dict(dcache, k=kc, v=vc,
                     lengths=dcache["lengths"] + advance)
    return logits, h, new_cache


def draft_propose(dcfg: ModelConfig, dparams, embed_params, dcache,
                  h_last, first_logits, gamma: int, *,
                  greedy: bool = True, key=None, keys=None):
    """Chain-draft γ tokens.  h_last: (B, D) draft hidden at the last
    verified position; first_logits: (B, V) draft logits there.

    ``keys``: optional (B,) per-lane key array — chain-step j for lane b
    samples with ``fold_in(keys[b], j)``, so draft randomness is
    per-request (scheduling-invariant); ``key`` is the legacy
    batch-global scalar.

    Returns (draft_tokens (B, γ), draft_logits (B, γ, V), dcache') —
    dcache' has the speculative entries written but its *lengths advanced
    by γ* so the target-verify block can be compared; the caller resets
    lengths on commit (stale entries are overwritten next round).
    """
    dt = dcfg.act_dtype
    b = h_last.shape[0]

    def pick(logits, k):
        if greedy:
            return logits.argmax(-1).astype(jnp.int32)
        if keys is not None:
            kj = jax.vmap(lambda kk: jax.random.fold_in(kk, k))(keys)
            return jax.vmap(jax.random.categorical)(kj, logits
                                                    ).astype(jnp.int32)
        return jax.random.categorical(k, logits).astype(jnp.int32)

    if keys is not None:
        xs = jnp.arange(gamma)                    # fold-in indices
    else:
        xs = (jax.random.split(key, gamma) if key is not None
              else jnp.zeros((gamma, 2), jnp.uint32))

    def step(carry, k):
        h, logits, cache = carry
        tok = pick(logits, k)
        tok_emb = embed(embed_params, tok[:, None], dt)
        x = _fuse_inputs(dcfg, dparams, h[:, None], tok_emb)
        x, kc, vc = _layer(dcfg, dparams, x, cache["k"], cache["v"],
                           cache["lengths"], cache["pad"],
                           page_tbl=cache.get("tbl"))
        h_new = rmsnorm(dparams["final_norm"], x, dcfg.norm_eps)[:, 0]
        logits_new = _head(dcfg, dparams, h_new[:, None])[:, 0]
        cache = dict(cache, k=kc, v=vc, lengths=cache["lengths"] + 1)
        return (h_new, logits_new, cache), (tok, logits)

    (h_f, logits_f, cache_f), (toks, logitss) = jax.lax.scan(
        step, (h_last, first_logits, dcache), xs)
    draft_tokens = toks.T                                    # (B, γ)
    draft_logits = logitss.transpose(1, 0, 2)                # (B, γ, V)
    return draft_tokens, draft_logits, cache_f


def draft_propose_tree(dcfg: ModelConfig, dparams, embed_params, dcache,
                       h_last, first_logits, gamma: int, width: int, *,
                       greedy: bool = True, key=None, keys=None):
    """Draft a token *tree*: ``width`` parallel chains of depth ``gamma``
    sharing the root position, for one tree-masked target verify pass.

    Branch 0 is the verbatim ``draft_propose`` chain (same randomness,
    same tokens — width == 1 is bitwise the chain).  Branch r >= 1
    re-proposes from the same post-extend cache with the previously
    picked depth-1 siblings masked to NEG_INF and a greedy
    continuation, so sibling roots are distinct and each branch is the
    draft's best completion of its alternative first token.  Every
    branch writes its speculative K/V at the same cache slots
    [lengths, lengths + gamma) — isolation comes from the causal
    frontier (each propose starts at the same base lengths, so a
    branch never reads a prior branch's stale rows), and the propose
    K/V is scratch that the next ``draft_extend`` overwrites anyway.

    Returns (tokens (B, width, γ), logits (B, width, γ, V), dcache')
    where dcache' is branch 0's propose cache (lengths advanced by γ,
    reset by the caller on commit).  Branch r's depth-1 logits row is
    the sibling-masked distribution — exactly the proposal density the
    residual-sampling acceptance must divide by.
    """
    b = h_last.shape[0]
    toks_all, logits_all = [], []
    masked = first_logits
    cache0 = None
    bidx = jnp.arange(b)
    for r in range(width):
        if r == 0:
            toks, logitss, cache0 = draft_propose(
                dcfg, dparams, embed_params, dcache, h_last, first_logits,
                gamma, greedy=greedy, key=key, keys=keys)
        else:
            toks, logitss, _ = draft_propose(
                dcfg, dparams, embed_params, dcache, h_last, masked,
                gamma, greedy=True)
        masked = masked.at[bidx, toks[:, 0]].set(attn.NEG_INF)
        toks_all.append(toks)
        logits_all.append(logitss)
    tokens = jnp.stack(toks_all, axis=1)              # (B, w, γ)
    logits = jnp.stack(logits_all, axis=1)            # (B, w, γ, V)
    return tokens, logits, cache0


def reset_propose(dcache, gamma: int):
    """Roll the speculative lengths back after verification."""
    return dict(dcache, lengths=dcache["lengths"] - gamma)


def seed_prompt_pairs(dcfg: ModelConfig, dparams, embed_params, dcache,
                      captures, tokens, pad):
    """The draft 'prefill' recipe, in one place: set the cache's pad and
    ingest the prompt pairs (caps[i], t_{i+1}) for i < S-1 so the draft
    has full context before the first propose.  Every seeding path (wave
    prologue, slot refill, offline tools) must go through this — the
    pair/advance convention here is load-bearing for the refilled-slot
    == served-alone parity."""
    b, s, _ = captures.shape
    dcache = dict(dcache, pad=pad)
    _, _, dcache = draft_extend(
        dcfg, dparams, embed_params, dcache,
        captures[:, :s - 1], tokens[:, 1:],
        jnp.full((b,), s - 1, jnp.int32))
    return dcache


def seed_chunk_pairs(dcfg: ModelConfig, dparams, embed_params, dcache,
                     captures, next_tokens, advance):
    """One chunk of the draft 'prefill': ingest the pairs
    (captures[:, j], next_tokens[:, j]) for j < advance.

    The chunked-refill pipeline splits ``seed_prompt_pairs`` across
    prompt chunks: chunk k passes its own target captures plus the
    *lookahead-shifted* token columns (token i+1 for capture i — the
    host slices them from the full prompt, so the chunk boundary never
    needs a device-side shift).  ``advance`` is ``chunk_width`` for
    interior chunks and ``chunk_width - 1`` for the final chunk (pair
    S-1 does not exist); trailing columns are scratch and get
    overwritten, exactly as in ``draft_extend``.  The caller must have
    set ``dcache['pad']`` before the first chunk (``seed_prompt_pairs``
    does the same).  Chunked == one-shot seeding is bitwise on the
    valid cache region (see ``_fuse_inputs``)."""
    _, _, dcache = draft_extend(dcfg, dparams, embed_params, dcache,
                                captures, next_tokens, advance)
    return dcache


def seed_refill_cache(dcfg: ModelConfig, dparams, embed_params, captures,
                      tokens, pad, max_len: int):
    """Build a fresh draft cache for a refill batch and seed it — the
    per-slot equivalent of the wave prologue's draft seed, batched over
    the refilled slots only.

    captures: (R, S, 3D) target prefill captures; tokens: (R, S) padded
    prompt; pad: (R,) left-pad lengths.  Returns the seeded cache
    (R-batch), ready to be scattered into the live cache lanes."""
    dcache = init_draft_cache(dcfg, captures.shape[0], max_len)
    return seed_prompt_pairs(dcfg, dparams, embed_params, dcache,
                             captures, tokens, pad)


def scatter_batch_rows(live, new, mask, src, axis: int = 0):
    """Overwrite the batch rows of ``live`` selected by ``mask`` with
    rows gathered from ``new`` at ``src``; batch dimension at ``axis``.

    A gather+where instead of a scatter: the refill count varies per
    call but the live batch is fixed, so the compiled graph has fixed
    shapes and never depends on scatter ordering.  ``src`` is arbitrary
    where ``mask`` is False."""
    rows = jnp.take(new, src, axis=axis)
    shp = [1] * rows.ndim
    shp[axis] = mask.shape[0]
    return jnp.where(mask.reshape(shp), rows.astype(live.dtype), live)


def scatter_draft_rows(live, new, mask, src):
    """Replace the masked batch lanes of a live draft cache with lanes of
    a refill-batch cache (all draft-cache leaves carry batch at axis 0)."""
    return jax.tree.map(
        lambda l, n: scatter_batch_rows(l, n, mask, src, axis=0),
        live, new)


def scatter_draft_rows_paged(live, new, mask, src):
    """Paged twin of ``scatter_draft_rows``: ``live`` is a paged draft
    cache (pools + ``tbl``); ``new`` is a dense R-batch staging cache.
    K/V rows are written *through* the live block table (unmasked lanes
    route to the trash page); lengths/pad scatter as rows; the table
    itself is host-authoritative and passes through unchanged."""
    from repro.core import paging
    tbl = live["tbl"]
    out = dict(live)
    for leaf in ("k", "v"):
        rows = jnp.take(new[leaf], src, axis=0)      # (B, W, Hk, D)
        out[leaf] = paging.write_rows_paged(live[leaf], tbl, rows, mask)
    for leaf in ("lengths", "pad"):
        out[leaf] = scatter_batch_rows(live[leaf], new[leaf], mask, src,
                                       axis=0)
    return out


def reseed_draft_rows_from_ring(dcfg: ModelConfig, dparams, embed_params,
                                dcache, cap_feats, cap_toks, cap_count):
    """Rebuild the trailing draft-cache K/V rows under new ``dparams``
    from the rolling capture ring (deploy-time in-place re-seed).

    The draft's K/V at cache slot p is a pure per-position function of
    the ingested pair (f_p, u_p) and its RoPE position, so the last
    ``n = min(cap_count, W)`` slots — exactly the pairs the ring holds —
    can be recomputed exactly for a freshly deployed draft.  Slots older
    than the window (and the prompt-seed region) keep the previous
    draft's K/V: token streams stay correct either way (the target
    verifies every draft), this only restores the new draft's acceptance
    gain on resident lanes immediately instead of at lane retirement.

    cap_feats: (B, W, 3D) ring of pair features; cap_toks: (B, W) ring
    of pair tokens; cap_count: (B,) pairs ingested since lane admission
    (ring write head).  Returns the re-seeded draft cache."""
    b, w = cap_toks.shape
    dt = dcfg.act_dtype
    lengths = dcache["lengths"]
    n = jnp.minimum(cap_count, w)
    j = jnp.arange(w)[None, :]
    slot = ((cap_count - n)[:, None] + j) % w      # ring → time order
    feats = jnp.take_along_axis(cap_feats, slot[..., None], axis=1)
    toks = jnp.take_along_axis(cap_toks, slot, axis=1)
    start = lengths - n
    x = _fuse_inputs(dcfg, dparams, feats, embed(embed_params, toks, dt))
    # run the decode layer purely for its K/V cache writes: entries land
    # at slots start + [0..W) with the exact RoPE positions the original
    # ingestion used (lengths=start, same pad); the attention output and
    # any out-of-range scratch writes are discarded
    _, kc, vc = _layer(dcfg, dparams, x,
                       jnp.zeros_like(dcache["k"]),
                       jnp.zeros_like(dcache["v"]),
                       start, dcache["pad"])
    pos = jnp.arange(dcache["k"].shape[1])[None, :]
    sel = ((pos >= start[:, None])
           & (pos < lengths[:, None]))[..., None, None]
    return dict(dcache,
                k=jnp.where(sel, kc, dcache["k"]),
                v=jnp.where(sel, vc, dcache["v"]))


def reseed_draft_rows_from_ring_paged(dcfg: ModelConfig, dparams,
                                      embed_params, dcache, cap_feats,
                                      cap_toks, cap_count, max_len: int):
    """Paged twin of ``reseed_draft_rows_from_ring``: recompute the
    ring-covered draft K/V rows in a dense scratch cache, then write
    them back through the lane block table (``dcache["tbl"]``) into the
    page pools.  Row values are identical to the dense re-seed — the
    draft layer runs on the same (B, W) fused inputs at the same RoPE
    positions — so paged+reseed streams stay bitwise equal to
    dense+reseed ones.  This is what lifts the PR 6 reseed_window x
    paging exclusivity: deploy-time re-seed writes through tables like
    any other draft-cache commit."""
    from repro.core import paging

    b, w = cap_toks.shape
    dt = dcfg.act_dtype
    lengths = dcache["lengths"]
    pool_k, pool_v = dcache["k"], dcache["v"]
    page_size = pool_k.shape[1]
    trash = pool_k.shape[0] - 1
    n = jnp.minimum(cap_count, w)
    j = jnp.arange(w)[None, :]
    slot = ((cap_count - n)[:, None] + j) % w      # ring → time order
    feats = jnp.take_along_axis(cap_feats, slot[..., None], axis=1)
    toks = jnp.take_along_axis(cap_toks, slot, axis=1)
    start = lengths - n
    x = _fuse_inputs(dcfg, dparams, feats, embed(embed_params, toks, dt))
    zeros = jnp.zeros((b, max_len) + pool_k.shape[2:], pool_k.dtype)
    _, kc, vc = _layer(dcfg, dparams, x, zeros, jnp.zeros_like(zeros),
                       start, dcache["pad"])
    # the layer wrote the W recomputed rows at positions start + [0, W);
    # gather exactly the n valid ones per lane and commit them through
    # the block table (invalid columns route to the trash page)
    pos = start[:, None] + j                        # (B, W)
    idx = jnp.clip(pos, 0, max_len - 1)
    rows_k = jnp.take_along_axis(kc, idx[..., None, None], axis=1)
    rows_v = jnp.take_along_axis(vc, idx[..., None, None], axis=1)
    valid = j < n[:, None]
    page, pslot = paging.page_slot(dcache["tbl"], page_size, pos, trash,
                                   valid=valid)
    return dict(dcache,
                k=pool_k.at[page, pslot].set(rows_k.astype(pool_k.dtype)),
                v=pool_v.at[page, pslot].set(rows_v.astype(pool_v.dtype)))


# ------------------------------------------------------------- training
def draft_train_loss(dcfg: ModelConfig, dparams, embed_params, feats, tokens,
                     *, ttt: bool = True, mask=None):
    """EAGLE-3 training loss on captured signals.

    Signal convention (SignalStore / draft_extend): pair i is
    (f_i, u_i) where f_i is the target capture at a committed position
    and u_i the token that followed it.  Draft input at i:
    (f_i, e(u_i)); label u_{i+1}.  The TTT term replays with the draft's
    own hidden as the feature (chain-step distribution matching).
    Returns (loss, metrics{accuracy}).
    """
    dt = dcfg.act_dtype
    b, s, _ = feats.shape
    f_in = feats[:, :s - 1]
    tok_in = tokens[:, :s - 1]
    labels = tokens[:, 1:]
    tok_emb = embed(embed_params, tok_in, dt)
    x = _fuse_inputs(dcfg, dparams, f_in, tok_emb)
    x = _layer_full(dcfg, dparams, x)
    h = rmsnorm(dparams["final_norm"], x, dcfg.norm_eps)
    logits = _head(dcfg, dparams, h)

    if mask is None:
        m = jnp.ones(labels.shape, jnp.float32)
    else:
        m = mask[:, 1:].astype(jnp.float32)

    def ce(lg, lb, mm):
        logz = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, lb[..., None], axis=-1)[..., 0]
        return ((logz - ll) * mm).sum() / jnp.maximum(mm.sum(), 1.0)

    loss = ce(logits, labels, m)
    acc = (((logits.argmax(-1) == labels) * m).sum()
           / jnp.maximum(m.sum(), 1.0))
    if ttt and s >= 3:
        # step-2 (TTT): feature = draft's own hidden at i, token u_{i+1},
        # label u_{i+2} — matches the propose-chain input distribution
        f2 = h[:, :-1]
        tok2 = tokens[:, 1:s - 1]
        lab2 = tokens[:, 2:]
        m2 = m[:, 1:]
        x2 = _fuse_inputs(dcfg, dparams, f2, embed(embed_params, tok2, dt))
        x2 = _layer_full(dcfg, dparams, x2)
        h2 = rmsnorm(dparams["final_norm"], x2, dcfg.norm_eps)
        loss = loss + 0.5 * ce(_head(dcfg, dparams, h2), lab2, m2)
    return loss, {"accuracy": acc}
