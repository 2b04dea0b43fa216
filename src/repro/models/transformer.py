"""Unified functional transformer covering all assigned families.

The model is a sequence of *groups*; each group is a repeating pattern of
heterogeneous layers scanned over its repeat count with stacked parameters
(`lax.scan` keeps HLO size flat in depth — compile-time hygiene for the
61–72-layer assigned archs).

Entry points:
  * ``param_specs`` / ``init``          — declarative params (+ logical axes)
  * ``forward_train``                   — teacher-forced LM loss (remat +
                                          microbatch grad-accum lives in
                                          repro.training.trainer)
  * ``prefill``                         — prompt pass → last logits, KV/SSM
                                          cache, EAGLE-3 capture states
  * ``decode_step``                     — γ+1-token speculative verify block
  * ``commit_cache``                    — per-request acceptance rollback
  * ``init_cache`` / ``cache_axes``     — decode-state construction/sharding
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models.config import (ATTN, ATTN_SW, CROSS, MAMBA, MLA, RWKV6,
                                 FFN_MOE, BlockDef, ModelConfig)
from repro.models.layers import (LAYERS, embed, embed_specs, ffn,
                                 ffn_specs, head_specs, lm_head, rmsnorm,
                                 rmsnorm_specs)
from repro.models.param import ParamSpec, init_params, map_specs

# Logical axis names for cache/activation sharding.
BATCH = "batch"
KV_SEQ = "kv_seq"
ACT_SEQ = "act_seq"


# ===================================================================== specs
def layer_specs(cfg: ModelConfig, blk: BlockDef) -> dict:
    d = cfg.d_model
    s: Dict[str, Any] = {"norm1": rmsnorm_specs(d)}
    if blk.mixer in (ATTN, ATTN_SW):
        s["mix"] = attn.attn_specs(cfg)
    elif blk.mixer == MLA:
        s["mix"] = mla_mod.mla_specs(cfg)
    elif blk.mixer == CROSS:
        s["mix"] = attn.attn_specs(cfg, cross=True)
    elif blk.mixer == MAMBA:
        s["mix"] = mam.mamba_specs(cfg)
    elif blk.mixer == RWKV6:
        s["mix"] = rwkv_mod.rwkv_specs(cfg)
    else:
        raise ValueError(blk.mixer)
    if blk.cross:
        s["norm_c"] = rmsnorm_specs(d)
        s["cross"] = attn.attn_specs(cfg, cross=True)
    s["norm2"] = rmsnorm_specs(d)
    if blk.ffn == FFN_MOE:
        s["moe"] = moe_mod.moe_specs(cfg)
    else:
        s["ffn"] = ffn_specs(cfg, blk.ffn)
    return s


def stack_specs(specs, n: int):
    return map_specs(
        lambda p: ParamSpec((n,) + p.shape, (LAYERS,) + p.axes, p.init,
                            p.scale, p.dtype), specs)


def model_groups(cfg: ModelConfig) -> List[Tuple[str, Tuple[BlockDef, ...], int]]:
    """Decoder groups as (name, pattern, repeats)."""
    gs = []
    if cfg.prologue:
        gs.append(("pre", (cfg.prologue[0],), len(cfg.prologue)))
    gs.append(("body", cfg.pattern, cfg.num_pattern_repeats))
    return gs


def param_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "embed": embed_specs(cfg),
        "final_norm": rmsnorm_specs(d),
    }
    if not cfg.tie_embeddings:
        specs["head"] = head_specs(cfg)
    for name, pattern, repeats in model_groups(cfg):
        specs[name] = {f"pos{i}": stack_specs(layer_specs(cfg, blk), repeats)
                       for i, blk in enumerate(pattern)}
    if cfg.encoder_layers:
        enc_blk = BlockDef(mixer=ATTN, ffn=cfg.pattern[0].ffn)
        specs["enc"] = {"pos0": stack_specs(layer_specs(cfg, enc_blk),
                                            cfg.encoder_layers)}
        specs["enc_norm"] = rmsnorm_specs(d)
    return specs


def init(cfg: ModelConfig, key):
    return init_params(key, param_specs(cfg), cfg.weight_dtype)


# ============================================================== layer apply
def _place(x, max_len: int):
    """Pad a (B, S, ...) prefill cache tensor out to (B, max_len, ...)."""
    s = x.shape[1]
    if s == max_len:
        return x
    if s > max_len:
        raise ValueError(f"prefill len {s} > max_len {max_len}")
    return jnp.pad(x, ((0, 0), (0, max_len - s)) + ((0, 0),) * (x.ndim - 2))


def apply_layer_prefill(cfg: ModelConfig, blk: BlockDef, p, x, positions, pad,
                        mem, max_len: int, causal: bool, want_cache: bool,
                        moe_impl: str):
    """Returns (x, cache_entry, aux_loss)."""
    dt = x.dtype
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    entry: Dict[str, Any] = {}
    if blk.mixer in (ATTN, ATTN_SW):
        out, (k, v) = attn.self_attention_prefill(
            cfg, p["mix"], h, positions, pad, window=cfg.window, causal=causal)
        if want_cache:
            entry = {"k": _place(k, max_len), "v": _place(v, max_len)}
    elif blk.mixer == MLA:
        out, (ckv, kr) = mla_mod.mla_prefill(cfg, p["mix"], h, positions, pad)
        if want_cache:
            entry = {"ckv": _place(ckv, max_len), "kr": _place(kr, max_len)}
    elif blk.mixer == CROSS:
        mk, mv = attn.cross_memory_kv(p["mix"], mem, dt)
        out = attn.cross_attention(cfg, p["mix"], h, mk, mv)
        if want_cache:
            entry = {"mk": mk, "mv": mv}
    elif blk.mixer == MAMBA:
        out, st = mam.mamba_prefill(cfg, p["mix"], h, pad)
        if want_cache:
            entry = st
    elif blk.mixer == RWKV6:
        out, st = rwkv_mod.rwkv_prefill(cfg, p["mix"], h, pad)
        if want_cache:
            entry = st
    else:
        raise ValueError(blk.mixer)
    x = x + out
    if blk.cross:
        hc = rmsnorm(p["norm_c"], x, cfg.norm_eps)
        mk, mv = attn.cross_memory_kv(p["cross"], mem, dt)
        x = x + attn.cross_attention(cfg, p["cross"], hc, mk, mv)
        if want_cache:
            entry["xmk"], entry["xmv"] = mk, mv
    h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if blk.ffn == FFN_MOE:
        out2, aux = moe_mod.moe(cfg, p["moe"], h2, moe_impl)
    else:
        out2, aux = ffn(p["ffn"], h2, blk.ffn), jnp.float32(0.0)
    return x + out2, entry, aux


def apply_layer_decode(cfg: ModelConfig, blk: BlockDef, p, x, entry, lengths,
                       pad, moe_impl: str, page_tbl=None, tree=None):
    """Returns (x, new_entry, aux). SSM entries gain a per-step T axis.
    With ``page_tbl``, attention entries are page pools written/read
    through the shared block table (see ``core.paging``).  With
    ``tree=(width, gamma)``, the block rows are a flattened draft tree
    scored under the tree-causal mask (attention mixers only — see
    ``tree_check``)."""
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if blk.mixer in (ATTN, ATTN_SW):
        out, (kc, vc) = attn.self_attention_decode(
            cfg, p["mix"], h, entry["k"], entry["v"], lengths, pad,
            window=cfg.window, page_tbl=page_tbl, tree=tree)
        new = dict(entry, k=kc, v=vc)
    elif blk.mixer == MLA:
        out, (ckv, kr) = mla_mod.mla_decode(
            cfg, p["mix"], h, entry["ckv"], entry["kr"], lengths, pad)
        new = dict(entry, ckv=ckv, kr=kr)
    elif blk.mixer == CROSS:
        out = attn.cross_attention(cfg, p["mix"], h, entry["mk"], entry["mv"])
        new = entry
    elif blk.mixer == MAMBA:
        out, states = mam.mamba_decode(cfg, p["mix"], h, entry)
        new = states
    elif blk.mixer == RWKV6:
        out, states = rwkv_mod.rwkv_decode(cfg, p["mix"], h, entry)
        new = states
    else:
        raise ValueError(blk.mixer)
    x = x + out
    if blk.cross:
        hc = rmsnorm(p["norm_c"], x, cfg.norm_eps)
        x = x + attn.cross_attention(cfg, p["cross"], hc, entry["xmk"],
                                     entry["xmv"])
    h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if blk.ffn == FFN_MOE:
        out2, aux = moe_mod.moe(cfg, p["moe"], h2, moe_impl)
    else:
        out2, aux = ffn(p["ffn"], h2, blk.ffn), jnp.float32(0.0)
    return x + out2, new, aux


# ============================================================= group runner
def _update_caps(caps, cap_targets, lidx, x):
    if caps is None:
        return None
    for j, tgt in enumerate(cap_targets):
        caps = caps.at[j].set(jnp.where(lidx == tgt, x, caps[j]))
    return caps


def run_group_prefill(cfg, group_params, pattern, repeats, x, positions, pad,
                      mem, base_idx: int, cap_targets, max_len, causal,
                      want_cache, want_caps, moe_impl, remat=False):
    """Scan the group. Returns (x, cache_group, caps, aux)."""
    P = len(pattern)

    def body(carry, xs):
        x, caps, aux = carry
        i, p_slice = xs
        entries = {}
        for pi, blk in enumerate(pattern):
            if remat:
                def layer_fn(p, x, positions, pad, mem, _blk=blk):
                    return apply_layer_prefill(
                        cfg, _blk, p, x, positions, pad, mem, max_len,
                        causal, want_cache, moe_impl)
                fn = jax.checkpoint(
                    layer_fn,
                    policy=jax.checkpoint_policies.nothing_saveable)
                x, entry, a = fn(p_slice[f"pos{pi}"], x, positions, pad, mem)
            else:
                x, entry, a = apply_layer_prefill(
                    cfg, blk, p_slice[f"pos{pi}"], x, positions, pad, mem,
                    max_len, causal, want_cache, moe_impl)
            aux = aux + a
            lidx = base_idx + i * P + pi
            caps = _update_caps(caps, cap_targets, lidx, x)
            entries[f"pos{pi}"] = entry
        return (x, caps, aux), entries

    caps0 = None
    if want_caps:
        caps0 = jnp.zeros((len(cap_targets),) + x.shape, x.dtype)
    aux0 = jnp.float32(0.0)
    (x, caps, aux), cache_group = jax.lax.scan(
        body, (x, caps0, aux0), (jnp.arange(repeats), group_params))
    return x, cache_group, caps, aux


def run_group_decode(cfg, group_params, pattern, repeats, x, cache_group,
                     lengths, pad, base_idx: int, cap_targets, want_caps,
                     moe_impl, page_tbl=None, tree=None):
    P = len(pattern)

    def body(carry, xs):
        x, caps, aux = carry
        i, p_slice, c_slice = xs
        new_entries = {}
        for pi, blk in enumerate(pattern):
            x, entry, a = apply_layer_decode(
                cfg, blk, p_slice[f"pos{pi}"], x, c_slice[f"pos{pi}"],
                lengths, pad, moe_impl, page_tbl=page_tbl, tree=tree)
            aux = aux + a
            lidx = base_idx + i * P + pi
            caps = _update_caps(caps, cap_targets, lidx, x)
            new_entries[f"pos{pi}"] = entry
        return (x, caps, aux), new_entries

    caps0 = None
    if want_caps:
        caps0 = jnp.zeros((len(cap_targets),) + x.shape, x.dtype)
    (x, caps, aux), new_cache = jax.lax.scan(
        body, (x, caps0, jnp.float32(0.0)),
        (jnp.arange(repeats), group_params, cache_group))
    return x, new_cache, caps, aux


# ================================================================ entry pts
def _caps_to_features(caps):
    """(3, B, T, D) -> (B, T, 3D) EAGLE-3 concatenated capture features."""
    if caps is None:
        return None
    n, b, t, d = caps.shape
    return caps.transpose(1, 2, 0, 3).reshape(b, t, n * d)


def encode(cfg: ModelConfig, params, frames):
    """Audio encoder (whisper): frames (B, S, D) pre-embedded by the stub
    frontend -> memory (B, S, D). Bidirectional, no cache."""
    x = frames.astype(cfg.act_dtype)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    enc_blk = BlockDef(mixer=ATTN, ffn=cfg.pattern[0].ffn)
    x, _, _, _ = run_group_prefill(
        cfg, params["enc"], (enc_blk,), cfg.encoder_layers, x, positions,
        None, None, 0, (), x.shape[1], causal=False, want_cache=False,
        want_caps=False, moe_impl="sort")
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _memory(cfg: ModelConfig, params, extra):
    if cfg.encoder_layers:
        return encode(cfg, params, extra["frames"])
    if cfg.num_image_tokens:
        return extra["image_embeds"].astype(cfg.act_dtype)
    return None


def prefill(cfg: ModelConfig, params, tokens, extra=None, *,
            max_len: Optional[int] = None, pad=None, moe_impl: str = "sort",
            want_caps: bool = True):
    """Prompt pass. Returns dict(logits (B,V) last-position, cache,
    captures (B,S,3D), aux)."""
    b, s = tokens.shape
    max_len = max_len or s
    mem = _memory(cfg, params, extra or {})
    x = embed(params["embed"], tokens, cfg.act_dtype)
    if pad is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    else:
        positions = jnp.maximum(jnp.arange(s)[None, :] - pad[:, None], 0)
    cap_targets = cfg.captures
    cache: Dict[str, Any] = {}
    caps_all = []
    base = 0
    aux = jnp.float32(0.0)
    for name, pattern, repeats in model_groups(cfg):
        x, cgroup, caps, a = run_group_prefill(
            cfg, params[name], pattern, repeats, x, positions, pad, mem,
            base, cap_targets, max_len, causal=True, want_cache=True,
            want_caps=want_caps, moe_impl=moe_impl)
        cache[name] = cgroup
        if want_caps:
            caps_all.append(caps)
        base += len(pattern) * repeats
        aux = aux + a
    # merge capture buffers across groups (each target hit in exactly one)
    caps = None
    if want_caps:
        caps = caps_all[0]
        for c in caps_all[1:]:
            caps = caps + c
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(params.get("head"), params["embed"], x[:, -1],
                     cfg.tie_embeddings)
    if pad is None:
        lengths = jnp.full((b,), s, jnp.int32)
        pad_arr = jnp.zeros((b,), jnp.int32)
    else:
        lengths = jnp.full((b,), s, jnp.int32)
        pad_arr = pad.astype(jnp.int32)
    cache["lengths"] = lengths
    cache["pad"] = pad_arr
    return {"logits": logits.astype(jnp.float32),
            "cache": cache,
            "captures": _caps_to_features(caps),
            "aux": aux}


def decode_step(cfg: ModelConfig, params, cache, tokens, *,
                moe_impl: str = "sort", want_caps: bool = True, tree=None):
    """Verify/decode block: tokens (B, T) at cache positions
    lengths + [0..T). Returns dict(logits (B,T,V), cache (uncommitted),
    captures (B,T,3D)).  With ``tree=(width, gamma)`` the block is a
    flattened draft tree (T = width*gamma + 1) scored in one
    tree-masked pass."""
    b, t = tokens.shape
    lengths, pad = cache["lengths"], cache["pad"]
    page_tbl = cache.get("page_tbl")
    x = embed(params["embed"], tokens, cfg.act_dtype)
    cap_targets = cfg.captures
    new_cache: Dict[str, Any] = {"lengths": lengths, "pad": pad}
    if page_tbl is not None:
        new_cache["page_tbl"] = page_tbl
    caps_all = []
    base = 0
    for name, pattern, repeats in model_groups(cfg):
        x, cgroup, caps, _ = run_group_decode(
            cfg, params[name], pattern, repeats, x, cache[name], lengths,
            pad, base, cap_targets, want_caps, moe_impl,
            page_tbl=page_tbl, tree=tree)
        new_cache[name] = cgroup
        if want_caps:
            caps_all.append(caps)
        base += len(pattern) * repeats
    caps = None
    if want_caps:
        caps = caps_all[0]
        for c in caps_all[1:]:
            caps = caps + c
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(params.get("head"), params["embed"], x,
                     cfg.tie_embeddings)
    return {"logits": logits.astype(jnp.float32),
            "cache": new_cache,
            "captures": _caps_to_features(caps)}


def commit_cache(cfg: ModelConfig, cache, n_accept):
    """Accept ``n_accept`` (B,) tokens out of the T-token verify block:
    advance lengths and select the surviving SSM states (rollback)."""
    new = {"lengths": cache["lengths"] + n_accept, "pad": cache["pad"]}
    if "page_tbl" in cache:
        new["page_tbl"] = cache["page_tbl"]
    idx = jnp.maximum(n_accept - 1, 0)
    for name, pattern, repeats in model_groups(cfg):
        group = cache[name]
        out_group = {}
        for pi, blk in enumerate(pattern):
            entry = group[f"pos{pi}"]
            if blk.mixer in (MAMBA, RWKV6):
                # leaves are (R, B, T, ...) -> select accepted step
                def pick(leaf):
                    ix = idx.reshape((1, -1) + (1,) * (leaf.ndim - 2))
                    return jnp.take_along_axis(leaf, ix, axis=2)[:, :, 0]
                entry = jax.tree.map(pick, entry)
            out_group[f"pos{pi}"] = entry
        new[name] = out_group
    return new


# ================================================================= training
def forward_train(cfg: ModelConfig, params, batch, *, moe_impl: str = "sort",
                  remat: bool = True):
    """Teacher-forced LM loss. batch: {"tokens" (B,S), "targets" (B,S),
    optional "image_embeds"/"frames"}. Returns (loss, metrics)."""
    tokens = batch["tokens"]
    targets = batch["targets"]
    b, s = tokens.shape
    mem = _memory(cfg, params, batch)
    x = embed(params["embed"], tokens, cfg.act_dtype)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    aux = jnp.float32(0.0)
    base = 0
    for name, pattern, repeats in model_groups(cfg):
        x, _, _, a = run_group_prefill(
            cfg, params[name], pattern, repeats, x, positions, None, mem,
            base, (), s, causal=True, want_cache=False, want_caps=False,
            moe_impl=moe_impl, remat=remat)
        base += len(pattern) * repeats
        aux = aux + a
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(params.get("head"), params["embed"], x,
                     cfg.tie_embeddings).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    tgt = jnp.maximum(targets, 0)
    ll = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    ce = ((logz - ll) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux,
                  "accuracy": ((logits.argmax(-1) == tgt) * mask).sum()
                  / jnp.maximum(mask.sum(), 1.0)}


# ============================================================ cache init/ax
def _entry_shape(cfg: ModelConfig, blk: BlockDef, b: int, max_len: int,
                 mem_len: int):
    """(shapes, logical axes) template for one layer's cache entry."""
    hd, hk = cfg.head_dim, cfg.num_kv_heads
    dt = cfg.act_dtype
    if blk.mixer in (ATTN, ATTN_SW):
        sh = {"k": ((b, max_len, hk, hd), dt), "v": ((b, max_len, hk, hd), dt)}
        ax = {"k": (BATCH, KV_SEQ, "kv_heads", "qkv"),
              "v": (BATCH, KV_SEQ, "kv_heads", "qkv")}
    elif blk.mixer == MLA:
        sh = {"ckv": ((b, max_len, cfg.kv_lora_rank), dt),
              "kr": ((b, max_len, cfg.qk_rope_head_dim), dt)}
        ax = {"ckv": (BATCH, KV_SEQ, "latent"),
              "kr": (BATCH, KV_SEQ, "qkv")}
    elif blk.mixer == CROSS:
        sh = {"mk": ((b, mem_len, hk, hd), dt), "mv": ((b, mem_len, hk, hd), dt)}
        ax = {"mk": (BATCH, None, "kv_heads", "qkv"),
              "mv": (BATCH, None, "kv_heads", "qkv")}
    elif blk.mixer == MAMBA:
        di, n, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
        sh = {"h": ((b, di, n), jnp.float32), "conv": ((b, dc - 1, di), dt)}
        ax = {"h": (BATCH, "mlp", "state"), "conv": (BATCH, None, "mlp")}
    elif blk.mixer == RWKV6:
        h, k = cfg.rwkv_heads, cfg.rwkv_head_dim
        sh = {"s": ((b, h, k, k), jnp.float32),
              "shift": ((b, 1, cfg.d_model), dt)}
        ax = {"s": (BATCH, "heads", "qkv", "qkv"),
              "shift": (BATCH, None, None)}
    else:
        raise ValueError(blk.mixer)
    if blk.cross:
        sh["xmk"] = ((b, mem_len, hk, hd), dt)
        sh["xmv"] = ((b, mem_len, hk, hd), dt)
        ax["xmk"] = (BATCH, None, "kv_heads", "qkv")
        ax["xmv"] = (BATCH, None, "kv_heads", "qkv")
    return sh, ax


def _mem_len(cfg: ModelConfig, seq_for_mem: int = 0) -> int:
    if cfg.num_image_tokens:
        return cfg.num_image_tokens
    if cfg.encoder_layers:
        return seq_for_mem
    return 0


def paged_check(cfg: ModelConfig, max_len: int, page_size: int):
    """Validate a paged-cache request: paging covers attention K/V
    pools only, so every mixer must be ATTN/ATTN_SW, and the lane
    window must tile into whole pages."""
    if page_size <= 0:
        raise ValueError(f"page_size must be positive, got {page_size}")
    if max_len % page_size:
        raise ValueError(
            f"max_len {max_len} must be a multiple of page_size "
            f"{page_size}")
    for _, pattern, _ in model_groups(cfg):
        for blk in pattern:
            if blk.mixer not in (ATTN, ATTN_SW):
                raise ValueError(
                    f"paged KV cache supports attention mixers only; "
                    f"config has {blk.mixer!r}")


def tree_check(cfg: ModelConfig):
    """Validate a tree-speculation request: the tree verify pass scores
    all branches in one block and commits only the accepted root path,
    which requires per-position K/V rollback — attention mixers only
    (SSM/RWKV commit picks one step state along the block T axis, which
    is path-order-dependent under a tree)."""
    for _, pattern, _ in model_groups(cfg):
        for blk in pattern:
            if blk.mixer not in (ATTN, ATTN_SW):
                raise ValueError(
                    f"tree speculation supports attention mixers only; "
                    f"config has {blk.mixer!r}")


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               mem_len: int = 0, *, page_size: int = 0,
               num_pages: int = 0) -> dict:
    """Zero-initialized decode cache (used directly by dry-run input_specs).

    With ``page_size > 0`` the attention K/V leaves are page *pools*
    shaped (repeats, num_pages + 1, page_size, Hk, D) — page
    ``num_pages`` is the trash page — plus one shared block table
    ``page_tbl`` (batch, max_len // page_size) initialized to all-trash
    (no lane maps any real page until the allocator reserves for it).
    """
    if page_size > 0:
        paged_check(cfg, max_len, page_size)
    cache: Dict[str, Any] = {
        "lengths": jnp.zeros((batch,), jnp.int32),
        "pad": jnp.zeros((batch,), jnp.int32),
    }
    if page_size > 0:
        cache["page_tbl"] = jnp.full(
            (batch, max_len // page_size), num_pages, jnp.int32)
    for name, pattern, repeats in model_groups(cfg):
        group = {}
        for pi, blk in enumerate(pattern):
            if page_size > 0:
                hd, hk = cfg.head_dim, cfg.num_kv_heads
                sh = {k: ((num_pages + 1, page_size, hk, hd), cfg.act_dtype)
                      for k in ("k", "v")}
            else:
                sh, _ = _entry_shape(cfg, blk, batch, max_len, mem_len)
            group[f"pos{pi}"] = {
                k: jnp.zeros((repeats,) + shape, dtype)
                for k, (shape, dtype) in sh.items()}
        cache[name] = group
    return cache


def cache_abstract(cfg: ModelConfig, batch: int, max_len: int,
                   mem_len: int = 0) -> dict:
    """ShapeDtypeStruct pytree mirroring ``init_cache`` (no allocation)."""
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, max_len, mem_len))


def cache_axes(cfg: ModelConfig) -> dict:
    """Logical axes pytree aligned with ``init_cache`` output."""
    axes: Dict[str, Any] = {"lengths": (BATCH,), "pad": (BATCH,)}
    for name, pattern, repeats in model_groups(cfg):
        group = {}
        for pi, blk in enumerate(pattern):
            _, ax = _entry_shape(cfg, blk, 1, 1, 1)
            group[f"pos{pi}"] = {k: (LAYERS,) + a for k, a in ax.items()}
        axes[name] = group
    return axes
