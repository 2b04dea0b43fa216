"""Pallas TPU kernel for zero-overhead training-signal packing (TIDE §3.2).

After verification, accepted-position capture features must be compacted
(per request) into the contiguous host-transfer buffer.  Fused into one
VMEM pass, this is the device half of the paper's "overlap extraction
with the next verification step": the packed buffer is the only thing the
host copies, and producing it costs one (T, F) tile per request.

T = γ+1 is tiny; F = 3·d_model is the wide axis.  Grid: (B, F_blocks).
The wrapper resolves, per row, which source position lands in each
packed slot — a (B, T) int32 map, scalar-prefetched into SMEM — and the
kernel moves the feature rows with a T×T select chain vectorized over
the F lane dimension: no gathers, MXU untouched, pure VPU work, and
exact (a select never rounds).  The (B, T) token and (B,) count outputs
are tiny and computed in the wrapper: (1, T) / (1,) blocks of them
would break Mosaic's (8, 128) block-tiling rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(src_ref, feat_ref, pf_ref, *, t: int):
    row = pl.program_id(0)
    # selects run on 32-bit vregs: a packed (bf16) select mask would need
    # a relayout Mosaic cannot do, and the widening round trip is exact
    feats = feat_ref[0].astype(jnp.float32)              # (t, block_f)
    dst = jax.lax.broadcasted_iota(jnp.int32, feats.shape, 0)
    src = jnp.full(feats.shape, -1, jnp.int32)           # -1 = empty slot
    for d in range(t):
        src = jnp.where(dst == d, src_ref[row * t + d], src)
    out = jnp.zeros_like(feats)
    for i in range(t):
        out = jnp.where(src == i,
                        jnp.broadcast_to(feats[i:i + 1, :], feats.shape),
                        out)
    pf_ref[0] = out.astype(pf_ref.dtype)


def pack_slots(mask):
    """(B, T) bool -> (B, T) int32: the source position that lands in
    each packed slot (accepted positions in order), -1 past the row's
    count."""
    t = mask.shape[1]
    m = mask.astype(jnp.int32)
    slot = jnp.cumsum(m, axis=1) - m                     # exclusive prefix
    hit = ((m[:, None, :] == 1)
           & (slot[:, None, :] == jnp.arange(t)[None, :, None]))
    return jnp.where(hit.any(-1), hit.argmax(-1), -1).astype(jnp.int32)


def extract_pack(feats, tokens, mask, *, block_f: int = 512,
                 interpret: bool = False):
    """feats: (B, T, F); tokens: (B, T) int32; mask: (B, T) bool.
    Returns (packed_feats, packed_tokens, counts) — accepted entries
    compacted to the front per row, zero tail."""
    b, t, f = feats.shape
    block_f = min(block_f, f)
    if f % block_f:
        raise ValueError(f"feature dim {f} % block_f {block_f} != 0")
    src = pack_slots(mask)
    pt = jnp.where(src >= 0,
                   jnp.take_along_axis(tokens.astype(jnp.int32),
                                       jnp.maximum(src, 0), axis=1), 0)
    cnt = mask.astype(jnp.int32).sum(axis=1)
    pf = pl.pallas_call(
        functools.partial(_kernel, t=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, f // block_f),
            in_specs=[pl.BlockSpec((1, t, block_f),
                                   lambda i, j, s: (i, 0, j))],
            out_specs=pl.BlockSpec((1, t, block_f),
                                   lambda i, j, s: (i, 0, j))),
        out_shape=jax.ShapeDtypeStruct((b, t, f), feats.dtype),
        interpret=interpret,
        name="extract_pack",
    )(src.reshape(-1), feats)
    return pf, pt, cnt
