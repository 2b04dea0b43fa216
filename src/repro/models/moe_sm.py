"""shard_map MoE dispatch (§Perf H-B3): per-shard local sort + explicit
all-to-all — the production TPU expert-parallel path.

The SPMD `moe_sort` baseline routes with a *global* argsort/capacity
scatter, which XLA resolves with activation-sized gathers across the
mesh (the dominant collective of the MoE prefill shapes).  Here each
token shard:

  1. routes and sorts its *local* tokens (65k, not 1M),
  2. slots them into per-expert capacity buffers with *local* capacity
     C_loc = n_loc·k/E·cf,
  3. if experts are sharded over the token axis (expert parallelism):
     regroups the buffer expert-major with one ``all_to_all`` so each
     shard holds all shards' rows for *its* experts, runs its local
     experts, and ``all_to_all``s back,
  4. combines locally with gate weights.

Per-chip ICI traffic is 2 × (E·C_loc·D) ≈ 2 × n_loc·k·cf·D bytes — the
napkin in EXPERIMENTS.md §Perf (~75× less than the baseline's gathers).

Capacity-drop semantics differ from the global sort under load
imbalance (drops are per-shard here); tests check exact equality in the
no-drop regime and bounded disagreement under tight capacity.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.moe import _capacity, _experts_ffn, _route
from repro.models.layers import ffn
from repro.models.config import FFN_SWIGLU


def _local_dispatch(cfg: ModelConfig, params, xf):
    """The local-shard part of moe_sort. xf: (n_loc, D)."""
    n, d = xf.shape
    dt = xf.dtype
    idx, gate, aux = _route(cfg, params, xf)
    k, e = cfg.experts_per_tok, cfg.num_experts
    cap = _capacity(cfg, n)
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n * k) - starts[sorted_e]
    keep = rank < cap
    slot = jnp.where(keep, sorted_e * cap + rank, e * cap)
    token_of = order // k
    buf = jnp.zeros((e * cap + 1, d), dt)
    buf = buf.at[slot].set(xf[token_of].astype(dt), mode="drop")
    return buf[:e * cap].reshape(e, cap, d), (slot, token_of, order,
                                              gate, aux, cap)


def _local_combine(cfg: ModelConfig, ys, meta, n, d, dt):
    slot, token_of, order, gate, aux, cap = meta
    e = cfg.num_experts
    ysf = jnp.concatenate([ys.reshape(e * cap, d),
                           jnp.zeros((1, d), ys.dtype)])
    contrib = ysf[slot] * gate.reshape(-1)[order, None].astype(ys.dtype)
    out = jnp.zeros((n, d), dt).at[token_of].add(contrib.astype(dt))
    return out


def moe_shard_map(cfg: ModelConfig, params, x, mesh, *,
                  token_axes=("pod", "data"),
                  expert_axis: Optional[str] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, D) batch-sharded over ``token_axes``.  Expert weights
    either replicated (expert_axis=None) or sharded over ``expert_axis``
    (must be one of token_axes, expert-parallel).  Returns (out, aux)."""
    dt = x.dtype
    b, t, d = x.shape
    taxes = tuple(a for a in token_axes if a in mesh.axis_names)
    e = cfg.num_experts
    ep = expert_axis if (expert_axis and expert_axis in mesh.axis_names
                         and e % mesh.shape[expert_axis] == 0) else None
    nshard = mesh.shape[ep] if ep else 1

    def local(px, pw):
        xf = px.reshape(-1, d)
        n = xf.shape[0]
        xs, meta = _local_dispatch(cfg, pw, xf)        # (E, C_loc, D)
        if ep:
            # regroup expert-major: (nshard, E_loc, C_loc, D) --a2a-->
            # rows of MY experts from every shard
            e_loc, cap = e // nshard, xs.shape[1]
            xs = xs.reshape(nshard, e_loc, cap, d)
            xs = jax.lax.all_to_all(xs, ep, split_axis=0, concat_axis=0,
                                    tiled=False)
            # (nshard, E_loc, C_loc, D) -> (E_loc, nshard*C_loc, D)
            xs = xs.transpose(1, 0, 2, 3).reshape(e_loc, nshard * cap, d)
            ys = _experts_ffn(pw, xs, dt)              # local experts
            ys = ys.reshape(e_loc, nshard, cap, d).transpose(1, 0, 2, 3)
            ys = jax.lax.all_to_all(ys, ep, split_axis=0, concat_axis=0,
                                    tiled=False)
            ys = ys.reshape(e, cap, d)
        else:
            ys = _experts_ffn(pw, xs, dt)
        out = _local_combine(cfg, ys, meta, n, d, dt)
        aux = meta[4]
        if taxes:
            aux = jax.lax.pmean(aux, taxes)
        return out.reshape(px.shape), aux

    in_x = P(taxes if taxes else None)
    # expert weights: sharded on the expert dim iff expert-parallel
    def wspec(w):
        if w.ndim == 3 and w.shape[0] == e and ep:
            return P(ep)
        return P()
    wspecs = jax.tree.map(wspec, {k: v for k, v in params.items()
                                  if k != "shared"})
    shard_params = {k: params[k] for k in wspecs}
    from repro.models import hints
    with hints.suspend():     # mesh axes are manual inside shard_map
        out, aux = jax.shard_map(
            local, mesh=mesh,
            in_specs=(in_x, wspecs),
            out_specs=(in_x, P()),
            check_vma=False,
        )(x, shard_params)
    if cfg.num_shared_experts:
        out = out + ffn(params["shared"], x, FFN_SWIGLU)
    return out, aux
