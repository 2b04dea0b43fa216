"""Plain float32 reference of the StarCoder2 decoder and of the EAGLE-3
draft TIDE trains for it, as the configuration files beside this one
state them (arXiv:2402.19173 and arXiv:2503.01840, with the departures
each file lists: RMSNorm, unbiased attention projections).

The reference imports nothing of the program.  It makes every weight
again from the run seed with ``bench.weights``, one layer at a time, and
runs each whole sequence causally from position 0 in float32 at the
highest matmul precision: no cache, no chunks, no kernels, no batching.

- ``forward`` scores sequences: the logits at every position, and the
  draft's input features (the hidden states after the configuration's
  three capture layers, concatenated) at the positions asked for;
- ``draft_train`` runs the draft's first optimizer steps on windows of
  (feature, next token) pairs, as the configuration's ``draft_training``
  states them, from the draft the seed makes.

With ``fp8`` every weight matrix and every matmul input is rounded to
float8_e4m3fn (per-column / per-row absmax scales): the control, the
precision below the configuration's bfloat16.

``program_config`` is the one place that maps a configuration file onto
the program's ``ModelConfig``; it imports the program only when called.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0   # largest finite float8_e4m3fn


def program_config(doc: dict):
    """The program's ModelConfig for this configuration file."""
    from repro.models.config import ATTN, FFN_GELU, BlockDef, ModelConfig
    return ModelConfig(
        name=doc["name"], family="dense", citation=doc["paper"],
        num_layers=doc["num_hidden_layers"], d_model=doc["hidden_size"],
        num_heads=doc["num_attention_heads"],
        num_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
        d_ff=doc["intermediate_size"], vocab_size=doc["vocab_size"],
        pattern=(BlockDef(ATTN, FFN_GELU),), rope_theta=doc["rope_theta"],
        norm_eps=doc["norm_epsilon"],
        tie_embeddings=doc["tie_word_embeddings"],
        dtype="bfloat16", param_dtype=doc["torch_dtype"])


def _dims(doc, group=None):
    g = group or doc
    return (g["hidden_size"], g["num_attention_heads"],
            g["num_key_value_heads"], g["head_dim"], doc["norm_epsilon"],
            doc["rope_theta"])


def layer_shapes(doc) -> dict:
    d, hq, hk, hd = _dims(doc)[:4]
    f = doc["intermediate_size"]
    return {"norm1/scale": (d,), "mix/wq": (d, hq, hd),
            "mix/wk": (d, hk, hd), "mix/wv": (d, hk, hd),
            "mix/wo": (hq, hd, d), "norm2/scale": (d,),
            "ffn/w_up": (d, f), "ffn/b_up": (f,), "ffn/w_down": (f, d),
            "ffn/b_down": (d,)}


def draft_shapes(doc) -> dict:
    """Every leaf of the EAGLE-3 draft, by its path in the draft's tree."""
    g = doc["draft"]
    d, hq, hk, hd = _dims(doc, g)[:4]
    f, v = g["intermediate_size"], doc["vocab_size"]
    return {"fuse": (g["fuse_inputs"] * d, d), "fc": (2 * d, d),
            "norm1/scale": (d,), "attn/wq": (d, hq, hd),
            "attn/wk": (d, hk, hd), "attn/wv": (d, hk, hd),
            "attn/wo": (hq, hd, d), "norm2/scale": (d,),
            "ffn/w_gate": (d, f), "ffn/w_up": (d, f), "ffn/w_down": (f, d),
            "final_norm/scale": (d,), "head/w": (d, v)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf32(root, path, shape, dtype, layer):
    return weights.leaf(root, path, shape, dtype, layer).astype(jnp.float32)


def _quant_fp8(x, axes):
    """Round to float8_e4m3fn with one scale per slice over ``axes``
    (absmax scaling), back in float32: the control's lower precision."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _weight(w, fp8):
    return _quant_fp8(w, tuple(range(w.ndim - 1))) if fp8 else w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE at positions 0..S-1; x (S, H, D)."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(x, w, fp8):
    """x @ w over x's last axis and w's first; with ``fp8`` the
    activations are rounded per row as the weights are per column."""
    if fp8:
        x = _quant_fp8(x, (-1,))
    return jnp.tensordot(x, w, axes=(x.ndim - 1, 0), precision=HI)


def _block(dims, w, x, fp8, attn="mix"):
    """One decoder layer over one whole sequence x (S, d): GELU FFN with
    biases (the target) or SwiGLU without (the draft)."""
    d, hq, hk, hd, eps, theta = dims
    g = hq // hk
    s = x.shape[0]
    h = _rms(x, w["norm1/scale"], eps)
    q = _rope(_mm(h, w[attn + "/wq"], fp8), theta)      # (S, hq, hd)
    k = _rope(_mm(h, w[attn + "/wk"], fp8), theta)      # (S, hk, hd)
    v = _mm(h, w[attn + "/wv"], fp8)
    q = q.reshape(s, hk, g, hd)
    sc = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI).reshape(s, hq * hd)
    x = x + _mm(o, w[attn + "/wo"].reshape(hq * hd, d), fp8)
    h2 = _rms(x, w["norm2/scale"], eps)
    if "ffn/w_gate" in w:
        u = jax.nn.silu(_mm(h2, w["ffn/w_gate"], fp8)) \
            * _mm(h2, w["ffn/w_up"], fp8)
        return x + _mm(u, w["ffn/w_down"], fp8)
    u = jax.nn.gelu(_mm(h2, w["ffn/w_up"], fp8) + w["ffn/b_up"],
                    approximate=True)
    return x + _mm(u, w["ffn/w_down"], fp8) + w["ffn/b_down"]


_layer = jax.jit(_block, static_argnums=(0, 3))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, scale, head_w, eps, fp8):
    return _mm(_rms(x, scale, eps), head_w, fp8)


def forward(doc: dict, seed: int, fp8: bool = False):
    """Returns ``run(seqs, keep=None, logits=True)``, an iterator of
    ``(logits, feats)`` per sequence of ``seqs`` (int token arrays, each
    scored causally from position 0; give them all one length, padded
    at the end, and every program compiles once): ``logits`` (S, V)
    float32, or None without ``logits``; ``feats`` the draft's input
    features (hidden states after each capture layer, concatenated) at
    the positions ``keep[i]``, on the host, or None without ``keep``."""
    d, hq, hk, hd, eps, theta = dims = _dims(doc)
    vocab = doc["vocab_size"]
    wdt = jnp.dtype(doc["torch_dtype"])
    root = weights.root_key(seed, "target")
    capture = list(doc["draft"]["capture_layers"])

    def w32(path, shape, layer=None):
        return _weight(_leaf32(root, path, shape, wdt, layer),
                       fp8 and len(shape) > 1)

    def run(seqs, keep=None, logits=True):
        # the embedding is a row lookup, not a matmul: never rounded
        tok = _leaf32(root, "embed/tok", (vocab, d), wdt, None)
        xs = [tok[jnp.asarray(s)] for s in seqs]
        del tok
        rows = None
        if keep is not None:
            # rows are taken on the host: a gather on the device would
            # compile once for every count of positions
            idx = [np.asarray(k, np.int64) for k in keep]
            rows = [[None] * len(capture) for _ in seqs]
        for l in range(doc["num_hidden_layers"]):
            w = {k: w32("body/pos0/" + k, shape, l)
                 for k, shape in layer_shapes(doc).items()}
            xs = [_layer(dims, w, x, fp8) for x in xs]
            del w
            for j, c in enumerate(capture):
                if rows is not None and c == l:
                    for i, x in enumerate(xs):
                        rows[i][j] = np.asarray(x)[idx[i]]
        scale = w32("final_norm/scale", (d,))
        head_w = w32("head/w", (d, vocab))
        for i, x in enumerate(xs):
            yield (_head(x, scale, head_w, eps, fp8) if logits else None,
                   None if rows is None else np.concatenate(rows[i], -1))

    return run


# ------------------------------------------------------------ draft
def _draft_loss(dims, fp8, ttt, w, emb, feats, toks):
    """The EAGLE-3 loss on windows of pairs (feature f_i, token u_i):
    input (f_i, e(u_i)) predicts u_{i+1}; a second, training-time-test
    term feeds the draft's own hidden state in place of the feature and
    predicts u_{i+2}, at weight ``ttt``."""
    d, eps = dims[0], dims[4]

    def fused(f, t):
        if f.shape[-1] != d:
            f = _mm(f, w["fuse"], fp8)
        return _mm(jnp.concatenate([f, emb[t]], -1), w["fc"], fp8)

    def trunk(x):
        x = jax.vmap(lambda xi: _block(dims, w, xi, fp8, "attn"))(x)
        return _rms(x, w["final_norm/scale"], eps)

    def ce(h, labels):
        lg = _mm(h, w["head/w"], fp8)
        ll = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(lg, -1) - ll)

    s = toks.shape[1]
    h = trunk(fused(feats[:, :-1], toks[:, :-1]))
    loss = ce(h, toks[:, 1:])
    if s >= 3:
        h2 = trunk(fused(h[:, :-1], toks[:, 1:-1]))
        loss = loss + ttt * ce(h2, toks[:, 2:])
    return loss


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3,))
def _adamw_step(dims, fp8, opt, state, emb, feats, toks, t):
    """One AdamW step of the draft, as ``draft_training`` states it:
    the gradient clipped to a global norm, bias-corrected moments, the
    weights kept in their stated dtype.  Returns the new state, the
    loss before the step and the clipped gradient's per-leaf norms."""
    lr, b1, b2, eps, wd, clip, ttt, wdt = opt
    w, m, v = state
    w32 = {k: x.astype(jnp.float32) for k, x in w.items()}
    loss, g = jax.value_and_grad(
        lambda p: _draft_loss(dims, fp8, ttt, _fp8_weights(p, fp8), emb,
                              feats, toks))(w32)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    g = {k: x * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
         for k, x in g.items()}
    t = t.astype(jnp.float32) + 1.0
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
    v = {k: b2 * v[k] + (1 - b2) * g[k] * g[k] for k in g}
    w = {k: (w32[k] - lr * (m[k] / (1 - b1 ** t)
                            / (jnp.sqrt(v[k] / (1 - b2 ** t)) + eps)
                            + wd * w32[k])).astype(wdt) for k in g}
    gnorm = {k: jnp.sqrt(jnp.sum(x * x)) for k, x in g.items()}
    return (w, m, v), loss, gnorm


def _fp8_weights(w, fp8):
    return {k: _weight(x, fp8 and x.ndim > 1) for k, x in w.items()}


def draft_train(doc: dict, seed: int, batches, fp8: bool = False) -> dict:
    """The draft's first ``len(batches)`` optimizer steps from the draft
    the seed makes, one per ``(feats (B, S, F), tokens (B, S))`` batch.
    Returns the loss before each step, the per-leaf norms of the first
    step's clipped gradient, and of the weights' change over all the
    steps (all by leaf path)."""
    g = doc["draft"]
    t = doc["draft_training"]
    dims = _dims(doc, g)
    wdt = jnp.dtype(doc["torch_dtype"])
    opt = (t["lr"], t["beta1"], t["beta2"], t["eps"], t["weight_decay"],
           t["grad_clip"], t["ttt_weight"], wdt)
    droot = weights.root_key(seed, "draft")
    shapes = draft_shapes(doc)
    w = {k: weights.leaf(droot, k, s, wdt) for k, s in shapes.items()}
    zeros = lambda: {k: jnp.zeros(s, jnp.float32)  # noqa: E731
                     for k, s in shapes.items()}
    state = (w, zeros(), zeros())
    emb = _leaf32(weights.root_key(seed, "target"), "embed/tok",
                  (doc["vocab_size"], doc["hidden_size"]), wdt, None)
    losses, grad = [], None
    for i, (f, tk) in enumerate(batches):
        state, loss, gnorm = _adamw_step(
            dims, fp8, opt, state, emb, jnp.asarray(f, jnp.float32),
            jnp.asarray(tk, jnp.int32), jnp.int32(i))
        losses.append(float(loss))
        if grad is None:
            grad = {k: float(x) for k, x in gnorm.items()}
    w = state[0]
    del state, emb
    change = {k: float(jnp.linalg.norm(
        (w[k].astype(jnp.float32) - _leaf32(droot, k, shapes[k], wdt, None)
         ).ravel())) for k in shapes}
    return {"losses": losses, "grad": grad, "change": change}
