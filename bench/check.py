"""The comparison that decides ``correct``: what the timed path produced,
against the plain float32 reference, once the window has closed and the
program's state is freed.

Three products of the run are compared, each by a number with a limit:

- ``served_gap_max``, the target path (chunked prefill, cached verify,
  commit): a sample of the window's finished requests, drawn from the
  seed with the longest among them, is scored by the reference over each
  prompt followed by its served tokens.  For each served token the gap
  is the reference's best logit minus its logit of the served token: 0
  where the program picked the reference's argmax, small where rounding
  flipped a near tie.  The number is the widest gap.
- ``signal_feat_err``, signal capture and the pack kernel: every window
  of (feature, token) pairs the draft trainer's first steps consumed is
  found, by its tokens, at its place in a served sequence, and its
  features are compared with the reference's hidden states after the
  capture layers at those positions: the worst position's error norm
  relative to the reference's norm.
- ``train_loss_err``, ``train_grad_err``, ``train_change_err``, one draft
  train step: the reference runs the same first steps, on its own
  features of the same windows, from the draft the seed makes.  The
  loss before each step, as a gap relative to the reference's; the
  first step's clipped gradient as the optimizer holds it (its first
  moment over ``1 - beta1``) and the weights' change over the steps,
  each by the worst leaf: the gap between the program's norm of the
  leaf and the reference's, relative to the larger of the reference's
  norm of that leaf and of the median leaf.  A leaf whose reference
  gradient is under a thousandth of the median leaf's moves by
  round-off alone and is left out of the change.

The control is the reference computed with float8_e4m3 weights and
matmul inputs, put in the program's place: its own top token at each
position of the same served sequences, its own features of the same
windows, and its own train steps on them.  Its readings go through the
same comparison.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE = 8      # requests drawn from the seed, besides the longest
GRAD_FLOOR = 1e-3   # leaves moved by round-off: gradient under this x median


def sample(rows: List[dict], seed: int, k: int = SAMPLE) -> List[int]:
    """Indices of the sampled finished requests: the one with the most
    served tokens, and ``k`` more drawn from the seed."""
    done = [i for i, r in enumerate(rows) if r["finish"] is not None
            and r["n"] > 0]
    if not done:
        return []
    longest = max(done, key=lambda i: (rows[i]["n"], -i))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([seed, 11])
    pick = rng.choice(len(rest), size=min(k, len(rest)), replace=False)
    return [longest] + sorted(rest[j] for j in pick)


@jax.jit
def _gaps(logits, toks, mask):
    """Per position: best logit minus the logit of ``toks`` (masked)."""
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, toks[:, None], -1)[:, 0]
    return jnp.where(mask, best - got, -jnp.inf), jnp.where(
        mask, jnp.sqrt(jnp.mean(logits * logits, -1)), 0.0)


def _aligned(prompt, served, length: int):
    """Input ids (prompt + served[:-1], zero-padded to ``length``) and, per
    input position, the served token it predicts, with a mask."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    ids = np.zeros(length, np.int32)
    ids[:seq.size] = seq
    toks = np.zeros(length, np.int32)
    mask = np.zeros(length, bool)
    start = prompt.size - 1
    toks[start:start + served.size] = served
    mask[start:start + served.size] = True
    return ids, toks, mask


def served_gaps(ref, doc: dict, seed: int,
                seqs: List[Tuple[np.ndarray, np.ndarray]], length: int,
                control: bool = False) -> dict:
    """Widest reference gap over the served tokens of ``seqs`` (and, with
    ``control``, over the tokens the control puts first)."""
    if not seqs:
        return {"gap_max": float("inf"), "tokens": 0, "ref_rms": None,
                "ctrl_gap_max": float("inf") if control else None}
    rows = [_aligned(p, s, length) for p, s in seqs]
    ctrl_toks = None
    if control:
        run_c = ref.forward(doc, seed, fp8=True)
        ctrl_toks = [np.asarray(lg.argmax(-1)).astype(np.int32)
                     for lg, _ in run_c([r[0] for r in rows])]
    run = ref.forward(doc, seed)
    gap_max, ctrl_max, rms, n = -np.inf, -np.inf, [], 0
    for i, (lg, _) in enumerate(run([r[0] for r in rows])):
        _, toks, mask = rows[i]
        g, r = _gaps(lg, jnp.asarray(toks), jnp.asarray(mask))
        gap_max = max(gap_max, float(g.max()))
        rms.append(np.asarray(r)[mask])
        n += int(mask.sum())
        if ctrl_toks is not None:
            gc_, _ = _gaps(lg, jnp.asarray(ctrl_toks[i]), jnp.asarray(mask))
            ctrl_max = max(ctrl_max, float(gc_.max()))
    return {"gap_max": gap_max, "tokens": n,
            "ref_rms": float(np.sqrt(np.mean(np.square(
                np.concatenate(rms))))),
            "ctrl_gap_max": ctrl_max if control else None}


# --------------------------------------------------------- train step
def locate(windows: np.ndarray, seqs: List[np.ndarray]):
    """Where each window of pair tokens lies in the served sequences: a
    pair (f_i, u_i) is the feature at input position i and the token at
    position i + 1, so a window of tokens ``u`` sits at ``seq[q+1:q+1+S]``
    and its features at positions ``q .. q+S-1``.  Returns, per window,
    the list of (sequence, q) where its tokens occur (empty if none)."""
    s = windows.shape[1]
    index: Dict[tuple, List[tuple]] = {}
    for j, seq in enumerate(seqs):
        for q in range(seq.size - s):
            index.setdefault(tuple(seq[q + 1:q + 5]), []).append((j, q))
    out = []
    for w in windows:
        hits = [(j, q) for j, q in index.get(tuple(w[:4]), [])
                if np.array_equal(seqs[j][q + 1:q + 1 + s], w)]
        out.append(hits)
    return out


def _padded(seq, length: int) -> np.ndarray:
    out = np.zeros(length, np.int32)
    out[:seq.size] = seq
    return out


def _reference_feats(run, seqs, hits, s, length):
    """The reference's features for every located window: per window,
    one (S, F) array per place it occurs.  Sequences are padded to
    ``length`` at the end (causal: no position sees the padding)."""
    need = sorted({j for h in hits for j, _ in h})
    keep = [sorted({q + t for h in hits for jj, q in h if jj == j
                    for t in range(s)}) for j in need]
    feats = {}
    for j, k, (_, f) in zip(need, keep,
                            run([_padded(seqs[j], length) for j in need],
                                keep=keep, logits=False)):
        for p, row in zip(k, f):
            feats[(j, p)] = row
    return [[np.stack([feats[(j, q + t)] for t in range(s)])
             for j, q in h] for h in hits]


def _rel(a, b):
    return float(np.linalg.norm(a - b)) / max(float(np.linalg.norm(b)),
                                                1e-30)


def feat_err(got: np.ndarray, want: List[np.ndarray]) -> float:
    """Worst position's error norm relative to the reference's, at the
    place where the window agrees best with the reference."""
    got = got.astype(np.float32)
    return min(max(_rel(g, w) for g, w in zip(got, cand))
               for cand in want)


def leaf_err(got: Dict[str, float], want: Dict[str, float],
             skip=()) -> float:
    """Worst leaf's gap of norms, relative to the larger of the
    reference's norm of that leaf and the median leaf's."""
    if set(got) != set(want):
        return float("inf")
    keys = [k for k in want if k not in skip]
    med = float(np.median([want[k] for k in keys]))
    return max(abs(got[k] - want[k]) / max(want[k], med, 1e-30)
               for k in keys)


def train_readings(side: dict, ref: dict) -> dict:
    """A side's (the program's or the control's) train readings against
    the reference's."""
    gmed = float(np.median(list(ref["grad"].values())))
    still = {k for k, g in ref["grad"].items() if g < GRAD_FLOOR * gmed}
    return {
        "train_loss_err": max(abs(a - b) / abs(b) for a, b in
                              zip(side["losses"], ref["losses"])),
        "train_grad_err": leaf_err(side["grad"], ref["grad"]),
        "train_change_err": leaf_err(side["change"], ref["change"], still),
        "still_leaves": sorted(still),
    }


def train_step(ref_mod, doc: dict, seed: int, steps: dict,
               seqs: List[np.ndarray], length: int,
               control: bool = False) -> dict:
    """Compare the recorded first draft train steps, and the signal
    windows they consumed, with the reference (and, with ``control``,
    the control with the reference).  ``steps`` holds the program's
    ``feats`` and ``tokens`` per step and its readings ``losses``,
    ``grad`` and ``change``; ``seqs`` every sequence of the stream, each
    at most ``length`` tokens."""
    toks = np.concatenate(steps["tokens"])
    s = toks.shape[1]
    hits = locate(toks, seqs)
    found = [h for h in hits if h]
    out = {"windows": len(hits), "unlocated": len(hits) - len(found)}
    if len(found) < len(hits):
        return out
    sides = [False] + ([True] if control else [])
    feats: Dict[bool, List[List[np.ndarray]]] = {}
    for fp8 in sides:
        feats[fp8] = _reference_feats(ref_mod.forward(doc, seed, fp8=fp8),
                                      seqs, hits, s, length)
    want = feats[False]
    got = np.concatenate(steps["feats"])
    out["program"] = {"signal_feat_err": max(
        feat_err(g, w) for g, w in zip(got, want))}
    # the reference trains on its own features of each window, at the
    # place where the program's agree best with them
    at = [int(np.argmin([feat_err(g, [c]) for c in w]))
          for g, w in zip(got, want)]
    best = [w[i] for w, i in zip(want, at)]
    n = len(steps["tokens"])
    b = toks.shape[0] // n

    def batches(fs):
        return [(np.stack(fs[i * b:(i + 1) * b]), toks[i * b:(i + 1) * b])
                for i in range(n)]
    r = ref_mod.draft_train(doc, seed, batches(best))
    out["program"].update(train_readings(steps, r))
    out["ref_losses"] = r["losses"]
    if control:
        pick = [c[i] for c, i in zip(feats[True], at)]
        c = ref_mod.draft_train(doc, seed, batches(pick), fp8=True)
        out["control"] = {"signal_feat_err": max(
            feat_err(p, w) for p, w in zip(pick, want))}
        out["control"].update(train_readings(c, r))
    return out


TRAIN_NUMBERS = ("signal_feat_err", "train_loss_err", "train_grad_err",
                 "train_change_err")


def merge(served: dict, train: Optional[dict], side: str,
          trains: bool = True) -> dict:
    """The compared numbers of one side, by name.  A stream that ran no
    train step reads each train number as infinite, unless its mix says
    it ``trains`` not, and then they are not compared."""
    gap = served["gap_max"] if side == "program" else served["ctrl_gap_max"]
    out = {"served_gap_max": gap}
    if train is None and not trains:
        return out
    t = (train or {}).get(side, {})
    for k in TRAIN_NUMBERS:
        out[k] = t.get(k, float("inf"))
    return out
