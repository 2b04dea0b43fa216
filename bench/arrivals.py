"""Seeded open-loop traffic, generated from a mix file.

A mix is a JSON file ``bench/traffic/<name>.json`` of parameters; this
one generator reads every mix.  The shapes of a run's traffic are fixed
by the mix's ``template_seed`` and by the run's length: every run seed
sends the same multiset of (prompt width, output budget) pairs and the
same multiset of inter-arrival gaps, in an order and with token contents
drawn from the run seed.  So two seeds offer the same work, and a
difference between them is noise, not a different load.

Each seed reorders the template only within consecutive blocks of
``reorder_block`` requests (the whole window where the mix sets none),
so a long request stays near its place in the window and no seed moves
work across the window's edges.

Arrivals are a Poisson process conditioned on its count: ``n`` requests
in ``seconds``, with ``n + 1`` exponential gaps scaled to sum to
``seconds`` (the uniform order statistics of a Poisson process).

The Markov token domains follow ``repro.data.workloads.make_domains``
(disjoint vocabulary halves, an order-1 chain per domain), sampled for
all requests of a domain at once.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


def load_mix(name: str, directory: str = TRAFFIC_DIR) -> dict:
    """The mix file ``<directory>/<name>.json``."""
    with open(os.path.join(directory, name + ".json")) as f:
        mix = json.load(f)
    mix.setdefault("name", name)
    return mix


@dataclasses.dataclass
class Send:
    """One request of a schedule: its send time (seconds since the
    stream starts), domain, prompt tokens and output budget."""
    t: float
    domain: str
    prompt: np.ndarray
    max_new: int


class Domain:
    """Order-1 Markov chain over ``[lo, hi)`` with ``branching`` next-token
    choices per state (Dirichlet(0.5) weights), seeded."""

    def __init__(self, lo: int, hi: int, branching: int, seed: int):
        rng = np.random.default_rng(seed)
        n = hi - lo
        self.lo = lo
        self.next_tok = rng.integers(0, n, size=(n, branching))
        probs = rng.dirichlet(np.full(branching, 0.5), size=n)
        self.cum = np.cumsum(probs, axis=1)

    def sample(self, rng: np.random.Generator, lengths) -> List[np.ndarray]:
        lengths = np.asarray(lengths, int)
        if not lengths.size:
            return []
        n_state = self.next_tok.shape[0]
        rows = np.arange(lengths.size)
        out = np.empty((lengths.size, int(lengths.max())), np.int32)
        tok = rng.integers(0, n_state, size=lengths.size)
        out[:, 0] = tok
        for j in range(1, out.shape[1]):
            u = rng.random(lengths.size)
            k = (self.cum[tok] < u[:, None]).sum(axis=1)
            k = np.minimum(k, self.next_tok.shape[1] - 1)
            tok = self.next_tok[tok, k]
            out[:, j] = tok
        out += self.lo
        return [out[i, :lengths[i]] for i in rows]


def make_domains(vocab: int, mix: dict) -> Dict[str, Domain]:
    d = mix["domains"]
    names, branchings = d["names"], d["branchings"]
    span = vocab // len(names)
    return {name: Domain(i * span, (i + 1) * span, branchings[i],
                         d.get("seed", 0) + 17 * i)
            for i, name in enumerate(names)}


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    return np.exp(np.log(spec["median"]) + spec["sigma"]
                  * rng.standard_normal(n))


def shapes(mix: dict, seconds: float, phase: str):
    """The fixed shapes of ``seconds`` of traffic: (gaps, widths, budgets),
    from the mix's template seed alone.  ``phase`` separates the
    template of the warm-up from that of the measured window."""
    salt = {"warm": 1, "window": 2}[phase]
    rng = np.random.default_rng([int(mix["template_seed"]), salt])
    n = max(int(round(mix["rate_rps"] * seconds)), 1)
    gaps = rng.exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()
    p = mix["prompt"]
    widths = np.asarray(sorted(p["widths"]), float)
    drawn = _lognormal(rng, p, n)
    # nearest width in log space: each prompt is sent at exactly one of
    # the stated widths, so no other prefill shape can occur
    idx = np.abs(np.log(drawn)[:, None] - np.log(widths)[None, :]
                 ).argmin(axis=1)
    o = mix["output"]
    budgets = np.clip(np.round(_lognormal(rng, o, n)), o["min"], o["max"])
    return gaps[:n], widths[idx].astype(int), budgets.astype(int)


def _block_permutation(rng, n: int, block: int) -> np.ndarray:
    """A permutation of ``range(n)`` that moves each index only within
    its block of ``block`` consecutive indices."""
    out = np.arange(n)
    for s0 in range(0, n, block):
        out[s0:s0 + block] = s0 + rng.permutation(min(block, n - s0))
    return out


def schedule(mix: dict, vocab: int, seed: int, seconds: float, *,
             phase: str = "window", start: float = 0.0,
             domains=None) -> List[Send]:
    """``seconds`` of the mix's traffic from ``start`` on, for ``seed``:
    the template's gaps and (width, budget) pairs, each permuted by the
    seed, with token contents drawn from the seed."""
    gaps, widths, budgets = shapes(mix, seconds, phase)
    rng = np.random.default_rng([seed, {"warm": 1, "window": 2}[phase]])
    block = int(mix.get("reorder_block", 0)) or widths.size
    gaps = gaps[_block_permutation(rng, gaps.size, block)]
    order = _block_permutation(rng, widths.size, block)
    widths, budgets = widths[order], budgets[order]
    domains = domains if domains is not None else make_domains(vocab, mix)
    names = list(domains)
    which = rng.integers(0, len(names), size=widths.size)
    prompts: List[np.ndarray] = [None] * widths.size
    for k, name in enumerate(names):
        sel = np.flatnonzero(which == k)
        for i, toks in zip(sel, domains[name].sample(rng, widths[sel])):
            prompts[i] = toks
    times = start + np.cumsum(gaps)
    return [Send(float(times[i]), names[which[i]], prompts[i],
                 int(budgets[i])) for i in range(widths.size)]


def warm_bursts(widths, batch: int) -> List[Dict[int, int]]:
    """Admission bursts that compile every chunked-refill program the
    mix can reach: for each prompt width and each power-of-two row
    bucket up to ``batch``, one burst of that width alone (its pipeline
    commits fused with its last chunk) and one beside a single prompt of
    another width (a cohort: chunks unfused, then a separate commit).
    Each burst maps width -> number of requests sent at once."""
    widths = sorted(widths)
    buckets = []
    b = 1
    while b <= batch:
        buckets.append(b)
        b *= 2
    bursts = []
    for i, w in enumerate(widths):
        for b in buckets:
            bursts.append({w: b})
            if len(widths) > 1 and batch > 1:
                # a count that rounds up to bucket b and leaves a lane
                # for the other width's single prompt
                n = b if b + 1 <= batch else b // 2 + 1
                other = widths[(i + 1) % len(widths)]
                bursts.append({w: n, other: 1})
    return bursts
