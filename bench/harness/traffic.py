"""Seeded open-loop traffic, read from a traffic file's parameters.

Adapted from the program's ``serve/trace.py`` Poisson generator, with
prompt-length buckets and clipped lognormal output lengths.  Every seed
draws the SAME multiset of prompt lengths, output lengths and
inter-arrival gaps (stratified quantiles of the stated distributions);
the seed only permutes them and draws the prompt tokens.  The trace's
first ``fixed_head`` requests (the opening burst among them) are evenly
spaced members of those multisets, in one order for every seed: a
window sees only a few admissions, and their lengths would otherwise
decide its tails.  So two seeds ask for the same work, and the spread
between runs is the system's, not the generator's.

Arrival times are on the serving clock (the program's virtual clock),
so the generator cannot run late: it is a schedule, not a thread.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    t_arrival: float          # serving-clock seconds
    prompt: np.ndarray        # (S0,) int32
    max_new_tokens: int
    seed: int                 # per-request RNG root handed to the program


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def bucket_counts(weights, n: int) -> List[int]:
    """Largest-remainder split of n requests over the buckets."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    raw = w * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def output_lengths(t: dict, n: int) -> np.ndarray:
    """Clipped lognormal at stratified quantiles: median ``out_median``,
    log-sigma ``out_sigma``, clipped to [out_min, out_max]."""
    z = np.array([NormalDist().inv_cdf(u) for u in _stratified(n)])
    x = np.exp(math.log(t["out_median"]) + t["out_sigma"] * z)
    return np.clip(np.rint(x), t["out_min"], t["out_max"]).astype(int)


def gaps(rate: float, n: int) -> np.ndarray:
    """Exponential inter-arrival gaps at stratified quantiles."""
    return -np.log1p(-_stratified(n)) / rate


def _head_first(x: np.ndarray, h: int, rng) -> np.ndarray:
    """``x`` (sorted) reordered: ``h`` evenly spaced members first, in
    one fixed order, then the rest permuted by ``rng``."""
    pick = ((np.arange(h) + 0.5) * len(x) / max(h, 1)).astype(int)
    order = np.random.default_rng(0).permutation(h)
    return np.concatenate([x[pick][order],
                           rng.permutation(np.delete(x, pick))])


def make_trace(t: dict, vocab: int, seed: int, n: int) -> List[Arrival]:
    """``n`` arrivals for traffic parameters ``t`` (a traffic file).
    The first ``burst`` arrive at time 0 — the slots start full — and
    the rest follow as a Poisson stream at ``rate_rps``; the first
    ``fixed_head`` are the same work for every seed."""
    rng = np.random.default_rng(seed)
    burst = int(t.get("burst", 0))
    head = max(burst, int(t.get("fixed_head", 0)))
    lens = np.repeat(t["prompt_buckets"], bucket_counts(t["prompt_weights"], n))
    lens = _head_first(lens, head, rng)
    outs = _head_first(output_lengths(t, n), head, rng)
    g = _head_first(np.sort(gaps(t["rate_rps"], n - burst)), head - burst,
                    rng)
    times = np.concatenate([np.zeros(burst), np.cumsum(g)])
    req_seeds = rng.integers(0, 2**31 - 1, n)
    return [Arrival(rid=i, t_arrival=float(times[i]),
                    prompt=rng.integers(0, vocab, int(lens[i]), dtype=np.int32),
                    max_new_tokens=int(outs[i]), seed=int(req_seeds[i]))
            for i in range(n)]
