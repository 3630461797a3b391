"""The one traffic generator: every mix is a data file under ``traffic/``.

A mix names its arrival process and its length distributions; the cell's
file gives the rate.  Every seed gets the same multiset of lengths, gaps
and document choices (stratified quantiles of each distribution) and its
own token ids: the amount of work is fixed.  The arrival times, the
document each request asks for and each document's length follow one
schedule for every seed; the seed deals the prompt and output lengths
out over it in its own order.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

ARRIVALS = ("poisson", "backlog")


@dataclass(frozen=True)
class Req:
    """One request as the client sends it."""
    rid: int
    due_s: float                 # offset from the window's start
    prompt: tuple[int, ...]
    max_new: int
    doc: int = -1                # shared document index, -1 if none


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("arrivals") not in ARRIVALS:
        raise ValueError(f"{path}: arrivals must be one of {ARRIVALS}")
    for key in ("prompt", "output"):
        _check_dist(mix[key], f"{path}: {key}")
    return mix


def _check_dist(spec: dict, where: str) -> None:
    if spec.get("dist") not in ("lognormal", "uniform"):
        raise ValueError(f"{where}: dist must be lognormal or uniform")
    if not 1 <= spec["min"] <= spec["max"]:
        raise ValueError(f"{where}: need 1 <= min <= max")


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths at the distribution's stratified quantiles, clipped to
    ``[min, max]``, in an order drawn from ``rng``."""
    u = _strata(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        x = np.floor(spec["min"] + u * (spec["max"] - spec["min"] + 1))
    x = np.clip(x, spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(x)


def poisson_due(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets of ``n`` requests whose gaps are the exponential
    distribution's stratified quantiles at mean ``1 / rate``."""
    gaps = -np.log1p(-_strata(n)) / rate
    return np.cumsum(rng.permutation(gaps))


def zipf_ranks(count: int, s: float, n: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` popularity ranks (0 = most popular) at the stratified
    quantiles of Zipf(``s``) over ``count`` items."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w) / w.sum()
    ranks = np.searchsorted(cdf, _strata(n), side="left")
    return rng.permutation(np.minimum(ranks, count - 1))


def request_count(mix: dict, cell: dict, seconds: float) -> int:
    """Requests a run of ``seconds`` offers: the rate times the window
    for an open loop; for a backlog, enough that the queue still holds
    ``waiting`` requests when the window closes at the cell's rate."""
    n = math.ceil(cell["rate"] * seconds)
    if mix["arrivals"] == "backlog":
        n += mix["waiting"]
    return max(1, n)


@dataclass(frozen=True)
class Traffic:
    reqs: list            # Req, in due order
    docs: list            # shared documents, most popular first


def generate(mix: dict, cell: dict, seed: int, vocab: int,
             seconds: float, rid0: int = 0) -> Traffic:
    """The requests of one run, in due order."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    # one schedule for every seed: when requests come, and which document
    # each asks for with the length each document has.  The seed deals
    # the lengths out over it and makes the tokens; were the schedule
    # the seed's, its clumps and misses would move the tail
    fixed = np.random.default_rng(0x5C4ED)
    n = request_count(mix, cell, seconds)
    outs = draw_lengths(mix["output"], n, rng)
    if mix["arrivals"] == "poisson":
        due = poisson_due(cell["rate"], n, fixed)
    else:
        due = np.zeros(n)
    docs = mix.get("documents")
    if docs is None:
        plens = draw_lengths(mix["prompt"], n, rng)
        return Traffic([Req(rid0 + i, float(due[i]), tuple(
            int(t) for t in rng.integers(0, vocab, plens[i])), int(outs[i]))
            for i in range(n)], [])
    doc_lens = draw_lengths(dict(dist="uniform", min=docs["min"],
                                 max=docs["max"]), docs["count"], fixed)
    texts = [tuple(int(t) for t in rng.integers(0, vocab, m))
             for m in doc_lens]
    ranks = zipf_ranks(docs["count"], docs["zipf"], n, fixed)
    qlens = draw_lengths(mix["prompt"], n, rng)
    return Traffic([Req(rid0 + i, float(due[i]),
                        texts[ranks[i]] + tuple(int(t) for t in rng.integers(
                            0, vocab, qlens[i])),
                        int(outs[i]), int(ranks[i]))
                    for i in range(n)], texts)


def longest_prompt(mix: dict) -> int:
    docs = mix.get("documents")
    return mix["prompt"]["max"] + (docs["max"] if docs else 0)


def shape_warmup(buckets, max_len: int, vocab: int, seed: int,
                 rid0: int) -> list[Req]:
    """Requests that make the engine run every prompt bucket and every
    (allocation, tail) pair a prefix hit can produce, whatever the mix:
    a preempted request comes back with its tokens appended and finds its
    own prompt indexed, so any mix can reach any pair.

    ``buckets``: the engine's padded prompt lengths.  For each bucket
    ``a``, a prompt of ``n = a - 2`` tokens (two outputs each: a prefill
    token and a decode step) and, for each smaller bucket ``b``, a fresh
    prompt's first ``n - b`` tokens followed by that whole prompt, which
    then finds ``n - b`` tokens cached and prefills a tail of ``b``.  The
    first request fills the first bucket exactly and asks for four
    tokens: served alone, its three decode steps take a fresh page and
    run as one burst, the second step fed by the first's output."""
    rng = np.random.default_rng([seed, 0x3A12])
    prompts = [rng.integers(0, vocab, buckets[0])]
    for a in buckets:
        n = min(a, max_len) - 2
        prompts.append(rng.integers(0, vocab, n))
        for b in buckets:
            if b < a and n - b >= 1:
                p = rng.integers(0, vocab, n)
                prompts += [p[:n - b], p]
    return [Req(rid0 + i, 0.0, tuple(int(t) for t in p), 4 if i == 0 else 2)
            for i, p in enumerate(prompts)]
