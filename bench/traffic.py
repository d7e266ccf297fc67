"""The benchmark's one traffic generator, driven by the data files under
``bench/traffic/``.

Every seed gets the same multiset of sizes and gaps, in another order: sizes
are the quantiles of the stated distribution at (i + 0.5) / n, and arrival
gaps the quantiles of the exponential gap of a Poisson process at the cell's
rate.  The seed shuffles each of them by its own independent permutation and
draws the token ids, so runs on different seeds do the same total work,
while gaps and sizes stay independent of one another and of their
neighbours: short gaps bunch, and long requests meet, as they do among
independent users.  Timing starts from each request's due time, not from
when the generator got round to it.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


def rng(seed: int, *tag: int) -> np.random.Generator:
    """A numpy generator for (seed, tag): any whole number is a seed."""
    return np.random.default_rng([int(seed) % 2**63, *tag])


def jax_seed(seed: int, tag: int = 0) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` drawn from (seed, tag)."""
    return int(rng(seed, 0x5EED, tag).integers(0, 2**31 - 1))


def shuffled(x: np.ndarray, seed: int, tag: int) -> np.ndarray:
    """``x`` in the order of a permutation drawn from (seed, tag)."""
    return x[rng(seed, tag).permutation(len(x))]


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n integer sizes: the quantiles of ``spec`` at (i + 0.5) / n, clipped
    to [min, max].  ``spec["dist"]`` is ``lognormal`` (median, sigma) or
    ``poisson`` (mean)."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        inv = NormalDist().inv_cdf
        x = spec["median"] * np.exp(spec["sigma"] * np.array([inv(v) for v in q]))
        x = np.rint(x)
    elif spec["dist"] == "poisson":
        lam, hi = float(spec["mean"]), int(spec["max"])
        k = np.arange(hi + 1)
        logp = k * math.log(lam) - lam - np.array([math.lgamma(v + 1) for v in k])
        cdf = np.cumsum(np.exp(logp))
        x = np.searchsorted(cdf, q).astype(np.float64)
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


# -- serving ------------------------------------------------------------------


@dataclasses.dataclass
class ServeRequest:
    index: int
    due_s: float                 # offset from the window's start
    prompt: np.ndarray           # (prompt_len,) int32
    max_new_tokens: int
    temperature: float           # 0 = greedy
    top_k: int
    top_p: float
    min_p: float
    seed: int


def serve_requests(traffic: dict, seed: int, vocab: int, seconds: float,
                   rate_per_s: Optional[float] = None,
                   count: Optional[int] = None) -> List[ServeRequest]:
    """The requests of one run.  ``poisson``: open-loop arrivals with
    exponential gaps at ``rate_per_s`` over ``seconds``; ``backlog``:
    ``count`` requests all due
    at 0.  Every ``greedy_every``-th request, in arrival order, is greedy;
    the rest sample with ``traffic["sampling"]``."""
    proc = traffic["process"]
    if proc == "poisson":
        n = max(1, int(math.ceil(rate_per_s * seconds)))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / rate_per_s
        gaps = shuffled(gaps, seed, 1)
        due = np.cumsum(gaps) - gaps[0]
    elif proc == "backlog":
        n = int(count)
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    plen = shuffled(quantiles(traffic["prompt_len"], n), seed, 2)
    olen = shuffled(quantiles(traffic["output_len"], n), seed, 3)
    r = rng(seed, 4)
    sp = traffic["sampling"]
    every = int(traffic["greedy_every"])
    seeds = rng(seed, 5).integers(0, 2**31 - 1, size=n)
    out = []
    for i in range(n):
        greedy = i % every == 0
        out.append(ServeRequest(
            index=i, due_s=float(due[i]),
            prompt=r.integers(0, vocab, int(plen[i])).astype(np.int32),
            max_new_tokens=int(olen[i]),
            temperature=0.0 if greedy else float(sp["temperature"]),
            top_k=int(sp["top_k"]), top_p=float(sp["top_p"]),
            min_p=float(sp["min_p"]), seed=int(seeds[i]),
        ))
    return out


def prefill_buckets(traffic: dict) -> List[int]:
    """Prompt lengths whose prefixes land in every power-of-two prefill
    bucket that this traffic's prompts can reach, and no other."""
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    lens, b = [], 1 << max(0, (lo - 2).bit_length())
    while True:
        # a prefix of b tokens fills bucket b; the longest prompt reaches the last
        lens.append(min(b + 1, hi))
        if b >= hi - 1:
            break
        b *= 2
    return lens


# -- LDA corpus -----------------------------------------------------------------


@dataclasses.dataclass
class LDACorpus:
    docs: np.ndarray      # (M, maxN) int32 word ids, 0-padded
    mask: np.ndarray      # (M, maxN) bool
    lengths: np.ndarray   # (M,) int64

    @property
    def tokens(self) -> int:
        return int(self.lengths.sum())


def lda_corpus(traffic: dict, M: int, V: int, K: int, seed: int) -> LDACorpus:
    """A corpus with planted topics: topics ~ Dirichlet(topic_concentration)
    over words, documents ~ Dirichlet(doc_concentration) over topics, each
    token's topic and word by inverse cdf.  Document lengths are the fixed
    Poisson quantiles, in the seed's order."""
    g = rng(seed, 10)
    lengths = quantiles({"dist": "poisson", "mean": traffic["avg_len"],
                         "min": 1, "max": traffic["max_len"]}, M)
    lengths = lengths[g.permutation(M)]
    phi = g.dirichlet(np.full(V, traffic["topic_concentration"]), size=K)   # (K, V)
    theta = g.dirichlet(np.full(K, traffic["doc_concentration"]), size=M)   # (M, K)
    n = int(lengths.sum())
    doc = np.repeat(np.arange(M), lengths)
    # one searchsorted over all rows: row m's cdf is shifted up by m
    cdf_t = np.cumsum(theta, axis=1)
    cdf_t /= cdf_t[:, -1:]
    cdf_t += np.arange(M)[:, None]
    topic = np.searchsorted(cdf_t.ravel(), doc + g.random(n), side="right") - doc * K
    topic = np.clip(topic, 0, K - 1)
    cdf_p = np.cumsum(phi, axis=1)
    cdf_p /= cdf_p[:, -1:]
    cdf_p += np.arange(K)[:, None]
    word = np.searchsorted(cdf_p.ravel(), topic + g.random(n), side="right") - topic * V
    word = np.clip(word, 0, V - 1)
    maxN = int(lengths.max())
    mask = np.arange(maxN)[None, :] < lengths[:, None]
    docs = np.zeros((M, maxN), np.int32)
    docs[mask] = word
    return LDACorpus(docs=docs, mask=mask, lengths=lengths)
