"""Seeded tokenized corpus, shared by every configuration of the benchmark.

A corpus is a list of documents of token ids (int32), drawn from the
configuration's laws:

* token ids from a Zipf law of exponent ``zipf_s`` over ``vocab`` ids
  (id 0 is the most frequent);
* document lengths from a log-normal law of mean ``doc_len_mean`` and
  shape ``doc_len_sigma``;
* a share ``dup_share`` of documents each carry one span of
  ``dup_span[0]``..``dup_span[1]`` tokens copied from the tokens that
  precede that document (the near-copies an exact-substring dedup pass
  looks for).

The total, counting the one separator the index adds per document, is
exactly ``n_tokens``: the last document is cut to fit, so every corpus of
one size has the same shapes whatever the seed. The same seed gives the
same corpus; `reorder` shuffles one corpus's documents by another seed.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator for one named stream of a run's seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def zipf_cdf(n: int, s: float) -> np.ndarray:
    """Cumulative probabilities of a Zipf law of exponent s over n ranks."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def zipf_draw(rng: np.random.Generator, cdf: np.ndarray, size: int):
    """`size` ranks (0-based) drawn from the law whose cdf is given."""
    return np.minimum(np.searchsorted(cdf, rng.random(size), side="right"),
                      len(cdf) - 1)


def doc_lengths(rng: np.random.Generator, n_tokens: int, mean: float,
                sigma: float) -> np.ndarray:
    """Log-normal document lengths (each >= 1) whose sum plus one separator
    per document is exactly `n_tokens`."""
    mu = np.log(mean) - sigma * sigma / 2.0
    lens = np.zeros(0, np.int64)
    while lens.sum() + len(lens) < n_tokens:
        more = np.maximum(1, np.rint(rng.lognormal(
            mu, sigma, size=max(16, int(2 * n_tokens / (mean + 1)))))
        ).astype(np.int64)
        lens = np.concatenate([lens, more])
    total = np.cumsum(lens + 1)
    k = int(np.searchsorted(total, n_tokens))      # doc k reaches n_tokens
    lens = lens[:k + 1].copy()
    lens[k] -= int(total[k] - n_tokens)
    if lens[k] < 1:                                 # no empty last document
        lens = lens[:k]
        lens[-1] += 1
    assert int(lens.sum() + len(lens)) == n_tokens
    return lens


def make_corpus(spec: dict, n_tokens: int, seed: int, *stream: int) -> list:
    """The documents of one corpus of `n_tokens` (separators included)."""
    rng = rng_for(seed, 1, *stream)
    lens = doc_lengths(rng, n_tokens, spec["doc_len_mean"],
                       spec["doc_len_sigma"])
    flat = zipf_draw(rng, zipf_cdf(spec["vocab"], spec["zipf_s"]),
                     int(lens.sum())).astype(np.int32)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    lo_span, hi_span = spec["dup_span"]
    n_dup = int(round(spec["dup_share"] * len(lens)))
    targets = np.sort(rng.choice(np.arange(1, len(lens)), size=n_dup,
                                 replace=False))
    span = rng.integers(lo_span, hi_span + 1, size=n_dup)
    u_src, u_dst = rng.random(n_dup), rng.random(n_dup)
    for t, m, a, b in zip(targets, span, u_src, u_dst):
        m = int(min(m, lens[t], starts[t]))         # fits doc and its past
        if m < 1:
            continue
        src = int(a * (starts[t] - m + 1))
        dst = int(starts[t] + b * (lens[t] - m + 1))
        flat[dst:dst + m] = flat[src:src + m]
    return np.split(flat, np.cumsum(lens)[:-1])


def reorder(docs, seed: int, *stream: int) -> list:
    """The same documents in an order drawn from `seed`: every seed then
    has the same set of lengths, copies and tokens to index, in another
    order."""
    order = rng_for(seed, 2, *stream).permutation(len(docs))
    return [docs[i] for i in order]


def plant_repeat(docs, length: int) -> int:
    """Copy the head of the second-longest document over the head of the
    longest, up to `length` tokens: one repeat longer than any the laws
    make. Returns the repeat's length."""
    order = np.argsort([len(d) for d in docs])
    src, dst = docs[order[-2]], docs[order[-1]]
    m = int(min(length, len(src), len(dst)))
    dst[:m] = src[:m]
    return m
