"""Seeded tweet stream and microblog query log.

Copied from ``repro.data.synth`` (``TweetStream``, ``query_log``) so that
a change to the program cannot move the yardstick.  Batch ``i`` of a
stream depends only on ``(seed, i)``, so the reference can regenerate
every batch the program was given.
"""
from __future__ import annotations

import numpy as np


def _zipf_probs(vocab: int, alpha: float) -> np.ndarray:
    r = np.arange(1, vocab + 1, dtype=np.float64)
    p = r ** -alpha
    return p / p.sum()


class TweetStream:
    """Zipf(``alpha``) term ids over ``vocab`` terms, rows of ``width``
    slots padded with -1, row lengths ``clip(Poisson(mean_len), 1,
    width)``.  Term ids are Zipf ranks shuffled by :attr:`rank_to_term`,
    as in a real dictionary."""

    def __init__(self, *, vocab: int, mean_len: float, alpha: float,
                 width: int, seed: int):
        self.vocab = int(vocab)
        self.mean_len = float(mean_len)
        self.width = int(width)
        self.seed = int(seed)
        rng = np.random.default_rng([self.seed, 0])
        self.probs = _zipf_probs(self.vocab, alpha)
        self.rank_to_term = rng.permutation(self.vocab)
        self._cdf = np.cumsum(self.probs)

    def batch(self, index: int, n_docs: int) -> np.ndarray:
        """int32[n_docs, width] term ids of batch ``index``, -1-padded."""
        rng = np.random.default_rng([self.seed, 1, int(index)])
        lens = np.clip(rng.poisson(self.mean_len, n_docs), 1, self.width)
        ranks = np.searchsorted(self._cdf, rng.random(int(lens.sum())),
                                side="right")
        ranks = np.minimum(ranks, self.vocab - 1)
        docs = np.full((n_docs, self.width), -1, np.int32)
        docs[np.arange(self.width)[None, :] < lens[:, None]] = \
            self.rank_to_term[ranks]
        return docs

    def expected_freqs(self, n_docs: int) -> np.ndarray:
        """float64[vocab]: expected postings per term id in ``n_docs``."""
        out = np.empty(self.vocab)
        out[self.rank_to_term] = self.probs * n_docs * self.mean_len
        return out


def query_log(kind: str, n_queries: int, freqs: np.ndarray, *,
              seed: int, max_terms: int = 4) -> list:
    """``n_queries`` term tuples drawn by postings-length rank from the
    terms with ``freqs > 0`` (the shape of the paper's Figure 2):
    ``microblog`` is beta(2.2, 2.2) over the frequency ranks, so it
    de-emphasises the very common and the very rare terms; ``aol`` is
    log-uniform over the ranks.  Query lengths are geometric(0.45),
    clipped to ``max_terms`` (mean about 2.3 terms).  Terms within one
    query are distinct."""
    rng = np.random.default_rng([int(seed), 2])
    seen = np.nonzero(freqs)[0]
    order = seen[np.argsort(-freqs[seen], kind="stable")]
    n = len(order)
    if n < max_terms:
        raise ValueError(f"only {n} distinct terms to draw queries from")
    if kind == "microblog":
        u = rng.beta(2.2, 2.2, n_queries * max_terms)
        idx = np.clip((u * (n - 1)).astype(np.int64), 0, n - 1)
    elif kind == "aol":
        u = rng.random(n_queries * max_terms)
        idx = np.clip((np.exp(u * np.log(n)) - 1).astype(np.int64), 0, n - 1)
    else:
        raise ValueError(f"unknown query log kind {kind!r}")
    terms = order[idx].reshape(n_queries, max_terms)
    lens = np.clip(rng.geometric(0.45, n_queries), 1, max_terms)
    out = []
    for row, ln in zip(terms, lens):
        uniq = list(dict.fromkeys(int(t) for t in row))[:int(ln)]
        out.append(tuple(uniq))
    return out
