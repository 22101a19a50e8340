"""Shared benchmark substrate: calibrated corpus facsimile, paper config
lists, pool sizing, and timing helpers.

The paper's corpora (Tweets2011 / AOL / TREC) are not redistributable
offline; `repro.data.synth` generates a Zipf(alpha=1.0) facsimile with the
paper's query-log shapes (DESIGN.md §7).  Scale is reduced for CPU; every
table states it validates ORDERINGS AND RATIOS, not absolute ms.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analytical
from repro.core.index import ActiveSegment
from repro.core.pointers import PoolLayout
from repro.data import synth

# Paper Table 1 configurations (§9.1)
ZG = (1, 4, 7, 11)
Z_MULTI = {
    "Z0": (0, 1, 2, 3, 4, 5, 6, 8),
    "Z1": (1, 2, 3, 5, 6, 8, 9, 10),
    "Z2": (1, 3, 5, 6, 8, 9, 10, 11),
    "Z3": (1, 3, 5, 7, 8, 10, 12),
    "Z4": (1, 3, 6, 8, 9, 11, 12),
    "Z5": (2, 6, 9, 12),
}
Z_FOUR = {
    "Z'0": (1, 2, 3, 5),
    "Z'1": (1, 3, 5, 6),
    "Z'2": (1, 3, 5, 7),
    "Z'3": (1, 3, 6, 8),
    "Z'4": (2, 5, 7, 9),
    "Z'5": (2, 5, 8, 10),
    "Z'6": (2, 5, 8, 11),
    "Z'7": (2, 6, 9, 12),
}
TABLE1 = {"Zg": ZG, **Z_MULTI, **Z_FOUR}

QUERY_KINDS = ("aol", "terabyte", "microblog")


@dataclasses.dataclass(frozen=True)
class BenchScale:
    vocab: int
    n_docs: int
    n_queries: int
    doc_len: int = 14          # tweets average ~14 terms


FAST = BenchScale(vocab=20_000, n_docs=6_000, n_queries=128)
FULL = BenchScale(vocab=60_000, n_docs=30_000, n_queries=256)


@functools.lru_cache(maxsize=4)
def corpus(scale: BenchScale):
    """(first_half, second_half, freqs_first, freqs_second)."""
    spec = synth.CorpusSpec(vocab=scale.vocab, n_docs=scale.n_docs,
                            max_len=scale.doc_len, seed=0)
    first, second = synth.corpus_halves(spec)
    return (spec, first, second,
            synth.term_freqs(first, scale.vocab),
            synth.term_freqs(second, scale.vocab))


@functools.lru_cache(maxsize=16)
def queries(scale: BenchScale, kind: str):
    spec, first, second, _, _ = corpus(scale)
    return synth.query_log(kind, scale.n_queries, second, scale.vocab,
                           seed=hash(kind) % 2**31)


def build_segment(z: Sequence[int], scale: BenchScale,
                  term_start_pools=None) -> Tuple[ActiveSegment, dict]:
    """Index the SECOND corpus half under config z (paper §8 protocol)."""
    spec, first, second, f1, f2 = corpus(scale)
    sp = (None if term_start_pools is None
          else np.asarray(term_start_pools))
    layout = PoolLayout(z=tuple(z),
                        slices_per_pool=analytical.slices_per_pool(
                            z, f2, start_pools=sp))
    seg = ActiveSegment(layout, scale.vocab)
    t0 = time.perf_counter()
    seg.ingest(jnp.asarray(second), term_start_pools=term_start_pools)
    jax.block_until_ready(seg.state.heap)
    t_ingest = time.perf_counter() - t0
    seg.check_health()
    return seg, {"layout": layout, "t_ingest_s": t_ingest,
                 "n_postings": int((second >= 0).sum())}


def time_fn(fn: Callable, *args, repeats: int = 3, warmup: int = 1):
    """Median wall seconds of fn(*args) after warmup (jit-friendly)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), float(np.std(ts))


def pad_queries(qs: np.ndarray, width: int = 8):
    """-1-padded int32[n, k] -> (uint32[n, width] terms, int32[n] lens)."""
    n, k = qs.shape
    out = np.zeros((n, width), np.uint32)
    lens = (qs >= 0).sum(axis=1).astype(np.int32)
    out[:, :k] = np.where(qs >= 0, qs, 0).astype(np.uint32)
    return jnp.asarray(out), jnp.asarray(lens)


def fmt_ms(mean_s: float, std_s: float) -> str:
    return f"{mean_s * 1e3:8.2f} (±{std_s * 1e3:5.2f})"
