"""The plain reference: what a correct index holds and answers.

Numpy only, and independent of ``repro``: an inverted index of the query
terms built from the raw batches (a copy of ``chip_smoke.NumpyIndex``,
with a docid cut-off so that each answer is judged on the documents that
had been ingested when its query was dispatched), the postings and
per-term counts a correct allocator holds, and a reader of the ingest
journal's records.

Postings follow the paper's encoding (section 3.2): one uint32 holds a
24-bit docid above an 8-bit position.  Scores follow the quantized
impact of the scored queries: the sum over the query terms of
``min(tf, 255)``, ranked by score and then by docid, both descending.
"""
from __future__ import annotations

import json
import struct
import zlib

import numpy as np

POS_BITS = 8
SCORE_MAX = 255


def postings_of(docs: np.ndarray, first_docid: int, terms) -> dict:
    """``{term: uint32[n]}``: the postings of ``terms`` in a batch whose
    first row is document ``first_docid``, in (docid, position) order."""
    terms = np.unique(np.asarray(list(terms), np.int64))
    rows, cols = np.nonzero(np.isin(docs, terms))
    t = docs[rows, cols].astype(np.int64)
    packed = (((rows + first_docid).astype(np.uint64) << POS_BITS)
              | np.minimum(cols, (1 << POS_BITS) - 1).astype(np.uint64))
    order = np.lexsort((packed, t))
    t, packed = t[order], packed[order].astype(np.uint32)
    lo = np.searchsorted(t, terms)
    hi = np.searchsorted(t, terms, side="right")
    return {int(x): packed[a:b] for x, a, b in zip(terms, lo, hi)}


def local_to_global(postings: np.ndarray, shard: int,
                    shards: int) -> np.ndarray:
    """A document shard's postings with their local docids mapped to
    global ones (``g = local * shards + shard``), positions kept."""
    p = np.asarray(postings, np.uint64)
    ids = (p >> POS_BITS) * shards + shard
    return ((ids << POS_BITS) | (p & ((1 << POS_BITS) - 1))).astype(
        np.uint32)


class QueryIndex:
    """Per-term ``(docid, tf)`` of a fixed term set, fed batch by batch
    in ingest order (docids count from 0 across every batch)."""

    def __init__(self, terms):
        self.terms = np.unique(np.asarray(list(terms), np.int64))
        self._chunks = []
        self.n_docs = 0

    def add(self, docs: np.ndarray) -> None:
        rows, cols = np.nonzero(np.isin(docs, self.terms))
        self._chunks.append(np.stack(
            [docs[rows, cols].astype(np.int64), rows + self.n_docs], 1))
        self.n_docs += docs.shape[0]

    def finish(self) -> None:
        occ = (np.concatenate(self._chunks) if self._chunks
               else np.zeros((0, 2), np.int64))
        occ = occ[np.lexsort((occ[:, 1], occ[:, 0]))]
        self._docs, self._tf = {}, {}
        for t in self.terms:
            lo, hi = np.searchsorted(occ[:, 0], [t, t + 1])
            ids, tf = np.unique(occ[lo:hi, 1], return_counts=True)
            self._docs[int(t)] = ids
            self._tf[int(t)] = tf
        self._chunks = []

    def list_len(self, term: int) -> int:
        return int(self._docs[int(term)].size)

    def _cut(self, term: int, upto: int):
        ids = self._docs[int(term)]
        n = int(np.searchsorted(ids, upto))
        return ids[:n], self._tf[int(term)][:n]

    def conjunctive(self, terms, upto: int) -> np.ndarray:
        """Docids below ``upto`` that hold every term, newest first."""
        out = self._cut(terms[0], upto)[0]
        for t in terms[1:]:
            out = np.intersect1d(out, self._cut(t, upto)[0])
        return out[::-1]

    def topk(self, terms, k: int, upto: int) -> np.ndarray:
        return self.conjunctive(terms, upto)[:k]

    def scored(self, terms, k: int, upto: int):
        """The ``k`` best (docids, scores) of the conjunction, by score
        and then docid, both descending."""
        ids = self.conjunctive(terms, upto)[::-1]
        score = np.zeros(ids.size, np.int64)
        for t in terms:
            d, tf = self._cut(t, upto)
            score += np.minimum(tf[np.searchsorted(d, ids)], SCORE_MAX)
        order = np.lexsort((-ids, -score))[:k]
        return ids[order], score[order]


# ---------------------------------------------------------------------------
# The ingest journal's framing, read back byte for byte
# ---------------------------------------------------------------------------
JOURNAL_MAGIC = b"REPROJRNL\x01\n"
_HDR = struct.Struct("<QI")      # header length, crc32(header)
_REC = struct.Struct("<QII")     # body length, crc32(length), crc32(body)
_LEN = struct.Struct("<Q")
_U32 = struct.Struct("<I")


def read_journal(path: str):
    """``(base_seq, [(seq, int32 array), ...])`` of every complete
    record; a record whose checksums fail ends the read (a torn tail)."""
    with open(path, "rb") as f:
        buf = f.read()
    if not buf.startswith(JOURNAL_MAGIC):
        raise ValueError(f"{path}: not an ingest journal")
    at = len(JOURNAL_MAGIC)
    n, crc = _HDR.unpack_from(buf, at)
    at += _HDR.size
    head = buf[at:at + n]
    if zlib.crc32(head) != crc:
        raise ValueError(f"{path}: journal header checksum fails")
    base = int(json.loads(head)["base_seq"])
    at += n
    out = []
    while at + _REC.size <= len(buf):
        n, crc_len, crc_body = _REC.unpack_from(buf, at)
        body = buf[at + _REC.size: at + _REC.size + n]
        if (zlib.crc32(_LEN.pack(n)) != crc_len or len(body) != n
                or zlib.crc32(body) != crc_body):
            break
        hlen = _U32.unpack_from(body, 0)[0]
        meta = json.loads(body[_U32.size:_U32.size + hlen])
        arr = np.frombuffer(body[_U32.size + hlen:], dtype=meta["dtype"])
        out.append((int(meta["seq"]), arr.reshape(meta["shape"])))
        at += _REC.size + n
    return base, out
