"""What decides ``correct``: the timed path's output against the numpy
reference, under the configuration's guarantees.

Every number here is a count of violations, so each limit is 0 (an
exact comparison):

* ``journal_missing``: acknowledged batches that the journal does not
  hold byte for byte (an ack means the batch is in the journal);
* ``docs_unapplied``: acknowledged tweets that the index never applied;
* ``freq_wrong`` / ``postings_wrong`` (ingest cells): terms whose
  posting count, over the whole vocabulary, or whose postings, read back
  from the pool for a sample drawn from the seed with the longest lists
  in it, differ from the reference's;
* ``answers_wrong`` / ``answers_missing`` (query cells): answers that
  differ from the reference's over the documents that were visible when
  the query was dispatched, and accepted queries never answered.  Every
  answer is compared, not a sample.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference

LIMITS = {"journal_missing": 0, "docs_unapplied": 0, "freq_wrong": 0,
          "postings_wrong": 0, "answers_wrong": 0, "answers_missing": 0}
HEAD_TERMS = 8          # the longest posting lists, always read back
SAMPLE_TERMS = 248      # and this many more, drawn from the seed


def acked_batches(rec) -> list:
    """The acknowledged batches in sequence order, i.e. ingest order."""
    return [rec.acks[s][1] for s in sorted(rec.acks)]


def check_journal(rec, wal, journaled: bool) -> int:
    if not journaled:
        return len(rec.acks)
    _, records = reference.read_journal(str(wal))
    held = dict(records)
    return sum(1 for s, (_, b, _) in rec.acks.items()
               if s not in held or not np.array_equal(held[s], b.docs))


def check_ingest(cfg, engine, batches, seed: int) -> dict:
    """Per-term counts of the whole vocabulary, and the postings of the
    head terms plus a seeded sample, read back from the pool.

    A sharded state holds ``[S, ...]`` of each: the counts are summed
    over shards, and each shard's heap is read back on its own, its
    shard-local docids mapped to global ones (``g = local * S + s``).
    A term's merged list is right when each shard's list is the
    reference's postings of the documents dealt to it (``g % S == s``),
    in order; on one shard that is the whole list."""
    from repro.core.segments import freeze_state
    state = engine.segments.active.state
    V = cfg["vocab"]
    freqs = np.asarray(state.freq).astype(np.int64).reshape(-1, V)
    S = freqs.shape[0]
    heaps = np.asarray(state.heap).reshape(S, -1)
    tails = np.asarray(state.tail).reshape(S, -1)
    freq = freqs.sum(axis=0)
    want = np.zeros(V, np.int64)
    for b in batches:
        want += np.bincount(b.docs[b.docs >= 0], minlength=V)
    present = np.nonzero(want)[0]
    head = np.argsort(-want, kind="stable")[:HEAD_TERMS]
    rng = np.random.default_rng([seed, 5])
    rest = rng.choice(present, size=min(SAMPLE_TERMS, present.size),
                      replace=False)
    terms = np.unique(np.concatenate([head, rest]))
    parts = {int(t): [] for t in terms}
    first = 0
    for b in batches:
        for t, p in reference.postings_of(b.docs, first, terms).items():
            parts[t].append(p)
        first += b.docs.shape[0]
    ref = {t: np.concatenate(p) for t, p in parts.items()}
    bad = set()
    for s in range(S):
        only = np.zeros(V, np.int64)
        only[terms] = freqs[s, terms]
        got = freeze_state(engine.layout, heaps[s], tails[s], only,
                           n_docs=engine.segments.active.next_docid // S)
        for t in terms:
            want_s = ref[int(t)]
            want_s = want_s[(want_s >> reference.POS_BITS) % S == s]
            if not np.array_equal(
                    reference.local_to_global(got.postings(int(t)), s, S),
                    want_s):
                bad.add(int(t))
    return {"freq_wrong": int(np.sum(freq != want)),
            "postings_wrong": len(bad),
            "postings_read": int(sum(p.size for p in ref.values()))}


def check_answers(pool, batches, rec) -> dict:
    """Every answered query against the reference over the documents it
    could see; scored answers compare scores too."""
    answered = [q for q in rec.queries.values() if "resp" in q]
    terms = {t for q in answered for t in q["terms"]}
    idx = reference.QueryIndex(terms or {0})
    for b in batches:
        idx.add(b.docs)
    idx.finish()
    wrong = longest = empty = full = 0
    for q in answered:
        r, upto = q["resp"], q["visible"]
        got = np.asarray(r.docids, np.int64)
        if pool.kind == "scored":
            ids, sc = idx.scored(q["terms"], pool.k, upto)
            ok = (np.array_equal(got, ids) and r.scores is not None
                  and np.array_equal(np.asarray(r.scores, np.int64), sc))
        else:
            ids = idx.topk(q["terms"], pool.k, upto)
            ok = np.array_equal(got, ids)
        wrong += not ok
        longest = max(longest, ids.size)
        empty += ids.size == 0
        full += ids.size == pool.k
    return {"answers_wrong": wrong,
            "answers_missing": len(rec.queries) - len(answered),
            "answers_compared": len(answered), "longest_answer": longest,
            "answers_empty": empty, "answers_at_k": full}


def run_checks(cell, engine, loop, rec, wal, journaled: bool, seed: int):
    """``(checks, info)``: each compared number with its limit, and the
    sizes behind them (printed, not compared)."""
    batches = acked_batches(rec)
    acked_docs = sum(b.docs.shape[0] for b in batches)
    vals = {"journal_missing": check_journal(rec, wal, journaled),
            "docs_unapplied": acked_docs - engine.stats.docs_ingested}
    info = {"acked_batches": len(batches), "acked_docs": acked_docs}
    if cell.mix.get("queries"):
        got = check_answers(rec.pool, batches, rec)
    else:
        got = check_ingest(cell.config, engine, batches, seed)
    for k, v in got.items():
        (vals if k in LIMITS else info)[k] = v
    checks = {k: {"value": int(v), "limit": LIMITS[k]}
              for k, v in vals.items()}
    return checks, info
