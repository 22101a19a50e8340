"""The timed path broken underneath, with the rest of a run as it is:
``correct`` must come out false for each fault a cell can have.  (The
exchange between chips does not exist in these one-chip cells.)"""
import numpy as np
import pytest

from chipbench.tests import tiny


def state_unchanged(engine, loop):
    """Every ingest step hands back the pool state it was given."""
    engine.segments.active._ingest = lambda state, *a, **k: state


def half_batch(engine, loop):
    """The second half of every batch's tweets is left out."""
    ingest = engine.ingest

    def half(docs):
        docs = np.array(docs)
        docs[docs.shape[0] // 2:] = -1
        return ingest(docs)
    engine.ingest = half


def altered(engine, loop):
    """One term of every ingested batch, and one docid of every answer,
    altered where it is produced."""
    ingest, dispatch = engine.ingest, engine.dispatch
    vocab = engine.vocab_size

    def ingest_altered(docs):
        docs = np.array(docs)
        docs[0, 0] = (docs[0, 0] + 1) % vocab
        return ingest(docs)

    def alter(ids):
        ids = np.array(ids)
        if ids.size:
            ids[0] += 1
            return ids
        return np.append(ids, 0)

    def dispatch_altered(*a, **k):
        pend = dispatch(*a, **k)
        finish = pend._finish

        def wrong(*host):
            return [(alter(r[0]), r[1]) if isinstance(r, tuple) else alter(r)
                    for r in finish(*host)]
        pend._finish = wrong
        return pend
    engine.ingest = ingest_altered
    engine.dispatch = dispatch_altered


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, altered])
@pytest.mark.parametrize("cell", ["earlybird.ingest",
                                  "tweets2011.active_topk"])
def test_fault_is_not_correct(cell, fault, tmp_path):
    out = tiny.run_tiny(cell, tmp_path, fault=fault)
    assert not out["correct"], out["checks"]
