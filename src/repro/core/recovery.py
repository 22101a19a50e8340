"""Durable index snapshots + journaled crash recovery (ROADMAP item 5's
prerequisite: the serving layer can only be trusted once the engine under
it survives a crash).

The paper's index lives entirely in main memory; Mishne et al. ("Fast
Data in the Era of Big Data", PAPERS.md) make durability and fast
restart first-class requirements for exactly this real-time serving
shape.  This module closes that gap for both lifecycle engines with two
host-side artifacts and one contract:

  * **Snapshot archive** (:func:`snapshot` / :func:`restore`) — one file
    holding every ``PoolState`` leaf, every frozen segment's CSR (packed
    postings + offsets, per shard for the sharded engine), the lifecycle
    counters, compaction tiers and the engine's construction config,
    with a JSON manifest and a CRC32 per array.  ``restore`` rebuilds a
    :class:`~repro.core.lifecycle.LifecycleEngine` /
    :class:`~repro.core.lifecycle.ShardedLifecycleEngine` (re-stacking
    the sharded ``[S, ...]`` leaves; the shard count must match — docid
    residue classes ``d % S`` only survive for the same S) and re-syncs
    the qexec ``FrozenStack`` via ``_sync_frozen``.  Writes are atomic
    (tmp file + ``os.replace``), so a crash mid-snapshot leaves the
    previous snapshot intact.
  * **Ingest journal** (:class:`IngestJournal` / :func:`read_journal`) —
    an append-only log of raw ingest batches, CRC-framed per record with
    contiguous sequence numbers.  The WAL contract is append-THEN-apply:
    a batch is journaled (and only then acknowledged) before
    ``engine.ingest`` runs, so a crash at ANY point loses no
    acknowledged batch.  A torn final record (crash mid-append) is
    dropped silently — that batch was never applied or acked; any other
    framing/CRC/sequence damage raises :class:`CorruptSnapshotError`.
  * **Recovery** (:func:`recover`) — restore the newest snapshot, then
    replay the journal's batches through the ordinary ingest path
    (rollover, reclamation and compaction re-run deterministically), so
    the recovered engine is BIT-IDENTICAL to the uncrashed one: pool
    leaves, frozen CSRs, counters, and every query result
    (tests/test_recovery.py, repro.analysis.faults).  ``expect_seq``
    passes the caller's durable watermark (e.g. from an ack log): if the
    journal ends short of it — complete records missing, which framing
    alone cannot distinguish from a clean shutdown —
    :class:`CorruptSnapshotError` is raised instead of silently serving
    a shorter index.

:func:`engine_fingerprint` digests everything the contract covers into
CRC32s, so "bit-identical" is a dict equality check in tests, benches
and the fault harness.  See docs/durability.md for the archive format,
the replay contract and the recovery-time model.
"""
from __future__ import annotations

import dataclasses
import json
import os
import struct
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import segments as seg_mod
from repro.core import sharded_index as shx
from repro.core.pointers import PoolLayout
from repro.core.slicepool import PoolState

SNAP_MAGIC = b"REPROSNAP\x01\n"
JRNL_MAGIC = b"REPROJRNL\x01\n"
FORMAT_VERSION = 1

# manifest header: u64 manifest length + u32 manifest CRC32
_HDR = struct.Struct("<QI")
# journal record frame: u64 body length + u32 CRC32 of the length field
# itself + u32 body CRC32.  The length field gets its own checksum so a
# corrupted mid-file length cannot swallow the records after it and
# masquerade as a torn tail.
_REC = struct.Struct("<QII")
_LEN = struct.Struct("<Q")
_U32 = struct.Struct("<I")


class CorruptSnapshotError(RuntimeError):
    """A snapshot archive or ingest journal fails an integrity check
    (bad magic, truncation, CRC mismatch, sequence gap, or a journal
    ending short of the durable watermark).  Recovery NEVER proceeds
    past one of these — a loud failure beats a silently shorter or
    corrupted index."""


# ---------------------------------------------------------------------------
# Archive container: magic | manifest header | JSON manifest | payload
# ---------------------------------------------------------------------------
def write_archive(path: str, meta: Dict[str, Any],
                  arrays: List[Tuple[str, np.ndarray]]) -> None:
    """Write ``arrays`` (name-ordered) + ``meta`` as one checksummed
    archive, atomically (tmp file + rename)."""
    entries = []
    payload = bytearray()
    for name, arr in arrays:
        arr = np.asarray(arr)
        # NOTE: tobytes() handles layout; np.ascontiguousarray would
        # silently promote 0-d leaves (the sticky overflow flag) to 1-d.
        raw = arr.tobytes()
        entries.append({"name": name, "dtype": str(arr.dtype),
                        "shape": list(arr.shape),
                        "offset": len(payload), "nbytes": len(raw),
                        "crc32": zlib.crc32(raw)})
        payload += raw
    manifest = json.dumps({"meta": meta, "arrays": entries},
                          sort_keys=True).encode()
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(SNAP_MAGIC)
        f.write(_HDR.pack(len(manifest), zlib.crc32(manifest)))
        f.write(manifest)
        f.write(bytes(payload))
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def read_archive(path: str) -> Tuple[Dict[str, Any],
                                     Dict[str, np.ndarray]]:
    """Read + verify an archive; every damaged byte is LOUD.

    Raises :class:`CorruptSnapshotError` on bad magic, a truncated
    manifest or payload, a manifest CRC mismatch, or any per-array CRC
    mismatch (a single flipped bit in any leaf is caught)."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as exc:
        raise CorruptSnapshotError(f"cannot read snapshot {path}: {exc}")
    if len(blob) < len(SNAP_MAGIC) + _HDR.size:
        raise CorruptSnapshotError(
            f"{path}: {len(blob)} bytes is shorter than the archive "
            f"header — truncated snapshot")
    if blob[: len(SNAP_MAGIC)] != SNAP_MAGIC:
        raise CorruptSnapshotError(
            f"{path}: bad magic {blob[:len(SNAP_MAGIC)]!r} — not a "
            f"repro snapshot archive")
    mlen, mcrc = _HDR.unpack_from(blob, len(SNAP_MAGIC))
    mstart = len(SNAP_MAGIC) + _HDR.size
    manifest = blob[mstart: mstart + mlen]
    if len(manifest) != mlen:
        raise CorruptSnapshotError(
            f"{path}: manifest truncated ({len(manifest)}/{mlen} bytes)")
    if zlib.crc32(manifest) != mcrc:
        raise CorruptSnapshotError(f"{path}: manifest CRC mismatch")
    try:
        doc = json.loads(manifest)
    except ValueError as exc:
        raise CorruptSnapshotError(f"{path}: manifest not JSON: {exc}")
    payload = blob[mstart + mlen:]
    arrays: Dict[str, np.ndarray] = {}
    for e in doc["arrays"]:
        raw = payload[e["offset"]: e["offset"] + e["nbytes"]]
        if len(raw) != e["nbytes"]:
            raise CorruptSnapshotError(
                f"{path}: leaf {e['name']!r} truncated "
                f"({len(raw)}/{e['nbytes']} bytes)")
        if zlib.crc32(raw) != e["crc32"]:
            raise CorruptSnapshotError(
                f"{path}: leaf {e['name']!r} CRC mismatch — corrupted "
                f"payload byte(s)")
        arr = np.frombuffer(raw, dtype=np.dtype(e["dtype"]))
        want = int(np.prod(e["shape"], dtype=np.int64))
        if arr.size != want:
            raise CorruptSnapshotError(
                f"{path}: leaf {e['name']!r} holds {arr.size} elements, "
                f"manifest shape {e['shape']} wants {want}")
        arrays[e["name"]] = arr.reshape(e["shape"]).copy()
    return doc["meta"], arrays


# ---------------------------------------------------------------------------
# Engine serialization
# ---------------------------------------------------------------------------
def _engine_kind(engine) -> str:
    from repro.core import lifecycle as lc
    if isinstance(engine, lc.ShardedLifecycleEngine):
        return "sharded"
    if isinstance(engine, lc.LifecycleEngine):
        return "single"
    raise TypeError(f"cannot snapshot {type(engine).__name__}; expected "
                    f"LifecycleEngine or ShardedLifecycleEngine")


def _frozen_members(fz) -> List[seg_mod.FrozenSegment]:
    shards = getattr(fz, "shards", None)
    return list(shards) if shards is not None else [fz]


def snapshot(engine, path: str, *, seq: int = 0) -> Dict[str, Any]:
    """Serialize the engine's full state to ``path``; returns the meta
    dict written into the manifest.

    ``seq`` is the journal sequence watermark: the number of ingest
    batches applied to this engine so far.  :func:`recover` replays only
    journal records with ``record.seq >= seq``, so one long-lived
    journal can span several snapshots.
    """
    kind = _engine_kind(engine)
    segs = engine.segments
    policy = getattr(segs, "compaction", None)
    admission = getattr(engine, "admission", None)
    cfg = {
        "z": list(engine.layout.z),
        "slices_per_pool": list(engine.layout.slices_per_pool),
        "vocab_size": int(engine.vocab_size),
        "docs_per_segment": int(segs.docs_per_segment),
        "max_slices": int(engine.max_slices),
        "max_len": int(engine.max_len),
        "max_query_len": int(engine.max_query_len),
        "max_segments": int(segs.max_segments),
        "use_kernel": bool(engine.use_kernel),
        "interpret": engine.interpret,
        "bulk_ingest": bool(segs.bulk_ingest),
        "batched": bool(engine.batched),
        # the RAW constructor arg (None = backend default), so an
        # explicit True/False round-trips while None keeps resolving
        # against whatever backend restores the snapshot
        "batched_kernel": engine.batched_kernel,
        "validate": bool(engine.validate),
        "stable_shapes": bool(getattr(engine, "stable_shapes", False)),
        "compaction_fanout": (int(policy.fanout)
                              if policy is not None else None),
        "admission": (dataclasses.asdict(admission)
                      if admission is not None else None),
    }
    arrays: List[Tuple[str, np.ndarray]] = [
        (f"active/{name}", np.asarray(leaf))
        for name, leaf in zip(PoolState._fields, segs.active.state)]
    if segs._hist_freqs is not None:
        arrays.append(("hist_freqs",
                       np.asarray(segs._hist_freqs, np.int64)))
    frozen_meta = []
    for i, fz in enumerate(segs.frozen):
        frozen_meta.append({"n_docs": int(fz.n_docs),
                            "doc_base": int(fz.doc_base),
                            "tier": int(getattr(fz, "tier", 0))})
        for s, member in enumerate(_frozen_members(fz)):
            prefix = (f"frozen/{i}/shard{s}" if kind == "sharded"
                      else f"frozen/{i}")
            arrays.append((f"{prefix}/offsets",
                           np.asarray(member.offsets, np.int64)))
            arrays.append((f"{prefix}/data",
                           np.asarray(member.data, np.uint32)))
    meta = {
        "format": FORMAT_VERSION,
        "kind": kind,
        "num_shards": (int(segs.num_shards) if kind == "sharded"
                       else 1),
        "config": cfg,
        "active": {"next_docid": int(segs.active.next_docid)},
        "segments": {"doc_base": int(segs._doc_base),
                     "n_rollovers": int(segs.n_rollovers),
                     "n_compactions": int(segs.n_compactions)},
        "frozen": frozen_meta,
        "has_hist_freqs": segs._hist_freqs is not None,
        "stats": dataclasses.asdict(engine.stats),
        "seq": int(seq),
    }
    write_archive(path, meta, arrays)
    return meta


def _leaf(arrays: Dict[str, np.ndarray], name: str) -> np.ndarray:
    """One archive leaf, or :class:`CorruptSnapshotError` if the
    manifest lacks it (a tampered-but-checksummed archive must fail as
    corruption, not as a bare ``KeyError``)."""
    arr = arrays.get(name)
    if arr is None:
        raise CorruptSnapshotError(f"archive lacks leaf {name}")
    return arr


def _build_engine(meta: Dict[str, Any], arrays: Dict[str, np.ndarray],
                  *, mesh=None, rules=None, **overrides):
    """Rebuild an engine from archive contents (shared by
    :func:`restore` and :func:`recover`)."""
    from repro.core import lifecycle as lc

    kind = meta["kind"]
    cfg = dict(meta["config"])
    layout = PoolLayout(z=tuple(cfg.pop("z")),
                        slices_per_pool=tuple(cfg.pop("slices_per_pool")))
    fanout = cfg.pop("compaction_fanout")
    adm_cfg = cfg.pop("admission")
    kwargs = dict(
        max_slices=cfg["max_slices"], max_len=cfg["max_len"],
        max_query_len=cfg["max_query_len"],
        max_segments=cfg["max_segments"],
        use_kernel=cfg["use_kernel"], interpret=cfg["interpret"],
        bulk_ingest=cfg["bulk_ingest"], batched=cfg["batched"],
        batched_kernel=cfg.get("batched_kernel"),
        validate=cfg["validate"],
        stable_shapes=cfg.get("stable_shapes", False),
        compaction=(seg_mod.CompactionPolicy(fanout=fanout)
                    if fanout is not None else None),
        admission=(lc.AdmissionController(**adm_cfg)
                   if adm_cfg is not None else None),
    )
    kwargs.update(overrides)
    if kind == "sharded":
        S = int(meta["num_shards"])
        if mesh is None:
            mesh, rules = shx.make_doc_mesh(S)
        eng = lc.ShardedLifecycleEngine(
            layout, cfg["vocab_size"], cfg["docs_per_segment"], mesh,
            rules=rules, **kwargs)
        if eng.segments.num_shards != S:
            raise ValueError(
                f"snapshot was taken on {S} shards but the mesh "
                f"provides {eng.segments.num_shards}; docid residue "
                f"classes d % S only match for the same shard count")
    else:
        eng = lc.LifecycleEngine(layout, cfg["vocab_size"],
                                 cfg["docs_per_segment"], **kwargs)

    # -- active pool: every PoolState leaf restacked verbatim ------------
    init = eng.segments.active.state
    leaves = []
    for name, ref in zip(PoolState._fields, init):
        arr = _leaf(arrays, f"active/{name}")
        if tuple(arr.shape) != tuple(ref.shape) \
                or np.dtype(arr.dtype) != np.dtype(ref.dtype):
            raise CorruptSnapshotError(
                f"leaf active/{name}: archive {arr.dtype}{arr.shape} "
                f"does not match the engine's "
                f"{np.dtype(ref.dtype)}{tuple(ref.shape)}")
        leaves.append(jnp.asarray(arr))
    segs = eng.segments
    segs.active.state = PoolState(*leaves)
    segs.active.next_docid = int(meta["active"]["next_docid"])
    segs._doc_base = int(meta["segments"]["doc_base"])
    segs.n_rollovers = int(meta["segments"]["n_rollovers"])
    segs.n_compactions = int(meta["segments"]["n_compactions"])
    segs._hist_freqs = (_leaf(arrays, "hist_freqs")
                        if meta.get("has_hist_freqs") else None)

    # -- frozen segments: CSR + packed streams, tiers preserved ----------
    # (freed_slices stays None: the slices were recycled at the original
    # rollover; only release-time bookkeeping consumed them.)
    frozen = []
    for i, fm in enumerate(meta["frozen"]):
        if kind == "sharded":
            S = int(meta["num_shards"])
            shards = []
            for s in range(S):
                pre = f"frozen/{i}/shard{s}"
                shards.append(seg_mod.FrozenSegment(
                    offsets=_leaf(arrays, pre + "/offsets"),
                    data=_leaf(arrays, pre + "/data"),
                    n_docs=fm["n_docs"] // S, doc_base=fm["doc_base"],
                    freed_slices=None, tier=fm["tier"]))
            frozen.append(shx.ShardedFrozenSegment(
                shards, n_docs=fm["n_docs"], doc_base=fm["doc_base"],
                tier=fm["tier"]))
        else:
            pre = f"frozen/{i}"
            frozen.append(seg_mod.FrozenSegment(
                offsets=_leaf(arrays, pre + "/offsets"),
                data=_leaf(arrays, pre + "/data"), n_docs=fm["n_docs"],
                doc_base=fm["doc_base"], freed_slices=None,
                tier=fm["tier"]))
    segs.frozen = frozen
    eng._sync_frozen()   # rebuild packed views, drop the qexec stack
    for k, v in meta["stats"].items():
        if hasattr(eng.stats, k):
            setattr(eng.stats, k, v)
    # a restored archive is exactly the state the validators were built
    # for: a tampered-but-checksummed archive must fail HERE, not at the
    # first wrong query result.
    if eng.validate:
        eng.validate_invariants()
    return eng


def restore(path: str, *, mesh=None, rules=None, **overrides):
    """Rebuild an engine from a snapshot archive.

    ``mesh``/``rules`` are required semantics only for sharded archives
    (``mesh=None`` builds a fresh ``make_doc_mesh(S)`` over the saved
    shard count).  ``overrides`` are constructor keyword overrides
    (e.g. ``use_kernel=False``, ``validate=True``, ``batched_kernel=``)
    for restoring onto a different backend than the snapshotting one.
    When the (possibly overridden) config has ``validate=True``, the
    structural validators run on the restored state before it is
    returned."""
    meta, arrays = read_archive(path)
    return _build_engine(meta, arrays, mesh=mesh, rules=rules,
                         **overrides)


# ---------------------------------------------------------------------------
# Ingest journal: append-only WAL of raw arrival batches
# ---------------------------------------------------------------------------
def _pack_record(seq: int, docs: np.ndarray) -> bytes:
    hdr = json.dumps({"seq": int(seq), "dtype": str(docs.dtype),
                      "shape": list(docs.shape)},
                     sort_keys=True).encode()
    body = _U32.pack(len(hdr)) + hdr + docs.tobytes()
    return _REC.pack(len(body), zlib.crc32(_LEN.pack(len(body))),
                     zlib.crc32(body)) + body


class IngestJournal:
    """Append-only host-side log of raw ingest batches.

    Contract (WAL-then-apply): ``journal.append(docs)`` BEFORE
    ``engine.ingest(docs)``; only an appended batch may be acknowledged
    upstream.  A crash mid-append leaves a torn final record, which
    :func:`read_journal` drops — that batch was never applied or acked.
    A crash between append and apply leaves a complete record the engine
    never saw — replay applies it.  Either way no acknowledged batch is
    lost and recovery is bit-identical.

    Opening an existing journal resumes it: the file is parsed, a torn
    final record's leftover bytes are TRUNCATED away, and appends
    continue from the next sequence number — so a resumed journal never
    interleaves new records behind torn bytes (which would swallow them
    on the next read).

    ``fsync=False`` (the default) flushes each append to the OS page
    cache: the batch survives a process crash, not an OS crash or power
    loss.  ``fsync=True`` adds an ``os.fsync`` per append for power-loss
    durability, at a per-batch cost (see ``journal_overhead_pct`` in
    benchmarks/bench_recovery.py).
    """

    def __init__(self, path: str, *, base_seq: int = 0,
                 fsync: bool = False):
        self.path = path
        self.fsync = bool(fsync)
        if os.path.exists(path) and os.path.getsize(path) > 0:
            base, records, end = _parse_journal(path)
            self.next_seq = base + len(records)
            # drop any torn tail BEFORE appending: new records written
            # after leftover torn bytes would be swallowed by the torn
            # frame's declared length on the next read.
            self._f = open(path, "rb+")
            self._f.truncate(end)
            self._f.seek(end)
        else:
            self.next_seq = int(base_seq)
            self._f = open(path, "wb")
            hdr = json.dumps({"format": FORMAT_VERSION,
                              "base_seq": int(base_seq)},
                             sort_keys=True).encode()
            self._f.write(JRNL_MAGIC)
            self._f.write(_HDR.pack(len(hdr), zlib.crc32(hdr)))
            self._f.write(hdr)
            self._flush()

    def _flush(self) -> None:
        self._f.flush()
        if self.fsync:
            os.fsync(self._f.fileno())

    def append(self, docs) -> int:
        """Append one raw arrival batch; returns its sequence number.
        The record is flushed before returning — once ``append`` comes
        back, the batch survives a process crash (and, with
        ``fsync=True``, an OS crash or power loss)."""
        docs = np.ascontiguousarray(np.asarray(docs))
        seq = self.next_seq
        with jax.profiler.TraceAnnotation("journal.append", seq=seq,
                                          bytes=docs.nbytes):
            self._f.write(_pack_record(seq, docs))
            self._flush()
        self.next_seq += 1
        return seq

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()

    def __enter__(self) -> "IngestJournal":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _parse_journal(path: str) -> Tuple[int, List[Tuple[int, np.ndarray]],
                                       int]:
    """Parse a journal into ``(base_seq, [(seq, docs), ...], end)``
    where ``end`` is the byte offset just past the last COMPLETE record
    (= where a resuming writer must truncate before appending)."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as exc:
        raise CorruptSnapshotError(f"cannot read journal {path}: {exc}")
    if len(blob) < len(JRNL_MAGIC) + _HDR.size:
        raise CorruptSnapshotError(
            f"{path}: {len(blob)} bytes is shorter than the journal "
            f"header")
    if blob[: len(JRNL_MAGIC)] != JRNL_MAGIC:
        raise CorruptSnapshotError(
            f"{path}: bad magic — not a repro ingest journal")
    hlen, hcrc = _HDR.unpack_from(blob, len(JRNL_MAGIC))
    hstart = len(JRNL_MAGIC) + _HDR.size
    hdr = blob[hstart: hstart + hlen]
    if len(hdr) != hlen or zlib.crc32(hdr) != hcrc:
        raise CorruptSnapshotError(f"{path}: journal header damaged")
    base_seq = int(json.loads(hdr)["base_seq"])

    records: List[Tuple[int, np.ndarray]] = []
    pos = hstart + hlen
    while pos < len(blob):
        if len(blob) - pos < _REC.size:
            break                      # torn tail: partial record frame
        body_len, len_crc, crc = _REC.unpack_from(blob, pos)
        # a crash truncates — it never leaves a complete frame header
        # with damaged bytes — so a bad length checksum is corruption
        # even at EOF; without this, a flipped mid-file length byte
        # would swallow every record after it as a fake torn tail.
        if zlib.crc32(blob[pos: pos + _LEN.size]) != len_crc:
            raise CorruptSnapshotError(
                f"{path}: record frame at byte {pos} has a damaged "
                f"length field — journal corruption, not a torn append")
        body = blob[pos + _REC.size: pos + _REC.size + body_len]
        at_eof = pos + _REC.size + body_len >= len(blob)
        if len(body) != body_len:
            break                      # torn tail: payload cut short
        if zlib.crc32(body) != crc:
            if at_eof:
                break                  # torn tail: crash mid-append
            raise CorruptSnapshotError(
                f"{path}: record at byte {pos} fails CRC with records "
                f"after it — journal corruption, not a torn append")
        rhlen, = _U32.unpack_from(body, 0)
        rhdr = json.loads(body[_U32.size: _U32.size + rhlen])
        raw = body[_U32.size + rhlen:]
        docs = np.frombuffer(raw, dtype=np.dtype(rhdr["dtype"]))
        want = int(np.prod(rhdr["shape"], dtype=np.int64))
        if docs.size != want:
            raise CorruptSnapshotError(
                f"{path}: record seq {rhdr['seq']} holds {docs.size} "
                f"elements, header shape {rhdr['shape']} wants {want}")
        seq = int(rhdr["seq"])
        if seq != base_seq + len(records):
            raise CorruptSnapshotError(
                f"{path}: record sequence jumps to {seq}, expected "
                f"{base_seq + len(records)} — missing or reordered "
                f"records")
        records.append((seq, docs.reshape(rhdr["shape"]).copy()))
        pos += _REC.size + body_len
    return base_seq, records, pos


def read_journal(path: str) -> Tuple[int, List[Tuple[int, np.ndarray]]]:
    """Parse a journal into ``(base_seq, [(seq, docs), ...])``.

    A torn FINAL record (bytes missing or a body CRC failing at EOF —
    the signature of a crash mid-append) is dropped silently.
    Everything else — bad magic/header, a damaged record length field,
    a CRC failure with records after it, a sequence gap or reorder —
    raises :class:`CorruptSnapshotError`: those are corruption or data
    loss, not a clean crash."""
    base_seq, records, _ = _parse_journal(path)
    return base_seq, records


# ---------------------------------------------------------------------------
# Recovery: restore + replay
# ---------------------------------------------------------------------------
def recover(snapshot_path: str, journal_path: Optional[str] = None, *,
            mesh=None, rules=None, expect_seq: Optional[int] = None,
            on_replay=None, **overrides):
    """Restore the snapshot, then replay journaled batches through the
    ordinary ingest path.  Returns the recovered engine.

    ``expect_seq`` is the durable watermark: the total number of batches
    acknowledged upstream (e.g. the ack log's length).  Pass it whenever
    one exists — a journal whose COMPLETE records were lost (deleted
    tail, restored-from-older-copy file) parses cleanly, and only this
    check can tell that apart from a clean shutdown.  If the snapshot +
    journal cover fewer than ``expect_seq`` batches,
    :class:`CorruptSnapshotError` is raised.

    ``on_replay(seq, docs, admitted)`` is called after each replayed
    batch (``admitted`` is the ingest's admission verdict) — the serving
    loop's hook for progress accounting while it is unavailable."""
    meta, arrays = read_archive(snapshot_path)
    eng = _build_engine(meta, arrays, mesh=mesh, rules=rules, **overrides)
    applied = int(meta["seq"])
    if journal_path is not None and os.path.exists(journal_path):
        base_seq, records = read_journal(journal_path)
        for seq, docs in records:
            if seq < applied:
                continue               # journal predates this snapshot
            if seq > applied:
                raise CorruptSnapshotError(
                    f"{journal_path}: first replayable record is seq "
                    f"{seq} but the snapshot was taken at seq {applied} "
                    f"— journal records between them are missing")
            ok = eng.ingest(docs)
            applied += 1
            if on_replay is not None:
                on_replay(seq, docs, ok)
    if expect_seq is not None and applied < int(expect_seq):
        raise CorruptSnapshotError(
            f"recovery covers only {applied} batches but the durable "
            f"watermark acknowledges {int(expect_seq)} — the journal "
            f"tail is missing")
    return eng


# ---------------------------------------------------------------------------
# Bit-identity fingerprint
# ---------------------------------------------------------------------------
def _crc(arr) -> int:
    return zlib.crc32(np.ascontiguousarray(np.asarray(arr)).tobytes())


def engine_fingerprint(engine) -> Dict[str, Any]:
    """CRC32 digest of everything the recovery contract promises to
    reproduce bit-for-bit: every active ``PoolState`` leaf, every frozen
    segment's CSR (per shard when sharded) with its docid range and
    tier, the lifecycle counters and stats.  Two engines with equal
    fingerprints answer every conjunctive/disjunctive/phrase/scored
    query identically (the query paths are pure functions of this
    state).  ``freed_slices`` is excluded — it is rollover-time release
    bookkeeping, consumed before any snapshot can observe it."""
    segs = engine.segments
    fp: Dict[str, Any] = {
        f"active/{name}": _crc(leaf)
        for name, leaf in zip(PoolState._fields, segs.active.state)}
    fp["next_docid"] = int(segs.active.next_docid)
    fp["doc_base"] = int(segs._doc_base)
    fp["n_rollovers"] = int(segs.n_rollovers)
    fp["n_compactions"] = int(segs.n_compactions)
    fp["hist_freqs"] = (None if segs._hist_freqs is None
                        else _crc(np.asarray(segs._hist_freqs, np.int64)))
    for i, fz in enumerate(segs.frozen):
        fp[f"frozen/{i}"] = (
            int(fz.doc_base), int(fz.n_docs),
            int(getattr(fz, "tier", 0)),
            tuple((_crc(m.offsets), _crc(m.data))
                  for m in _frozen_members(fz)))
    fp["n_frozen"] = len(segs.frozen)
    fp["stats"] = dataclasses.asdict(engine.stats)
    return fp


__all__ = ["CorruptSnapshotError", "IngestJournal", "engine_fingerprint",
           "read_archive", "read_journal", "recover", "restore",
           "snapshot", "write_archive"]
