"""SPMD correctness: the sharded train step on an 8-device (2x4) mesh
must produce the same loss/gradients as the single-device step.

This is the strongest CPU-side check that the sharding rules (DP batch,
TP heads/ffn/vocab, grouped MoE dispatch) don't change semantics.
Subprocess keeps the 8 forced host devices away from other tests.
"""
import json
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    from repro.dist import collectives as C
    C.force_host_device_count(8)
    import json
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import registry
    from repro.dist.sharding import default_rules, use_rules, tree_shardings
    from repro.models import transformer as T
    from repro.train import steps as S
    from repro.train.optimizer import AdamW

    out = {}
    for arch in ("tinyllama-1.1b", "qwen2-moe-a2.7b"):
        cfg = registry.reduced_config(arch)
        entry = registry.get(arch)
        opt = AdamW(lr=1e-3, clip_norm=None, weight_decay=0.0)
        step = S.make_lm_train_step(cfg, opt, n_microbatches=2, q_chunk=8)

        key = jax.random.key(0)
        params = T.init_lm(cfg, key)
        opt_state = opt.init(params)
        toks = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab)

        # single device
        p1, o1, m1 = jax.jit(step)(params, opt_state, toks)

        # 2x4 mesh, full rules
        mesh = C.host_mesh((2, 4), ("data", "model"))
        rules = default_rules(mesh, fsdp=True)
        with mesh, use_rules(rules):
            p_sh = tree_shardings(rules, T.lm_param_specs(cfg))
            o_sh = type(opt_state)(
                step=jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
                mu=p_sh, nu=p_sh)
            b_sh = rules.sharding(("batch", None))
            p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))(
                params, opt_state, toks)

        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        # compare a few updated parameters elementwise
        w1 = np.asarray(jax.tree.leaves(p1)[0], np.float32)
        w2 = np.asarray(jax.tree.leaves(p2)[0], np.float32)
        out[arch] = {"dloss": dl,
                     "dparam": float(np.max(np.abs(w1 - w2))),
                     "loss": float(m1["loss"])}

    # logical-axis collectives: psum/all_gather through the rules table
    # must equal the plain jnp reductions.
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = C.host_mesh((2, 4), ("data", "model"))
    rules = default_rules(mesh, fsdp=True)
    x = jnp.arange(32.0).reshape(8, 4)
    with use_rules(rules):
        assert C.axis_size("batch") == 2 and C.axis_size("model") == 4

        def body(xs):
            return C.psum(jnp.sum(xs), "batch"), C.all_gather(xs, "batch")

        tot, gathered = shard_map(
            body, mesh=mesh, in_specs=P("data", None),
            out_specs=(P(), P()), check_vma=False)(x)
        assert abs(float(tot) - float(jnp.sum(x))) < 1e-6
        np.testing.assert_array_equal(np.asarray(gathered), np.asarray(x))
        # unmapped logical name -> exact no-op
        assert C.psum(jnp.float32(3.0), "no_such_axis") == 3.0
    print(json.dumps(out))
""")


def _run_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    res = _run_subprocess(SCRIPT)
    for arch, r in res.items():
        assert r["loss"] > 0
        assert r["dloss"] < 1e-6, (arch, r)
        assert r["dparam"] < 1e-6, (arch, r)


# ---------------------------------------------------------------------------
# Sharded index engine == single-device engine (bit-identical results)
# ---------------------------------------------------------------------------
SCRIPT_INDEX = textwrap.dedent("""
    from repro.dist import collectives as C
    C.force_host_device_count(4)
    import json
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core import analytical, query
    from repro.core.index import ActiveSegment
    from repro.core.pointers import PoolLayout
    from repro.core.sharded_index import (ShardedActiveSegment,
                                          make_doc_mesh, make_sharded_engine)
    from repro.data import synth

    Z = (1, 4, 7, 11)
    layout = PoolLayout(z=Z, slices_per_pool=(4096, 2048, 1024, 512))
    spec = synth.CorpusSpec(vocab=2000, n_docs=500, seed=0)
    docs = synth.zipf_corpus(spec)
    freqs = synth.term_freqs(docs, spec.vocab)
    fmax = int(freqs.max())
    max_slices = int(analytical.slices_needed(Z, fmax)) + 1

    # single-device reference engine (jnp searchsorted intersect path)
    ref = ActiveSegment(layout, spec.vocab)
    ref.ingest(jnp.asarray(docs)); ref.check_health()
    eng1 = query.make_engine(layout, max_slices, max_len=1024)

    # 4-shard SPMD engine (Pallas intersect kernel per shard in shard_map)
    mesh, rules = make_doc_mesh(4)
    seg = ShardedActiveSegment(layout, spec.vocab, mesh, rules=rules)
    for i in range(0, 500, 100):            # streaming arrival batches
        seg.ingest(jnp.asarray(docs[i:i + 100]))
    seg.check_health()
    eng4 = make_sharded_engine(layout, mesh, max_slices, max_len=1024,
                               rules=rules, use_kernel=True)
    assert np.array_equal(seg.term_freqs(), freqs)

    top = np.argsort(-freqs)
    rows = [[int(top[a]), int(top[b])] + [0] * 6
            for a, b in [(0, 1), (2, 5), (1, 20), (10, 50)]]
    rows.append([int(top[0]), int(top[1]), int(top[2])] + [0] * 5)
    terms = jnp.asarray(np.asarray(rows, np.uint32))
    n_terms = jnp.asarray([2, 2, 2, 2, 3], jnp.int32)

    out = {"n_queries": 0}
    def check(kind, batch_fn, single_fn, *args1):
        d4, n4 = batch_fn(*args1)
        for i in range(d4.shape[0]):
            d1, n1 = single_fn(i)
            a = np.asarray(d1)[: int(n1)].tolist()
            b = np.asarray(d4[i])[: int(n4[i])].tolist()
            assert a == b, (kind, i, a[:8], b[:8])
            assert len(set(b)) == len(b), (kind, i, "duplicates")
            out["n_queries"] += 1

    check("conj", eng4.conjunctive,
          lambda i: eng1.conjunctive(ref.state, terms[i], n_terms[i]),
          seg.state, terms, n_terms)
    check("disj", eng4.disjunctive,
          lambda i: eng1.disjunctive(ref.state, terms[i], n_terms[i]),
          seg.state, terms, n_terms)
    t1 = jnp.asarray([int(top[0]), int(top[2]), int(top[1])], jnp.uint32)
    t2 = jnp.asarray([int(top[1]), int(top[3]), int(top[0])], jnp.uint32)
    check("phrase", eng4.phrase,
          lambda i: eng1.phrase(ref.state, t1[i], t2[i]),
          seg.state, t1, t2)

    # top-k path: newest k across shards
    dk, nk = eng4.topk_conjunctive(seg.state, terms, n_terms, 5)
    d1, n1 = eng1.topk_conjunctive(ref.state, terms[0], n_terms[0], 5)
    assert (np.asarray(dk[0])[: int(nk[0])].tolist()
            == np.asarray(d1)[: int(n1)].tolist())
    print(json.dumps(out))
""")


def test_sharded_index_engine_matches_single_device():
    """Conjunctive, disjunctive and phrase results from the 4-shard
    engine (Pallas intersect per shard + all_gather + top-k merge) must
    be bit-identical, docid-descending and duplicate-free vs the
    single-device engine."""
    res = _run_subprocess(SCRIPT_INDEX)
    assert res["n_queries"] == 13


# ---------------------------------------------------------------------------
# Lifecycle equivalence: K rollovers + reclamation == never-frozen index
# ---------------------------------------------------------------------------
SCRIPT_LIFECYCLE = textwrap.dedent("""
    from repro.dist import collectives as C
    C.force_host_device_count(4)
    import json
    import numpy as np
    import jax.numpy as jnp

    from repro.core import analytical, query
    from repro.core.index import ActiveSegment
    from repro.core.lifecycle import (LifecycleEngine,
                                      ShardedLifecycleEngine)
    from repro.core.pointers import PoolLayout
    from repro.core.sharded_index import make_doc_mesh
    from repro.data import synth

    Z = (1, 4, 7, 11)
    layout = PoolLayout(z=Z, slices_per_pool=(4096, 2048, 512, 64))
    spec = synth.CorpusSpec(vocab=800, n_docs=720, seed=17)
    docs = synth.zipf_corpus(spec)
    freqs = synth.term_freqs(docs, spec.vocab)
    fmax = int(freqs.max())
    max_slices = int(analytical.slices_needed(Z, fmax)) + 1
    max_len = 1 << (fmax - 1).bit_length()

    # never-frozen reference: one giant active segment, same stream
    ref = ActiveSegment(layout, spec.vocab)
    ref.ingest(jnp.asarray(docs)); ref.check_health()
    eng = query.make_engine(layout, max_slices, max_len=max_len)

    # K=3 rollovers + a half-full active segment, both deployments.
    # 200-docs segments over a 720-doc stream -> frozen at 200/400/600.
    mesh, rules = make_doc_mesh(4)
    lives = {
        "single": LifecycleEngine(layout, spec.vocab, 200,
                                  max_slices=max_slices, max_len=max_len),
        "sharded": ShardedLifecycleEngine(layout, spec.vocab, 200, mesh,
                                          max_slices=max_slices,
                                          max_len=max_len, rules=rules),
    }
    for name, life in lives.items():
        for i in range(0, 720, 40):
            life.ingest(docs[i:i + 40])
        life.check_health()
        assert life.stats.rollovers == 3, (name, life.stats)
        assert life.doc_base == 600, name
        # reclamation bound: the rolled-over engine's heap high-water is
        # one segment's demand -- strictly below the never-frozen index.
        assert (life.memory_high_water_slots()
                < ref.memory_slots_used()), name

    top = np.argsort(-freqs)
    pairs = [(0, 1), (2, 5), (1, 20), (10, 50)]
    out = {"n_queries": 0}

    def expect(kind, ts):
        pad = np.zeros(8, np.uint32); pad[: len(ts)] = ts
        if kind == "phrase":
            d, n = eng.phrase(ref.state, jnp.uint32(ts[0]),
                              jnp.uint32(ts[1]))
        else:
            fn = getattr(eng, kind)
            d, n = fn(ref.state, jnp.asarray(pad), jnp.int32(len(ts)))
        return np.asarray(d)[: int(n)].astype(np.int64).tolist()

    for name, life in lives.items():
        for a, b in pairs:
            ts = [int(top[a]), int(top[b])]
            for kind in ("conjunctive", "disjunctive"):
                got = getattr(life, kind)(ts).tolist()
                want = expect(kind, ts)
                assert got == want, (name, kind, ts, got[:8], want[:8])
                out["n_queries"] += 1
        ts3 = [int(top[0]), int(top[1]), int(top[2])]
        assert life.conjunctive(ts3).tolist() == expect("conjunctive", ts3)
        out["n_queries"] += 1
        for a, b in [(0, 1), (2, 3), (1, 0)]:
            t1, t2 = int(top[a]), int(top[b])
            got = life.phrase(t1, t2).tolist()
            assert got == expect("phrase", [t1, t2]), (name, t1, t2)
            out["n_queries"] += 1
    print(json.dumps(out))
""")


def test_lifecycle_rollover_matches_never_frozen():
    """An index driven through 3 lifecycle rollovers (freeze -> slice
    reclamation -> recycled active segment) must return bit-identical
    conjunctive/disjunctive/phrase results to a never-frozen index fed
    the same stream — single-device AND 4-shard — while its heap
    high-water mark stays below the never-frozen index's footprint."""
    res = _run_subprocess(SCRIPT_LIFECYCLE)
    assert res["n_queries"] == 24


# ---------------------------------------------------------------------------
# Bulk-vs-scan ingest equivalence through the full lifecycle
# ---------------------------------------------------------------------------
SCRIPT_BULK = textwrap.dedent("""
    from repro.dist import collectives as C
    C.force_host_device_count(4)
    import json
    import numpy as np
    import jax.numpy as jnp

    from repro.core import analytical
    from repro.core.lifecycle import (LifecycleEngine,
                                      ShardedLifecycleEngine)
    from repro.core.pointers import PoolLayout
    from repro.core.sharded_index import make_doc_mesh
    from repro.data import synth

    Z = (1, 4, 7, 11)
    layout = PoolLayout(z=Z, slices_per_pool=(4096, 2048, 512, 64))
    spec = synth.CorpusSpec(vocab=600, n_docs=500, seed=29)
    docs = synth.zipf_corpus(spec)
    freqs = synth.term_freqs(docs, spec.vocab)
    fmax = int(freqs.max())
    max_slices = int(analytical.slices_needed(Z, fmax)) + 1
    max_len = 1 << (fmax - 1).bit_length()

    # 200-doc segments over a 500-doc stream -> rollovers at 200 and 400
    # (>= 2), with the second one recycling the first's freed slices.
    mesh, rules = make_doc_mesh(4)
    def build(bulk):
        return {
            "single": LifecycleEngine(
                layout, spec.vocab, 200, max_slices=max_slices,
                max_len=max_len, bulk_ingest=bulk),
            "sharded": ShardedLifecycleEngine(
                layout, spec.vocab, 200, mesh, max_slices=max_slices,
                max_len=max_len, rules=rules, bulk_ingest=bulk),
        }
    bulks, scans = build(True), build(False)

    out = {"n_states": 0, "n_queries": 0}
    for i in range(0, 500, 20):
        batch = docs[i:i + 20]
        for name in bulks:
            bulks[name].ingest(batch)
            scans[name].ingest(batch)
    top = np.argsort(-freqs)
    for name in bulks:
        b, s = bulks[name], scans[name]
        assert b.stats.rollovers == 2, (name, b.stats)
        assert s.stats.rollovers == 2, (name, s.stats)
        # the ACTIVE PoolState must be bit-identical leaf for leaf
        for leaf, x, y in zip(b.segments.active.state._fields,
                              b.segments.active.state,
                              s.segments.active.state):
            assert np.array_equal(np.asarray(x), np.asarray(y)), \\
                (name, leaf)
            out["n_states"] += 1
        # every frozen segment's CSR store must match exactly
        for fb, fs in zip(b.segments.frozen, s.segments.frozen):
            if hasattr(fb, "shards"):
                pairs = list(zip(fb.shards, fs.shards))
            else:
                pairs = [(fb, fs)]
            for xb, xs in pairs:
                assert np.array_equal(xb.offsets, xs.offsets), name
                assert np.array_equal(xb.data, xs.data), name
        # and unified queries agree bit for bit
        for a_i, b_i in [(0, 1), (2, 5), (1, 20)]:
            ts = [int(top[a_i]), int(top[b_i])]
            for kind in ("conjunctive", "disjunctive"):
                got = getattr(b, kind)(ts).tolist()
                want = getattr(s, kind)(ts).tolist()
                assert got == want, (name, kind, ts)
                out["n_queries"] += 1
        assert (b.phrase(int(top[0]), int(top[1])).tolist()
                == s.phrase(int(top[0]), int(top[1])).tolist()), name
        out["n_queries"] += 1
    print(json.dumps(out))
""")


def test_bulk_ingest_lifecycle_matches_scan():
    """Lifecycle engines (single-device AND 4-shard) fed the same stream
    through >= 2 rollovers must be bit-identical whether built by the
    batch-parallel bulk allocator or the per-posting scan oracle: every
    active PoolState leaf, every frozen CSR segment, and every unified
    query result."""
    res = _run_subprocess(SCRIPT_BULK)
    assert res["n_states"] == 14  # 7 leaves x 2 deployments
    assert res["n_queries"] == 14
