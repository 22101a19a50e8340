"""chip_smoke.py's phases at a tiny size on the test's own devices, so the
smoke cannot rot between chip runs: the served path end to end, the
numpy reference it is judged by, and the result line the last stdout
line must carry.  ``main()`` itself refuses anything but a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(docs_per_segment=1024, batch_docs=128, vocab=1 << 12, seed=0)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


def test_run_smoke_matches_numpy_reference(tmp_path):
    out = smoke.run_smoke(devices=jax.devices(), chips=1, workdir=tmp_path,
                          log=lambda *_: None, **TINY)
    seg_batches = TINY["docs_per_segment"] // TINY["batch_docs"]
    # one segment, then half of the next one
    assert out["batches"] == seg_batches + seg_batches // 2
    assert out["docs"] == out["batches"] * TINY["batch_docs"]
    # every query of every kind
    assert out["answers_checked"] == len(
        smoke.query_set(_stream(TINY["vocab"])))
    assert out["postings"] > 0


def test_run_smoke_fails_on_a_wrong_answer(tmp_path, monkeypatch):
    """A reference that disagrees with the engine must fail the run."""
    real = smoke.NumpyIndex.disjunctive
    monkeypatch.setattr(smoke.NumpyIndex, "disjunctive",
                        lambda self, terms: real(self, terms)[1:])
    with pytest.raises(smoke.SmokeFailure, match="docids differ"):
        smoke.run_smoke(devices=jax.devices(), chips=1, workdir=tmp_path,
                        log=lambda *_: None, **TINY)


def _stream(vocab):
    from repro.data.synth import CorpusSpec, TweetStream
    return TweetStream(CorpusSpec(vocab=vocab, mean_len=smoke.MEAN_LEN,
                                  seed=0), width=smoke.DOC_WIDTH)


def test_numpy_index_matches_brute_force():
    """The reference itself, against per-document loops."""
    stream = _stream(64)
    docs = np.concatenate([stream.batch(i, 50) for i in range(3)])
    terms = [int(t) for t in stream.rank_to_term[:6]]
    ref = smoke.NumpyIndex(terms)
    for i in range(3):
        ref.add(docs[i * 50:(i + 1) * 50])
    ref.finish()
    rows = [[int(t) for t in d if t >= 0] for d in docs]

    def has(t):
        return {i for i, r in enumerate(rows) if t in r}

    a, b, c = terms[:3]
    desc = sorted(has(a) & has(b), reverse=True)
    assert ref.conjunctive((a, b)).tolist() == desc
    assert ref.disjunctive((a, b, c)).tolist() == sorted(
        has(a) | has(b) | has(c), reverse=True)
    assert ref.phrase(a, b).tolist() == sorted(
        {i for i, r in enumerate(rows)
         if any(r[j] == a and r[j + 1] == b for j in range(len(r) - 1))},
        reverse=True)
    ids, scores = ref.scored((a, b), 5)
    want = sorted(((min(rows[i].count(a), 255) + min(rows[i].count(b), 255),
                    i) for i in desc), reverse=True)[:5]
    assert list(zip(scores.tolist(), ids.tolist())) == want


def test_result_line_format():
    line = smoke.result_line(jax.devices(), 1)
    d = jax.devices()[0]
    assert json.loads(line) == {"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": 1}}
    assert "\n" not in line


def test_main_refuses_a_cpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


FOUR = textwrap.dedent("""
    import importlib.util, json, sys
    from repro.dist import collectives
    collectives.force_host_device_count(4)
    import jax
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    out = smoke.run_smoke(devices=jax.devices(), chips=4, workdir=sys.argv[2],
                          log=lambda *_: None, **json.loads(sys.argv[3]))
    # on a TPU mesh the replicated frozen merge leaves the kernel route
    jax.default_backend = lambda: "tpu"
    assert not smoke.build_engine(shards=4, docs_per_segment=1024,
                                  vocab=1 << 12, seed=0)._batched_kernel
    print(json.dumps(out["answers_checked"]))
    print(smoke.result_line(jax.devices(), 4))
""")


def test_run_smoke_four_devices(tmp_path):
    """``--chips 4``: the document-sharded engine on a 4-device mesh,
    same stream, queries and reference (forced host devices)."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", FOUR, str(ROOT / "chip_smoke.py"),
         str(tmp_path), json.dumps(TINY)],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    lines = res.stdout.strip().splitlines()
    assert int(lines[-2]) > 0
    assert json.loads(lines[-1])["device"]["count"] == 4


def test_compile_cache_placement(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise a fixed, git-ignored directory inside the checkout."""
    from repro import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV)
        got = compile_cache.enable()
        assert got == str(ROOT / ".jax_cache") == compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == got
        ignored = (ROOT / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
