"""Example-pipeline tests (mirrors example/max_test.go and the demo
programs)."""

import numpy as np
import pytest

import jax

import bigslice_tpu as bs
from bigslice_tpu import slicetest
from bigslice_tpu.exec.session import Session
import bigslice_tpu.models.kmeans as kmeans_mod
import bigslice_tpu.models.maxint as maxint
import bigslice_tpu.models.wordcount as wc_mod


def test_wordcount_ids_both_executors(sess):
    rng = np.random.RandomState(5)
    ids = rng.randint(0, 64, 8 * 300).astype(np.int32)
    got = dict(sess.run(wc_mod.wordcount_ids(8, ids, 64)).rows())
    oracle = dict(zip(*np.unique(ids, return_counts=True)))
    assert got == {int(k): int(v) for k, v in oracle.items()}


def test_int_max_random_vs_oracle():
    # Property-style check mirroring example/max_test.go's quick.Check.
    rng = np.random.RandomState(0)
    for trial in range(3):
        n = rng.randint(1, 2000)
        nshards = rng.randint(1, 8)
        keys = rng.randint(0, 50, n).astype(np.int32)
        vals = rng.randint(-1000, 1000, n).astype(np.int32)
        s = maxint.int_max(bs.Const(nshards, keys, vals))
        got = dict(slicetest.scan_all(s))
        oracle = {}
        for k, v in zip(keys.tolist(), vals.tolist()):
            oracle[k] = max(oracle.get(k, -10**9), v)
        assert got == oracle


def test_wordcount_file(tmp_path):
    p = tmp_path / "t.txt"
    p.write_text("a b a\nc a b\n")
    got = dict(slicetest.scan_all(wc_mod.wordcount(3, str(p))))
    assert got == {"a": 3, "b": 2, "c": 1}


def test_wordcount_ids_device():
    rng = np.random.RandomState(1)
    ids = rng.randint(0, 100, 5000).astype(np.int32)
    got = dict(slicetest.scan_all(wc_mod.wordcount_ids(4, ids, 100)))
    oracle = dict(zip(*np.unique(ids, return_counts=True)))
    assert got == {int(k): int(v) for k, v in oracle.items()}


def test_kmeans_step_single_device():
    rng = np.random.RandomState(2)
    pts = rng.rand(256, 8).astype(np.float32)
    cents = pts[:4].copy()
    out = np.asarray(jax.jit(kmeans_mod.kmeans_step)(pts, cents))
    # One manual step oracle.
    d2 = ((pts[:, None, :] - cents[None, :, :]) ** 2).sum(-1)
    assign = d2.argmin(1)
    for c in range(4):
        m = assign == c
        if m.any():
            np.testing.assert_allclose(out[c], pts[m].mean(0), rtol=1e-4)


def test_mesh_kmeans_step():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]), ("shards",))
    rng = np.random.RandomState(3)
    pts = rng.rand(8 * 32, 8).astype(np.float32)
    cents = pts[:4].copy()
    step = kmeans_mod.mesh_kmeans_step(mesh, k=4, d=8)
    pts_g = jax.device_put(pts, NamedSharding(mesh, P("shards")))
    out = np.asarray(step(pts_g, cents))
    single = np.asarray(jax.jit(kmeans_mod.kmeans_step)(pts, cents))
    np.testing.assert_allclose(out, single, rtol=1e-4)


def test_kmeans_slice_api_converges():
    rng = np.random.RandomState(4)
    # Three well-separated blobs.
    blobs = [rng.randn(50, 4).astype(np.float32) + 10 * i
             for i in range(3)]
    pts = np.concatenate(blobs)
    rng.shuffle(pts)
    sess = Session()
    cents = kmeans_mod.kmeans(sess, pts, k=3, iters=5, num_shards=3)
    centers = sorted(round(float(c[0]) / 10) for c in cents)
    assert centers == [0, 1, 2]


def test_urls_domain_count(tmp_path):
    import bigslice_tpu.models.urls as urls_mod

    p = tmp_path / "urls.txt"
    p.write_text(
        "http://a.com/x\nhttps://b.org/y\nhttp://A.com/z\n"
        "https://b.org/\nhttp://c.net\n"
    )
    got = dict(slicetest.scan_all(urls_mod.domain_count(3, str(p))))
    assert got == {"a.com": 2, "b.org": 2, "c.net": 1}


def test_domains_batch_matches_scalar():
    import bigslice_tpu.models.urls as urls_mod

    cases = [
        "http://A.com/x/y", "https://b.org/", "c.net", "c.net/",
        "HTTP://UPPER.COM", "ftp://f.io/a//b", "//bare.host/p",
        "no-scheme/with/path", "", "http://", "a//b/c",
    ]
    got = urls_mod._domains_batch(cases).tolist()
    want = [urls_mod._domain(u) for u in cases]
    assert got == want
    assert urls_mod._domains_batch([]).tolist() == []


def test_strparse_domains_codes_matches_scalar():
    """The vectorized byte-level parse (frame/strparse.py) must be
    bit-equal to _domain on every shape: schemes, missing schemes,
    multiple '//', case, unicode (fallback rows), embedded newlines
    (whole-batch fallback), and randomized fuzz."""
    import random

    from bigslice_tpu.frame import dictenc, strparse
    import bigslice_tpu.models.urls as urls_mod

    cases = [
        "http://A.com/x/y", "https://b.org/", "c.net", "c.net/",
        "HTTP://UPPER.COM", "ftp://f.io/a//b", "//bare.host/p",
        "no-scheme/with/path", "", "http://", "a//b/c", "/", "//",
        "///", "x//", "a//host", "Ünïcode://CASÉ/p", "ÅÄÖ",
        "http://ÅÄÖ.se/path", "a//bß/c", "we\nird//x/y",
    ]
    vocab = dictenc.GlobalVocab()
    got = list(vocab.decode(strparse.domains_codes(cases, vocab)))
    want = [urls_mod._domain(u) for u in cases]
    assert got == want
    rng = random.Random(7)
    alpha = "aB/:.xÅé \t"
    fuzz = ["".join(rng.choice(alpha) for _ in range(rng.randint(0, 12)))
            for _ in range(2000)]
    v2 = dictenc.GlobalVocab()
    got = list(v2.decode(strparse.domains_codes(fuzz, v2)))
    assert got == [urls_mod._domain(u) for u in fuzz]
    assert strparse.domains_codes([], dictenc.GlobalVocab()).tolist() == []


def test_strparse_pool_path_matches(monkeypatch):
    """The proc-pool chunked parse agrees with the single-process path
    (forced 2 workers, small chunks)."""
    from bigslice_tpu.frame import dictenc, strparse
    import bigslice_tpu.models.urls as urls_mod

    monkeypatch.setenv("BIGSLICE_PARSE_PROCS", "2")
    strparse.shutdown_pool()
    lines = [f"http://S{i % 97}.example.com/p{i}" for i in range(4096)]
    lines[17] = "Ünïcode://CASÉ/p"  # non-ascii fixup inside a chunk
    vocab = dictenc.GlobalVocab()
    codes = strparse.domains_codes(lines, vocab, chunk_rows=1024)
    assert list(vocab.decode(codes)) == [
        urls_mod._domain(u) for u in lines
    ]
    strparse.shutdown_pool()


def test_scanreader_sequence_source_matches_generator():
    """Sequence sources stripe by random access; the shard contents
    must equal the generator striping exactly."""
    import bigslice_tpu as bs

    lines = [f"line{i}" for i in range(101)]
    s_gen = bs.ScanReader(3, lambda: iter(lines))
    s_seq = bs.ScanReader(3, lines)
    for shard in range(3):
        rows_g = [r for f in s_gen.reader(shard, ())
                  for r in f.cols[0]]
        rows_s = [r for f in s_seq.reader(shard, ())
                  for r in f.cols[0]]
        assert rows_g == rows_s == lines[shard::3]


def test_urls_domain_count_encoded(tmp_path):
    import bigslice_tpu.models.urls as urls_mod

    p = tmp_path / "urls.txt"
    lines = [f"http://site{i % 7}.com/page{i}" for i in range(200)]
    p.write_text("\n".join(lines) + "\n")
    sess = Session()
    rows = urls_mod.domain_count_encoded(sess, 4, str(p))
    got = dict(rows)
    expect = {}
    for i in range(200):
        d = f"site{i % 7}.com"
        expect[d] = expect.get(d, 0) + 1
    assert got == expect


def test_kmeans_slice_api_on_mesh():
    import jax
    from jax.sharding import Mesh

    from bigslice_tpu.exec.meshexec import MeshExecutor

    rng = np.random.RandomState(6)
    blobs = [rng.randn(40, 4).astype(np.float32) + 12 * i
             for i in range(2)]
    pts = np.concatenate(blobs)
    rng.shuffle(pts)
    mesh = Mesh(np.array(jax.devices()[:8]), ("shards",))
    sess = Session(executor=MeshExecutor(mesh))
    cents = kmeans_mod.kmeans(sess, pts, k=2, iters=4, num_shards=8)
    centers = sorted(round(float(c[0]) / 12) for c in cents)
    assert centers == [0, 1]


def test_kmeans_rounds_free_what_each_round_computed():
    """Every round's assignment group output is a second copy of the
    points on the device; rounds must not pile them up (at config-5
    size three rounds would not fit a 16 GB chip). Only the uploaded
    points stay resident, and they are still there for the next
    round."""
    import itertools

    from jax.sharding import Mesh

    from bigslice_tpu.exec.meshexec import MeshExecutor

    rng = np.random.RandomState(6)
    pts = np.concatenate([rng.randn(40, 4).astype(np.float32) + 12 * i
                          for i in range(2)])
    mesh = Mesh(np.array(jax.devices()[:2]), ("shards",))
    ex = MeshExecutor(mesh)
    rounds = kmeans_mod.kmeans_rounds(Session(executor=ex), pts, k=2,
                                      num_shards=2)
    resident = []
    for cents, counts in itertools.islice(rounds, 3):
        assert counts.sum() == len(pts)
        resident.append(ex.device_group_count())
    assert resident == [1, 1, 1]
    assert sorted(round(float(c[0]) / 12) for c in cents) == [0, 1]


def test_result_discard_graph_keeps_the_named_results(sess):
    """discard_graph drops the whole subgraph behind a result except
    what the kept Results stand on; a kept Result stays readable and
    reusable, and plain discard() still drops the roots only."""
    keys = np.arange(64, dtype=np.int32) % 5
    base = sess.run(bs.Const(4, keys, np.ones(64, np.int32)))
    want = {int(k): int((keys == k).sum()) for k in range(5)}

    def add(a, b):
        return a + b

    def run():
        doubled = bs.Map(base, lambda k, v: (k, v * 2))
        return sess.run(bs.Reduce(doubled, add))

    res = run()
    assert dict(res.rows()) == {k: 2 * v for k, v in want.items()}
    discarded = []
    real = sess.executor.discard
    sess.executor.discard = lambda t: (discarded.append(t.name.op),
                                       real(t))[1]
    res.discard_graph(keep=[base])
    # More than the roots went, and nothing base stands on.
    assert len(discarded) > len(res.tasks)
    assert not any(op.startswith("const") for op in discarded)
    # base survived: a second round over it gives the same answer.
    assert dict(run().rows()) == {k: 2 * v for k, v in want.items()}
    assert sum(v for _, v in base.rows()) == 64


def test_result_discard_inputs_keeps_its_own_output_only(sess):
    """discard_inputs frees every task behind a result and none of its
    own: the result reads without recomputing anything, and feeds a
    later run."""
    keys = np.arange(96, dtype=np.int32) % 7
    want = {int(k): int((keys == k).sum()) for k in range(7)}

    def add(a, b):
        return a + b

    doubled = bs.Map(bs.Const(4, keys, np.ones(96, np.int32)),
                     lambda k, v: (k, v * 2))
    res = sess.run(bs.Reduce(doubled, add))
    discarded, submitted = [], []
    ex = sess.executor
    real_discard, real_submit = ex.discard, ex.submit
    ex.discard = lambda t: (discarded.append(t), real_discard(t))[1]
    res.discard_inputs()
    mine = {id(t) for t in res.tasks}
    behind = {id(p) for t in res.tasks for d in t.deps for p in d.tasks}
    assert discarded and behind <= {id(t) for t in discarded}
    assert not mine & {id(t) for t in discarded}
    # Its own output is still stored: a read submits no task.
    ex.submit = lambda t: (submitted.append(t), real_submit(t))[1]
    assert dict(res.rows()) == {k: 2 * v for k, v in want.items()}
    assert not submitted
    ex.discard, ex.submit = real_discard, real_submit
    again = sess.run(bs.Map(res, lambda k, v: (k, v + 1)))
    assert dict(again.rows()) == {k: 2 * v + 1 for k, v in want.items()}
