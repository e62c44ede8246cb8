"""The batched readback (shuffle.unshard_many): many group outputs'
valid prefixes in two overlapped device→host reads — counts, then
prefixes a batch — against a plain numpy reference and against
per-output ``unshard_columns``, on CPU meshes of 1, 2 and 8 devices,
through both the host-side slicing branch (what a CPU backend takes)
and the on-device prefix slice (what a TPU takes)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from bigslice_tpu.parallel import shuffle as shuffle_mod
from bigslice_tpu.parallel.jitutil import bucket_size


def make_outputs(nmesh, seed=0):
    """Three outputs of different capacities on a mesh of ``nmesh``
    devices, each with an int32, a float32 and a float32[3] column and
    counts that include 0 and ``capacity``: (outputs, reference), the
    reference being ``[output][column][shard]`` valid rows in numpy."""
    mesh = Mesh(np.array(jax.devices()[:nmesh]), ("shards",))
    rng = np.random.default_rng(seed)
    outputs, reference = [], []
    for o, cap in enumerate((16, 64, 8)):
        counts = rng.integers(1, cap, nmesh)
        counts[o % nmesh] = 0
        counts[(o + 1) % nmesh] = cap    # one device: the full shard
        chunks = [
            [rng.integers(0, 1000, k).astype(np.int32) for k in counts],
            [rng.random(k).astype(np.float32) for k in counts],
            [rng.random((k, 3)).astype(np.float32) for k in counts],
        ]
        cols, dev_counts = shuffle_mod.shard_columns(
            mesh, chunks, counts, cap)
        outputs.append((cols, dev_counts, cap))
        reference.append(chunks)
    return outputs, reference


def assert_chunks_equal(got, want):
    assert len(got) == len(want)
    for gcols, wcols in zip(got, want):
        assert len(gcols) == len(wcols)
        for gc, wc in zip(gcols, wcols):
            assert len(gc) == len(wc)
            for g, w in zip(gc, wc):
                assert isinstance(g, np.ndarray)
                assert g.dtype == w.dtype and g.shape == w.shape
                np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("bound", [None, 64], ids=["onebatch", "tiny"])
@pytest.mark.parametrize("on_device", [False, True],
                         ids=["hostslice", "deviceslice"])
@pytest.mark.parametrize("nmesh", [1, 2, 8])
def test_unshard_many_equals_reference_and_per_output(
        monkeypatch, nmesh, on_device, bound):
    monkeypatch.setattr(shuffle_mod, "_slices_on_device",
                        lambda: on_device)
    if bound is not None:
        monkeypatch.setattr(shuffle_mod, "READBACK_BATCH_BYTES", bound)
    outputs, reference = make_outputs(nmesh)
    crossed = [[] for _ in outputs]
    got = shuffle_mod.unshard_many(outputs, crossed)
    assert_chunks_equal(got, reference)
    # One output at a time: the same arrays, the same bytes crossing.
    total = 0
    for out, chunks, moved in zip(outputs, got, crossed):
        single = []
        assert_chunks_equal(
            [shuffle_mod.unshard_columns(*out, crossed=single)], [chunks])
        assert sorted(single) == sorted(moved)
        total += sum(single)
    assert sum(map(sum, crossed)) == total
    # What crossed: the bucketed prefix of every non-empty shard on the
    # device-slice branch, the whole shard on the host-side one.
    for (cols, counts, cap), moved in zip(outputs, crossed):
        want = []
        for c in cols:
            row = c.dtype.itemsize * int(np.prod(c.shape[1:]))
            want += [row * (min(cap, bucket_size(int(k))) if on_device
                            else cap)
                     for k in np.asarray(counts) if k]
        assert sorted(moved) == sorted(want)


@pytest.mark.parametrize("bound", [64 << 20, 1],
                         ids=["onebatch", "one_shard_a_batch"])
def test_reads_are_counts_once_then_a_batch_at_a_time(monkeypatch, bound):
    """Two ``device_get`` calls for a small result — every count, then
    every prefix — and, with the byte bound below any one shard, one a
    non-empty (output, shard), its columns together, in output order."""
    monkeypatch.setattr(shuffle_mod, "_slices_on_device", lambda: True)
    monkeypatch.setattr(shuffle_mod, "READBACK_BATCH_BYTES", bound)
    outputs, reference = make_outputs(4)
    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(len(x))
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    crossed = [[] for _ in outputs]
    got = shuffle_mod.unshard_many(outputs, crossed)
    assert_chunks_equal(got, reference)
    ncols = 3
    nonempty = sum(int(np.count_nonzero(np.asarray(counts)))
                   for _, counts, _ in outputs)
    assert sum(map(len, crossed)) == ncols * nonempty
    assert calls[0] == len(outputs)       # all the counts, first
    if bound == 1:
        assert calls[1:] == [ncols] * nonempty
    else:
        assert calls[1:] == [ncols * nonempty]


def test_prefix_programs_are_keyed_by_bucket_and_shape_alone(monkeypatch):
    """Reading more outputs, or outputs with other counts in the same
    buckets, compiles no new ``bs_prefix`` program."""
    monkeypatch.setattr(shuffle_mod, "_slices_on_device", lambda: True)
    program = shuffle_mod._prefix_program()
    outputs, _ = make_outputs(8, seed=1)
    shuffle_mod.unshard_many(outputs[:1])
    shuffle_mod.unshard_many(outputs)
    seen = program._cache_size()
    more, reference = make_outputs(8, seed=1)
    assert_chunks_equal(shuffle_mod.unshard_many(more + outputs),
                        reference * 2)
    assert program._cache_size() == seen > 0


def test_all_empty_outputs_read_counts_only(monkeypatch):
    mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))
    empty = [np.empty(0, np.int32)] * 4
    vec = [np.empty((0, 5), np.float32)] * 4
    outputs = [shuffle_mod.shard_columns(mesh, [empty, vec], [0] * 4, 8)
               + (8,) for _ in range(3)]
    calls = []
    real = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: calls.append(len(x)) or real(x))
    crossed = [[] for _ in outputs]
    got = shuffle_mod.unshard_many(outputs, crossed)
    assert calls == [3] and crossed == [[], [], []]
    for cols in got:
        assert [c.shape for c in cols[0]] == [(0,)] * 4
        assert [c.shape for c in cols[1]] == [(0, 5)] * 4
        assert cols[0][0].dtype == np.int32
        assert cols[1][0].dtype == np.float32


def test_gathered_numpy_input_takes_the_full_copy_branch(monkeypatch):
    """Already-numpy columns (a multi-process gather, host columns) are
    cut from the whole array; nothing is sliced or fetched a shard."""
    monkeypatch.setattr(shuffle_mod, "_slices_on_device", lambda: True)
    outputs, reference = make_outputs(4)
    gathered = [([np.asarray(c) for c in cols], np.asarray(counts), cap)
                for cols, counts, cap in outputs]
    # Mixed with a device-resident output in one call.
    mixed = [gathered[0], outputs[1], gathered[2]]
    crossed = [[] for _ in mixed]
    got = shuffle_mod.unshard_many(mixed, crossed)
    assert_chunks_equal(got, reference)
    for i in (0, 2):
        cols = gathered[i][0]
        assert crossed[i] == [c.nbytes for c in cols]
        for c, chunks in zip(cols, got[i]):
            assert all(np.shares_memory(c, ch) for ch in chunks
                       if ch.size)
    assert len(crossed[1]) > len(outputs[1][0])   # a prefix a shard


def test_host_side_chunks_do_not_pin_the_shard_buffer():
    """On a CPU backend ``np.asarray`` of a shard is zero-copy: the
    memoized chunk must own its rows, not view ``capacity`` of them."""
    outputs, _ = make_outputs(2)
    for cols in shuffle_mod.unshard_many(outputs):
        for chunks in cols:
            assert all(ch.base is None for ch in chunks)
