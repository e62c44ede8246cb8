"""Domain counting over a line corpus — the reference's demo program
(cmd/urls/urls.go:5-37: GDELT domain count = ReaderFunc → Map → Reduce).

Two variants:
- ``domain_count``: the straight port shape — host-tier parsing, string
  keys end-to-end.
- ``domain_count_encoded``: the TPU-recommended shape — one host pass
  builds a domain vocabulary, then counting runs on the device tier via
  surrogate keys (frame/dictenc.py), decoding at the edge.
"""

from __future__ import annotations

from typing import Callable, List, Tuple, Union

import numpy as np

import bigslice_tpu as bs
from bigslice_tpu.frame import strparse


def _domain(url: str) -> str:
    url = url.split("//", 1)[-1]
    return url.split("/", 1)[0].lower()


def _add(a, b):
    # Module-level (stable identity): device program/jit caches key on
    # the combine fn's id, so repeated domain_count_encoded calls in
    # one session reuse the compiled SPMD reduce.
    return a + b


def _attach_one(code):
    return (code, 1)


def _domains_batch(urls) -> np.ndarray:
    """Batch ``_domain`` over a whole column. Deliberately a list
    comprehension, not np.char: for short strings the fixed-width
    unicode round-trips np.char needs cost ~4× the C-dispatched str
    methods (measured in the wordcount bench profile); must stay
    bit-equal to _domain — tests/test_models.py pins the equivalence."""
    out = np.empty(len(urls), dtype=object)
    out[:] = [_domain(u) for u in urls]
    return out


def domain_count(num_shards: int, source: Union[str, Callable]) -> bs.Slice:
    """Count URLs per domain (host-tier strings)."""
    lines = bs.ScanReader(num_shards, source)
    pairs = bs.Map(lines, lambda u: (_domain(u), 1),
                   out=[str, np.int32])
    return bs.Reduce(pairs, lambda a, b: a + b)


def domain_count_encoded(sess, num_shards: int,
                         source: Union[str, Callable],
                         parse_tiers=None
                         ) -> List[Tuple[str, int]]:
    """Count URLs per domain with device-tier counting.

    Pass 1 (host, streaming): parse, build the vocabulary, and encode
    in one fused sweep, materializing int32 codes.
    Pass 2 (device): attach unit counts and Reduce over the codes;
    decode at the edge. ``parse_tiers`` (a ``strparse.ParseTiers``)
    counts the rows each host parse tier served.
    """
    from bigslice_tpu.frame import dictenc

    lines = bs.ScanReader(num_shards, source)
    vocab = dictenc.GlobalVocab()

    # Pass 1 — ONE host sweep: parse, build the vocabulary, and encode
    # in the same batch fn; the materialized corpus is int32 CODES, so
    # everything downstream (count attach, hash, shuffle, combine) is
    # device-tier. The sweep itself is vectorized byte-level span
    # extraction + Arrow dictionary_encode (frame/strparse.py) — zero
    # per-row Python for ASCII rows; _domains_batch remains the exact
    # fallback (and the equivalence oracle in tests).
    def parse_encode(f):
        return (strparse.domains_codes(f.cols[0], vocab,
                                       fallback_fn=_domain,
                                       tiers=parse_tiers),)

    corpus = sess.run(bs.MapBatches(lines, parse_encode, out=[np.int32]))
    try:
        # Pass 2 — all device: attach unit counts (traced Map), then a
        # dense-keyed Reduce (codes are in [0, len(vocab)) by
        # construction — the sort-free table lowering applies).
        pairs = bs.Map(corpus, _attach_one, out=[np.int32, np.int32])
        res = sess.run(bs.Reduce(pairs, _add,
                                 dense_keys=max(1, len(vocab))))
        return dictenc.decode_result_rows(res, vocab)
    finally:
        corpus.discard()
