"""Checksummed columnar batch codec for host/file persistence.

Mirrors the reference's gob-based column-major batch encoding with per-batch
CRC32 (sliceio/codec.go:68-114, 229-238). Device buffers moving over ICI
need no codec (raw XLA collectives); this codec serves the host tier: spill
files, shard caches, and cross-host result shipping.

Two container versions share the magic-version scheme:

``BSF4`` (current writer) — zero-copy decode. Numeric columns carry their
dtype and trailing dims in the column header and their payload as raw
C-order bytes, so decode materializes them as ``np.frombuffer`` views over
the frame buffer: no per-column ``np.load`` round-trip, no copy. Views are
read-only and hold a reference to the buffer, so they survive the caller
releasing its own reference. A header-only scan (``scan_frame``) walks row
counts and column extents without touching payload bytes — for consumers
staging from raw stream bytes (executor staging counts from decoded
frame lengths).

``BSF3`` (legacy) — numeric payloads are ``np.save`` containers. The
reader stays: old spill files and caches keep decoding; only the writer
was bumped.

Format (little-endian), common envelope:
  magic   4s   b"BSF3" | b"BSF4"
  blen    u64  body length
  crc32   u32  over the body (validated *before* any parsing)
  body:
    prefix u32, ncols u32, nrows u32
    per column: kind u8 (0=numeric, 1=object pickle),
                taglen u16 + tag utf-8 (ColType tag, so custom
                register_ops semantics survive a file round-trip),
                ndim u8 + ndim*u32 trailing dims (vector columns),
                [BSF4, kind 0 only] dlen u8 + dtype descr ascii,
                len u64, bytes (BSF3 numeric: npy container;
                BSF4 numeric: raw C-order column bytes)
"""

from __future__ import annotations

import io
import pickle
import struct
import threading
import time
import zlib
from typing import BinaryIO, Iterator, List, NamedTuple, Optional, Tuple

import numpy as np

from bigslice_tpu.frame.frame import Frame
from bigslice_tpu.slicetype import Schema
from bigslice_tpu.utils import faultinject

MAGIC = b"BSF3"    # legacy container (npy numeric payloads)
MAGIC4 = b"BSF4"   # raw-payload container (zero-copy decode)
# Every frame magic this module can read — public: format sniffers
# (e.g. the shard cache's validity check) key on it.
MAGICS = (MAGIC, MAGIC4)


class CorruptionError(IOError):
    pass


# -- decode clock ---------------------------------------------------------
#
# Staging wants its read/decode split without plumbing timers through
# every store and reader layer: decode_frame charges its elapsed time to
# a per-thread accumulator that the staging code brackets around a drain.
# Off (None) by default — the common path pays one attribute lookup.

_CLOCK = threading.local()


class decode_clock:
    """Context manager accumulating this thread's ``decode_frame`` time
    into ``.seconds``. Nests: inner clocks re-charge their total to the
    enclosing clock on exit."""

    def __enter__(self):
        self._prev = getattr(_CLOCK, "t", None)
        _CLOCK.t = 0.0
        self.seconds = 0.0
        return self

    def __exit__(self, *exc):
        self.seconds = _CLOCK.t
        if self._prev is None:
            del _CLOCK.t
        else:
            _CLOCK.t = self._prev + self.seconds
        return False


def _clock_charge(dt: float) -> None:
    t = getattr(_CLOCK, "t", None)
    if t is not None:
        _CLOCK.t = t + dt


# -- encode ---------------------------------------------------------------

def encode_frame(frame: Frame) -> bytes:
    """Encode one frame in the current (BSF4) container."""
    frame = frame.to_host()
    body = io.BytesIO()
    body.write(struct.pack("<III", frame.prefix, frame.num_cols, len(frame)))
    for c, ct in zip(frame.cols, frame.schema):
        if c.dtype == np.dtype(object):
            payload = pickle.dumps(list(c), protocol=pickle.HIGHEST_PROTOCOL)
            kind = 1
            descr = b""
            dims = ct.shape
        else:
            payload = np.ascontiguousarray(c).tobytes()
            kind = 0
            descr = c.dtype.str.encode("ascii")
            # Dims from the ARRAY, like nrows: the raw payload must be
            # self-consistent with its header even when a frame's
            # declared schema disagrees with its columns (BSF3's npy
            # container self-described; BSF4's header is the only
            # description).
            dims = tuple(int(d) for d in c.shape[1:])
        tag = ct.tag.encode("utf-8")
        body.write(struct.pack("<BH", kind, len(tag)))
        body.write(tag)
        body.write(struct.pack("<B", len(dims)))
        for d in dims:
            body.write(struct.pack("<I", d))
        if kind == 0:
            body.write(struct.pack("<B", len(descr)))
            body.write(descr)
        body.write(struct.pack("<Q", len(payload)))
        body.write(payload)
    payload = body.getvalue()
    crc = zlib_crc(payload)
    return MAGIC4 + struct.pack("<QI", len(payload), crc) + payload


def encode_frame_v3(frame: Frame) -> bytes:
    """The legacy BSF3 encoder (npy numeric payloads). Kept so compat
    tests and A/B benches can mint old-format streams; production
    writers use ``encode_frame``."""
    frame = frame.to_host()
    body = io.BytesIO()
    body.write(struct.pack("<III", frame.prefix, frame.num_cols, len(frame)))
    for c, ct in zip(frame.cols, frame.schema):
        if c.dtype == np.dtype(object):
            payload = pickle.dumps(list(c), protocol=pickle.HIGHEST_PROTOCOL)
            kind = 1
        else:
            buf = io.BytesIO()
            np.save(buf, c, allow_pickle=False)
            payload = buf.getvalue()
            kind = 0
        tag = ct.tag.encode("utf-8")
        body.write(struct.pack("<BH", kind, len(tag)))
        body.write(tag)
        body.write(struct.pack("<B", len(ct.shape)))
        for d in ct.shape:
            body.write(struct.pack("<I", d))
        body.write(struct.pack("<Q", len(payload)))
        body.write(payload)
    payload = body.getvalue()
    crc = zlib_crc(payload)
    return MAGIC + struct.pack("<QI", len(payload), crc) + payload


def zlib_crc(payload) -> int:
    """CRC32 of any buffer-protocol object (bytes, memoryview)."""
    return zlib.crc32(payload) & 0xFFFFFFFF


# -- header-only scan -----------------------------------------------------

class ColExtent(NamedTuple):
    """Where one column's payload lives inside the stream buffer."""

    kind: int                     # 0 numeric, 1 object pickle
    tag: str
    dims: Tuple[int, ...]         # trailing (vector) dims
    dtype: Optional[np.dtype]     # None for object cols / BSF3 numerics
    payload_offset: int           # absolute offset into the buffer
    payload_len: int


class FrameExtent(NamedTuple):
    """One frame's header facts: row count and column extents, gathered
    without touching (or checksumming) payload bytes."""

    version: int                  # 3 | 4
    nrows: int
    prefix: int
    cols: Tuple[ColExtent, ...]
    offset: int                   # frame start (magic byte)
    end: int                      # offset of the next frame


def _parse_envelope(data, offset: int) -> Tuple[int, int, int, int]:
    """(version, blen, crc, body_start) of the frame at ``offset``."""
    if len(data) < offset + 16:
        raise CorruptionError("truncated frame stream")
    magic = bytes(data[offset : offset + 4])
    if magic not in MAGICS:
        raise CorruptionError("bad magic in frame stream")
    blen, crc = struct.unpack_from("<QI", data, offset + 4)
    return (4 if magic == MAGIC4 else 3), blen, crc, offset + 16


def scan_frame(data, offset: int = 0) -> FrameExtent:
    """Header-only scan of one frame: row count and column extents with
    payload bytes skipped (no CRC validation — ``decode_frame`` remains
    the integrity gate). Works on both container versions; BSF3 numeric
    columns scan with ``dtype=None`` (their dtype lives inside the npy
    payload)."""
    version, blen, _crc, body_start = _parse_envelope(data, offset)
    end = body_start + blen
    if len(data) < end:
        raise CorruptionError("truncated frame stream")
    try:
        pos = body_start
        prefix, ncols, nrows = struct.unpack_from("<III", data, pos)
        pos += 12
        cols: List[ColExtent] = []
        for _ in range(ncols):
            kind, taglen = struct.unpack_from("<BH", data, pos)
            pos += 3
            tag = bytes(data[pos : pos + taglen]).decode("utf-8")
            pos += taglen
            (ndim,) = struct.unpack_from("<B", data, pos)
            pos += 1
            dims = (struct.unpack_from(f"<{ndim}I", data, pos)
                    if ndim else ())
            pos += 4 * ndim
            dtype = None
            if version == 4 and kind == 0:
                (dlen,) = struct.unpack_from("<B", data, pos)
                pos += 1
                dtype = np.dtype(
                    bytes(data[pos : pos + dlen]).decode("ascii")
                )
                pos += dlen
            (plen,) = struct.unpack_from("<Q", data, pos)
            pos += 8
            cols.append(ColExtent(kind, tag, tuple(dims), dtype, pos,
                                  plen))
            pos += plen
    except (struct.error, UnicodeDecodeError, TypeError,
            ValueError) as e:
        # A header field cut by truncation (or scrambled by
        # corruption) must surface as the module's contract error, not
        # a struct/unicode internal.
        raise CorruptionError("corrupt frame header") from e
    if pos != end:
        raise CorruptionError("frame body length mismatch")
    return FrameExtent(version, nrows, prefix, tuple(cols), offset, end)


def scan_frames(data) -> Iterator[FrameExtent]:
    """Header-only scan of a whole stream buffer (the staging arena's
    counting pass: exact row totals without decoding a byte of
    payload)."""
    pos = 0
    n = len(data)
    while pos < n:
        ext = scan_frame(data, pos)
        yield ext
        pos = ext.end


# -- decode ---------------------------------------------------------------

def _readonly_view(data, dtype: np.dtype, count: int, offset: int,
                   nrows: int, dims: Tuple[int, ...]) -> np.ndarray:
    col = np.frombuffer(data, dtype, count=count, offset=offset)
    if col.flags.writeable:  # writable source buffer (bytearray/mmap)
        col.setflags(write=False)
    if dims:
        col = col.reshape((nrows,) + dims)
    return col


def decode_frame(data, offset: int = 0) -> tuple:
    """Decode one frame; returns (frame, next_offset).

    BSF4 numeric columns come back as read-only ``np.frombuffer`` views
    over ``data`` — zero copies; the views keep ``data`` alive. BSF3
    frames decode through the legacy npy reader. CRC is validated over
    the body before any parsing, both versions."""
    t0 = time.perf_counter()
    version, blen, crc, body_start = _parse_envelope(data, offset)
    end = body_start + blen
    if len(data) < end:
        raise CorruptionError("truncated frame stream")
    # CRC over a memoryview slice: no body copy on the zero-copy path.
    if zlib_crc(memoryview(data)[body_start:end]) != crc:
        raise CorruptionError("frame checksum mismatch")
    ext = scan_frame(data, offset)
    cols: List[np.ndarray] = []
    for ce in ext.cols:
        payload_end = ce.payload_offset + ce.payload_len
        if payload_end > end:
            raise CorruptionError("truncated frame stream")
        if ce.kind == 1:
            from bigslice_tpu.frame.frame import obj_col

            cols.append(obj_col(pickle.loads(
                data[ce.payload_offset : payload_end]
            )))
        elif version == 4:
            count = ext.nrows
            for d in ce.dims:
                count *= d
            if count * ce.dtype.itemsize != ce.payload_len:
                raise CorruptionError("column payload size mismatch")
            cols.append(_readonly_view(
                data, ce.dtype, count, ce.payload_offset, ext.nrows,
                ce.dims,
            ))
        else:
            cols.append(np.load(
                io.BytesIO(data[ce.payload_offset : payload_end]),
                allow_pickle=False,
            ))
    from bigslice_tpu.slicetype import ColType

    schema = Schema(
        [ColType(c.dtype, ce.tag, ce.dims)
         for c, ce in zip(cols, ext.cols)],
        ext.prefix,
    )
    _clock_charge(time.perf_counter() - t0)
    return Frame(cols, schema), end


ZMAGIC = b"BSZ1"  # zstd-compressed container of a BSF3/BSF4 stream


def open_compressed_write(fp):
    """Wrap a binary file with a zstd stream writer (the reference's
    slicecache zstd writethrough, internal/slicecache/sliceio.go:53-96).
    Caller must close() the returned writer (finalizes the zstd frame;
    the underlying file stays open). Returns None when zstd is
    unavailable — caller writes plain."""
    try:
        import zstandard
    except ImportError:
        return None
    fp.write(ZMAGIC)
    return zstandard.ZstdCompressor(level=3).stream_writer(
        fp, closefd=False
    )


class _PushbackReader:
    """A file-like that replays already-sniffed header bytes."""

    def __init__(self, head: bytes, fp):
        self._head = head
        self._fp = fp

    def read(self, n: int = -1) -> bytes:
        if self._head:
            if n is None or n < 0 or n >= len(self._head):
                h, self._head = self._head, b""
                want = -1 if (n is None or n < 0) else n - len(h)
                return h + (self._fp.read(want) if want != 0 else b"")
            h, self._head = self._head[:n], self._head[n:]
            return h
        return self._fp.read(n)


def maybe_decompressed(fp):
    """Sniff a stream: ZMAGIC → zstd-decompressing reader; otherwise a
    reader replaying the sniffed bytes (plain BSF3/BSF4 files from
    before compression, or environments without zstd, stay readable)."""
    head = fp.read(4)
    if head == ZMAGIC:
        import zstandard

        return zstandard.ZstdDecompressor().stream_reader(fp)
    return _PushbackReader(head, fp)


class FrameWriter:
    """Streams encoded frames to a binary file object."""

    def __init__(self, fp: BinaryIO):
        self.fp = fp
        self.nrows = 0

    def write(self, frame: Frame) -> None:
        self.fp.write(encode_frame(frame))
        self.nrows += len(frame)


def read_frames(data: bytes) -> Iterator[Frame]:
    pos = 0
    while pos < len(data):
        frame, pos = decode_frame(data, pos)
        yield frame


def write_stream(fp: BinaryIO, frames) -> int:
    w = FrameWriter(fp)
    for f in frames:
        w.write(f)
    return w.nrows


def _read_exact(fp, n: int) -> bytes:
    """Read exactly n bytes (looping over short reads — decompressing
    and remote-object streams legitimately return partial chunks)."""
    parts = []
    got = 0
    while got < n:
        chunk = fp.read(n - got)
        if not chunk:
            break
        parts.append(chunk)
        got += len(chunk)
    return b"".join(parts)


def _corrupt_body(body: bytes, kind: str) -> bytes:
    """Chaos-plane frame damage: what a bad NIC/disk would have done.
    ``flip`` flips one payload bit (CRC catches it), ``truncate`` cuts
    the body short (the envelope length check catches it). Either way
    the *organic* CorruptionError path fires — the injection corrupts
    data, it never fakes the detector."""
    if kind == "truncate":
        return body[: len(body) // 2]
    ba = bytearray(body)
    if ba:
        ba[len(ba) // 2] ^= 0x40
    return bytes(ba)


def read_stream(fp: BinaryIO) -> Iterator[Frame]:
    """Incrementally decode frames from a file object — one frame's bytes
    resident at a time (spill-merge reads depend on this bound). BSF4
    frames' columns are views over that one frame's buffer, so the bound
    holds for them too: a consumed frame's buffer frees when its columns
    do."""
    while True:
        header = _read_exact(fp, 16)
        if not header:
            return
        if len(header) < 16 or header[:4] not in MAGICS:
            raise CorruptionError("bad frame header in stream")
        (blen, _crc) = struct.unpack_from("<QI", header, 4)
        body = _read_exact(fp, blen)
        if faultinject.ENABLED:
            fault = faultinject.fire("codec.read")
            if fault is not None:
                body = _corrupt_body(body, fault.kind)
        frame, _ = decode_frame(header + body)
        yield frame
