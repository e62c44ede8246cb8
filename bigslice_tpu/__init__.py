"""bigslice_tpu — a TPU-native distributed data-processing framework.

A brand-new framework with the capabilities of grailbio/bigslice
(https://github.com/grailbio/bigslice): typed, sharded, columnar datasets
composed with Map/Filter/Flatmap/Reduce/Fold/Cogroup/Reshuffle-style
combinators, compiled into a deterministic, pipelined task DAG and executed
with fault tolerance, per-shard caching, live status, tracing, and metrics.

Unlike the reference — pure Go, per-record reflection calls, gob-over-RPC
shuffles between ad-hoc cloud workers (see SURVEY.md) — this framework is
designed for JAX/XLA on TPU:

- columns are struct-of-arrays device buffers (``frame.Frame``),
- fused operator pipelines are traced once and compiled by XLA,
- shuffles lower to hash-bucket kernels + ``all_to_all`` over ICI,
- combiners lower to on-device sort + segmented reduction,
- multi-host coordination runs over DCN (``jax.distributed``),
- host-tier sources/sinks and file/GCS-backed caching sit at the edges.

Layering (mirrors SURVEY.md §1, re-architected for TPU):

  L5  user API: this package root — Slice combinators, Func/Invocation
  L4  planner: exec/compile.py — pipeline fusion, task graph
  L3  scheduler: exec/evaluate.py — DAG state machine
  L2  executors: exec/local.py | exec/meshexec.py (SPMD over jax Mesh)
  L1  data plane: frame/ (columnar SoA), parallel/ (shuffle, segment ops)
  L0  foundations: slicetype, typecheck, utils/
"""

from bigslice_tpu.slicetype import Schema, ColType
from bigslice_tpu.frame.frame import Frame
from bigslice_tpu.ops.base import (
    Slice,
    Dep,
    Pragma,
    Procs,
    Exclusive,
    Materialize,
)
from bigslice_tpu.ops.func import Func, func, Invocation
from bigslice_tpu.ops.const import Const
from bigslice_tpu.ops.source import ReaderFunc, WriterFunc, ScanReader
from bigslice_tpu.ops.mapops import Map, MapBatches, Filter, Flatmap, Head, Scan, Prefixed, Unwrap
from bigslice_tpu.ops.reduce import Reduce
from bigslice_tpu.ops.fold import Fold
from bigslice_tpu.ops.cogroup import Cogroup
from bigslice_tpu.ops.join import (
    DuplicateBuildKeyError,
    JoinAggregate,
    JoinLookup,
)
from bigslice_tpu.ops.groupby import GroupByKey
from bigslice_tpu.ops.attention import SelfAttend
from bigslice_tpu.ops.parquet import ParquetReader
from bigslice_tpu.ops.reshuffle import Reshuffle, Repartition, Reshard
from bigslice_tpu.ops.cache import Cache, CachePartial, ReadCache

__all__ = [
    "Schema",
    "ColType",
    "Frame",
    "Slice",
    "Dep",
    "Pragma",
    "Procs",
    "Exclusive",
    "Materialize",
    "Func",
    "func",
    "Invocation",
    "Const",
    "ReaderFunc",
    "WriterFunc",
    "ScanReader",
    "Map",
    "MapBatches",
    "Filter",
    "Flatmap",
    "Head",
    "Scan",
    "Prefixed",
    "Unwrap",
    "Reduce",
    "Fold",
    "Cogroup",
    "JoinAggregate",
    "JoinLookup",
    "DuplicateBuildKeyError",
    "GroupByKey",
    "SelfAttend",
    "ParquetReader",
    "Reshuffle",
    "Repartition",
    "Reshard",
    "Cache",
    "CachePartial",
    "ReadCache",
]

__version__ = "0.1.0"
