"""Micro-benchmarks (the reference's unrecorded Go benchmarks, §6):
evaluator scheduling overhead, frame kernel throughputs, codec rates.

Usage: python -m bigslice_tpu.tools.microbench [--quick]
Prints one line per metric; no JSON contract (the benchmark of record
is BENCHMARK.json + benchmarks/).
"""

from __future__ import annotations

import sys
import time

import numpy as np


def _instant_executor():
    """Stub executor for evaluator-overhead benches: completes every
    task instantly so only scheduling cost is measured."""
    from bigslice_tpu.exec.task import TaskState

    class InstantExecutor:
        def submit(self, task):
            if task.transition_if(TaskState.WAITING,
                                  TaskState.RUNNING):
                task.mark_ok()

    return InstantExecutor()


def timeit(fn, iters: int = 5) -> float:
    fn()  # warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_eval(n_tasks: int = 500):
    """Evaluator + stub executor scheduling overhead
    (BenchmarkEval, exec/eval_test.go:583)."""
    from bigslice_tpu.exec.evaluate import evaluate
    from bigslice_tpu.exec.task import (
        Partitioner, Task, TaskDep, TaskName,
    )

    def run():
        prev = None
        tasks = []
        for i in range(n_tasks):
            deps = [TaskDep((prev,), 0)] if prev is not None else []
            t = Task(TaskName(1, f"t{i}", 0, 1),
                     lambda f: iter(()), deps, Partitioner(), None)
            tasks.append(t)
            prev = t
        evaluate(_instant_executor(), [tasks[-1]])

    dt = timeit(run, 3)
    print(f"eval_chain        {n_tasks} tasks      "
          f"{dt * 1e6 / n_tasks:8.1f} us/task")


def bench_eval_fanout(width: int = 100, layers: int = 100):
    """Graph-shaped evaluator overhead: width x layers with full
    cross-layer fan-in (the BenchmarkEnqueue waitlist shape,
    exec/eval_test.go:602) — width*layers tasks,
    ~width^2*(layers-1) dependency edges."""
    from bigslice_tpu.exec.evaluate import evaluate
    from bigslice_tpu.exec.task import (
        Partitioner, Task, TaskDep, TaskName,
    )

    def run():
        below = [Task(TaskName(1, f"f0s{i}", i, width),
                      lambda f: iter(()), [], Partitioner(), None)
                 for i in range(width)]
        for L in range(1, layers):
            below = [Task(TaskName(1, f"f{L}s{i}", i, width),
                          lambda f: iter(()),
                          [TaskDep(tuple(below), i)], Partitioner(),
                          None) for i in range(width)]
        evaluate(_instant_executor(), below)

    n = width * layers
    dt = timeit(run, 3)
    print(f"eval_fanout       {n} tasks    "
          f"{dt * 1e6 / n:8.1f} us/task  ({dt:.2f}s total)")


def bench_wave_stress(shards: int = 64, rows_per_shard: int = 4096):
    """Wave streaming under partition pressure: S shards on an N-device
    mesh run ceil(S/N) waves per group, with the producer's
    wave-partitioned (subid-lane) shuffle and the consumer's waved
    re-combine — the dispatcher/evaluator shape of a pod-scale run
    (north-star task counts, SURVEY §7.3(5))."""
    import jax
    from jax.sharding import Mesh

    import bigslice_tpu as bs
    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("shards",))
    n = shards * rows_per_shard
    rng = np.random.RandomState(0)
    keys = rng.randint(0, 997, n).astype(np.int32)
    vals = np.ones(n, np.int32)
    sess = Session(executor=MeshExecutor(mesh))
    r = bs.Reduce(bs.Const(shards, keys, vals), lambda a, b: a + b)
    t0 = time.perf_counter()
    got = dict(sess.run(r).rows())
    dt = time.perf_counter() - t0
    assert sum(got.values()) == n
    waves = -(-shards // len(devs))
    print(f"wave_stress       {shards} shards/{len(devs)} devices "
          f"({waves} waves)  {n / dt / 1e3:8.1f} Krows/s "
          f"({dt:.2f}s e2e, compile included)")


def bench_frame(n: int = 1 << 20):
    from bigslice_tpu.frame.frame import Frame

    f = Frame([np.arange(n, dtype=np.int32),
               np.random.RandomState(0).rand(n).astype(np.float32)])
    dt = timeit(lambda: f.hash_keys())
    print(f"frame_hash        {n} rows     {n / dt / 1e6:8.1f} Mrows/s")
    dt = timeit(lambda: f.partition_ids(16))
    print(f"frame_partition   {n} rows     {n / dt / 1e6:8.1f} Mrows/s")
    dt = timeit(lambda: f.sorted_by_key())
    print(f"frame_sort        {n} rows     {n / dt / 1e6:8.1f} Mrows/s")


def bench_codec(n: int = 1 << 18):
    from bigslice_tpu.frame import codec
    from bigslice_tpu.frame.frame import Frame

    f = Frame([np.arange(n, dtype=np.int32),
               np.random.RandomState(0).rand(n).astype(np.float32)])
    blob = codec.encode_frame(f)
    dt = timeit(lambda: codec.encode_frame(f))
    print(f"codec_encode      {n} rows      {n / dt / 1e6:8.1f} Mrows/s "
          f"({len(blob) / 1e6:.1f} MB)")
    dt = timeit(lambda: codec.decode_frame(blob))
    print(f"codec_decode      {n} rows      {n / dt / 1e6:8.1f} Mrows/s")


def bench_device_reduce(n: int = 1 << 19):
    from bigslice_tpu.parallel import segment

    keys = np.random.RandomState(0).randint(0, 1 << 12, n).astype(np.int32)
    vals = np.ones(n, np.int32)
    red = segment.DeviceReduceByKey(lambda a, b: a + b, 1, 1)
    dt = timeit(lambda: red([keys], [vals], n))
    print(f"device_reduce     {n} rows      {n / dt / 1e6:8.1f} Mrows/s")


def main(argv=None) -> int:
    import os

    # The wave-stress bench needs a multi-device mesh even when the
    # CPU is pinned: force 8 virtual host devices BEFORE jax
    # initializes (no-op for real TPU backends — the flag only shapes
    # the host platform).
    flag = "--xla_force_host_platform_device_count=8"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag
        ).strip()
    from bigslice_tpu.utils.hermetic import accelerator_or_pinned_cpu

    accelerator_or_pinned_cpu("microbench")
    argv = argv if argv is not None else sys.argv[1:]
    quick = "--quick" in argv
    scale = 4 if quick else 1
    bench_eval(200 if quick else 10_000)
    bench_eval_fanout(*((20, 20) if quick else (100, 100)))
    bench_frame((1 << 20) // scale)
    bench_codec((1 << 18) // scale)
    bench_device_reduce((1 << 19) // scale)
    bench_wave_stress(16 if quick else 64,
                      1024 if quick else 4096)
    return 0


if __name__ == "__main__":
    sys.exit(main())
