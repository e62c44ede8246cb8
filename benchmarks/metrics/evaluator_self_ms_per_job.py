"""Layer: entry + session + evaluator. Self time of the program's
``session.run``, ``compile_tasks`` and ``evaluate`` spans: the part of
an invocation in which none of its groups was running on the executor
— slice to task compile, the evaluator's loop, and the executor's
gather-and-dispatch latency. Over the window's jobs."""

from benchmarks.harness import spans


def read(r):
    return spans.per_job(
        r, ("session.run", "compile_tasks", "evaluate"), "self_s", 1e3)
