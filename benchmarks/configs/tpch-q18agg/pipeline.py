"""TPC-H Q18's inner aggregate over ``lineitem``, as a bigslice user
writes it, with its data from the seed and its plain numpy reference.

    agg = sess.run(Reduce(Const(shards, l_orderkey, l_quantity), add))
    big = sess.run(Filter(agg, sum > 300))

The timed path produces the whole aggregate (``agg``), scans the HAVING
rows and then frees the job as upstream ``Discard`` does: every stored
output of the subgraph. Jobs whose full answer is checked spare
``agg``'s own output (12 MB) on the device until the window has
closed; they run the same steps as every other job."""

from __future__ import annotations

import numpy as np

import bigslice_tpu as bs

#: Op kinds of this pipeline that must run their waves on the mesh.
MESH_OPS = ("const", "reduce", "filter")


def _add(a, b):
    # Module-level: program caches key on the combine fn's identity.
    return a + b


class Data:
    def __init__(self, keys, qty, shards, having):
        self.keys, self.qty = keys, qty
        self.shards, self.having = shards, having
        # Module-level-stable predicate per Data: one identity for every
        # job of the run.
        self.over = lambda key, total: total > having


def make_data(cfg: dict, seed: int) -> Data:
    """``lineitem(l_orderkey, l_quantity)`` at the configured scale:
    dbgen's sparse order keys, 1..7 lines an order, quantity 1..50,
    rows in random order — exactly ``4 x orders`` rows for every seed."""
    rng = np.random.default_rng([abs(int(seed)), 18])
    orders = int(cfg["orders_per_sf"] * cfg["scale_factor"])
    top = int(cfg["lines_per_order_max"])
    o = np.arange(orders, dtype=np.int64)
    keys = ((o >> 3) << 5 | (o & 7)) + 1
    lines = rng.integers(1, top + 1, orders)
    target = orders * (top + 1) // 2
    # Nudge distinct random orders by one line until the total is exact.
    delta = target - int(lines.sum())
    step = 1 if delta > 0 else -1
    room = np.flatnonzero(lines < top if step > 0 else lines > 1)
    lines[rng.choice(room, abs(delta), replace=False)] += step
    k = np.repeat(keys, lines).astype(np.int32)
    q = rng.integers(1, int(cfg["quantity_max"]) + 1, len(k),
                     dtype=np.int32)
    order = rng.permutation(len(k))
    shards = -(-len(k) // int(cfg["rows_per_shard"]))
    return Data(k[order], q[order], shards, int(cfg["having_sum_over"]))


def work(cfg: dict, data: Data) -> dict:
    """What one job needs whatever implements it, from shapes alone.

    ``least_bytes``: every input row read once (key + quantity), every
    group's row written once, and the HAVING pass reading those once.
    ``kernel_bytes_per_call``: per named kernel, a shard's rows, each
    row's key read and a 4-byte partition id written."""
    row = data.keys.dtype.itemsize + data.qty.dtype.itemsize
    groups = int(cfg["orders_per_sf"] * cfg["scale_factor"])
    return {
        "input_rows": len(data.keys),
        "least_bytes": len(data.keys) * row + 2 * groups * row,
        "kernel_bytes_per_call": {
            "bigslice_hash_partition":
                -(-len(data.keys) // data.shards)
                * (data.keys.dtype.itemsize + 4),
        },
    }


def _columns(res) -> list:
    frames = [f.to_host() for f in res.frames()]
    return [np.concatenate([np.asarray(f.cols[j]) for f in frames])
            if frames else np.empty(0, np.int32)
            for j in range(len(res.schema))]


def _by_key(cols) -> tuple:
    order = np.argsort(cols[0], kind="stable")
    return cols[0][order], cols[1][order]


def discard_graph_sparing(result, spare=None) -> None:
    """``result.discard_graph()``, but the OWN stored outputs of
    ``spare``'s tasks stay (``discard_graph(keep=[spare])`` would spare
    everything ``spare`` was computed from as well: the map-side group
    output, 110 MB a job here)."""
    spared = {id(t) for t in spare.tasks} if spare is not None else set()
    seen, stack = set(), list(result.tasks)
    while stack:
        t = stack.pop()
        if id(t) in seen:
            continue
        seen.add(id(t))
        if id(t) not in spared:
            result.session.executor.discard(t)
        stack.extend(p for d in t.deps for p in d.tasks)


class Job:
    """One user job: fresh slices over the same rows."""

    def __init__(self, sess, data: Data, keep: bool):
        self.sess, self.data, self.keep = sess, data, keep
        self.answers: dict = {}
        self.results: list = []

    def steps(self):
        return (("build", self._build), ("run", self._run),
                ("scan", self._scan))

    def _build(self):
        d = self.data
        self.slice = bs.Reduce(bs.Const(d.shards, d.keys, d.qty), _add)

    def _run(self):
        self.agg = self.sess.run(self.slice)
        self.big = self.sess.run(bs.Filter(self.agg, self.data.over))
        self.results = [self.agg, self.big]

    def _scan(self):
        self.answers["having"] = _by_key(_columns(self.big))

    def discard(self):
        discard_graph_sparing(self.big, self.agg if self.keep else None)
        if not self.keep:
            self.results = []

    def late_answers(self) -> dict:
        """Every group's sum, scanned after the window from the ``agg``
        the timed path left on the device."""
        try:
            return {"aggregate": _by_key(_columns(self.agg))}
        finally:
            self.agg.discard()
            self.results = []


def lowering(sess, evidence, platform: str) -> dict:
    """What the executor picked, for an earlier line of the output."""
    ex = sess.executor
    out = {"hash_aggregate": bool(ex._hashagg_enabled())}
    if platform == "tpu":
        out["mosaic_kernels"] = evidence.mosaic_kernels(
            evidence.group_programs(ex))
    return out


def counters(data: Data) -> dict:
    return {}


def close(data: Data) -> None:
    pass


def _aggregate(keys, qty) -> tuple:
    uniq, inv = np.unique(keys, return_inverse=True)
    sums = np.bincount(inv, weights=qty, minlength=len(uniq))
    return uniq, sums.astype(np.int64).astype(np.int32)


def reference(cfg: dict, data: Data) -> dict:
    uniq, sums = _aggregate(data.keys, data.qty)
    big = sums > data.having
    return {"aggregate": (uniq, sums), "having": (uniq[big], sums[big])}


def controls(cfg: dict, data: Data) -> dict:
    """The reference with one stated guarantee broken each: a row left
    out, and sums kept in 8 bits (they wrap past 127)."""
    uniq, sums = _aggregate(data.keys[:-1], data.qty[:-1])
    big = sums > data.having
    dropped = {"aggregate": (uniq, sums), "having": (uniq[big], sums[big])}
    u, s = reference(cfg, data)["aggregate"]
    s8 = s.astype(np.int8).astype(np.int32)
    big = s8 > data.having
    return {"row_dropped": dropped,
            "sums_in_int8": {"aggregate": (u, s8),
                             "having": (u[big], s8[big])}}
