"""TPC-H Q1, the Pricing Summary Report, as a bigslice user writes it,
with its data from the seed and its plain numpy reference.

    li  = Const(shards, returnflag, linestatus, quantity, extendedprice,
                discount, tax, shipdate)
    q   = Filter(li, shipdate <= cutoff)
    m   = Map(q, (flag, status) + (qty, price, price*(100-disc),
                  price*(100-disc)*(100+tax), disc, 1), out=[... int64 ...])
    agg = sess.run(Reduce(Prefixed(m, 2), add,         # six sums a group
                          dense_keys=(len(flags), len(statuses))))

Money is in exact decimal units, as a database holds ``decimal``: cents
for ``l_extendedprice``, 10^-4 for the discounted price, 10^-6 for the
charge; the five decimal sums are 64-bit columns (one group's charge is
about 5.6 x 10^16 at SF 1) and ``count(*)`` an int32 one. The two
``char(1)`` flags arrive dictionary-coded, as a columnar reader hands
them, and are decoded at the edge, where the report's four rows are
ordered and the averages taken from the exact sums (``sum / count``,
the specification's own definition). The six sums are the answer that
is compared; every job scans all of it inside the timed path and then
frees everything it stored."""

from __future__ import annotations

import numpy as np

import bigslice_tpu as bs
from bigslice_tpu.frame import dictenc
from bigslice_tpu.slicetype import Schema

#: Op kinds of this pipeline that must run their waves on the mesh: the
#: fused Const -> Filter -> Map -> Prefixed group (which holds the
#: map-side combine) and the reduce side.
MESH_OPS = ("const_filter_map_prefixed", "reduce")

#: The dictionaries of the two flag columns (sorted, as a columnar
#: writer builds them): code = position.
RETURNFLAGS = ("A", "N", "R")
LINESTATUSES = ("F", "O")

#: The six sums of a group, in the order the Map emits them.
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
        "sum_disc", "count_order")

#: What the Map emits: the two flag codes, the addends of five 64-bit
#: decimal sums and of the count. Built when the module loads, so a package
#: whose device tier has no 64-bit integer columns (before PR 32) stops
#: here with its own ``TypeError``, before any work.
_OUT = Schema([np.int32, np.int32, np.int64, np.int64, np.int64,
               np.int64, np.int64, np.int32])


def _q1_columns(flag, status, qty, price, disc, tax, shipdate):
    # Module-level: program caches key on the function's identity.
    price = price.astype(np.int64)
    disc_price = price * (100 - disc)
    return (flag, status, qty, price, disc_price,
            disc_price * (100 + tax), disc, np.int32(1))


def _add(a, b):
    return tuple(x + y for x, y in zip(a, b))


class Data:
    """The batch, a column an attribute: ``keys`` and ``status`` are the
    dictionary codes of ``l_returnflag`` and ``l_linestatus``."""

    COLUMNS = ("keys", "status", "qty", "price", "disc", "tax",
               "shipdate")

    def __init__(self, cols, shards, cutoff):
        for name, col in zip(self.COLUMNS, cols):
            setattr(self, name, col)
        self.shards, self.cutoff = shards, cutoff
        # One predicate object for every job of the run.
        self.shipped_by = (
            lambda flag, status, qty, price, disc, tax, shipdate:
            shipdate <= cutoff)

    @property
    def cols(self) -> tuple:
        """The seven columns, cut to the rows every one of them has."""
        cols = [getattr(self, name) for name in self.COLUMNS]
        rows = min(len(c) for c in cols)
        return tuple(c[:rows] for c in cols)


def make_data(cfg: dict, seed: int) -> Data:
    """The seven columns of ``lineitem`` that Q1 reads, populated by
    the specification's section 4.2.3 rules (``config.json``: ``shapes``,
    ``assumed``), rows in random order — exactly ``4 x orders`` rows for
    every seed."""
    rng = np.random.default_rng([abs(int(seed)), 1])
    orders = int(cfg["orders_per_sf"] * cfg["scale_factor"])
    top = int(cfg["lines_per_order_max"])
    lines = rng.integers(1, top + 1, orders)
    # Nudge distinct random orders by one line until the total is exact.
    delta = orders * (top + 1) // 2 - int(lines.sum())
    step = 1 if delta > 0 else -1
    room = np.flatnonzero(lines < top if step > 0 else lines > 1)
    lines[rng.choice(room, abs(delta), replace=False)] += step
    n = int(lines.sum())
    orderdate = np.repeat(
        rng.integers(0, int(cfg["orderdate_days"]) + 1, orders), lines)
    shipdate = orderdate + rng.integers(
        1, int(cfg["ship_after_days_max"]) + 1, n)
    receipt = shipdate + rng.integers(
        1, int(cfg["receipt_after_days_max"]) + 1, n)
    today = int(cfg["currentdate_day"])
    flag = np.where(receipt <= today,
                    np.where(rng.integers(0, 2, n) == 0,
                             RETURNFLAGS.index("R"),
                             RETURNFLAGS.index("A")),
                    RETURNFLAGS.index("N"))
    status = np.where(shipdate > today, LINESTATUSES.index("O"),
                      LINESTATUSES.index("F"))
    qty = rng.integers(1, int(cfg["quantity_max"]) + 1, n)
    partkey = rng.integers(
        1, int(cfg["parts_per_sf"] * cfg["scale_factor"]) + 1, n)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    price = qty * retail
    disc = rng.integers(0, int(cfg["discount_pct_max"]) + 1, n)
    tax = rng.integers(0, int(cfg["tax_pct_max"]) + 1, n)
    order = rng.permutation(n)
    cols = tuple(c[order].astype(np.int32)
                 for c in (flag, status, qty, price, disc, tax, shipdate))
    return Data(cols, -(-n // int(cfg["rows_per_shard"])),
                int(cfg["cutoff_day"]))


def work(cfg: dict, data: Data) -> dict:
    """What one job needs whatever implements it, from shapes alone:
    every input byte read once (seven int32 columns, 28 bytes a row)
    and the answer's four rows written once."""
    cols = data.cols
    rows = len(cols[0])
    row = sum(c.dtype.itemsize for c in cols)
    answer = sum(ct.dtype.itemsize for ct in _OUT)
    return {"input_rows": rows,
            "least_bytes": rows * row + int(cfg["groups"]) * answer}


def _tables(codes, sums) -> dict:
    """The answer as the comparison wants it: one keyed table a sum,
    keyed by the group's code (``flag * len(LINESTATUSES) + status``),
    in key order."""
    order = np.argsort(codes, kind="stable")
    return {name: (codes[order], np.asarray(s, np.int64)[order])
            for name, s in zip(SUMS, sums)}


def report(flag, status, sums) -> list:
    """Q1's rows as the user reads them: flags decoded through their
    dictionaries, ordered by (returnflag, linestatus), the averages
    taken from the exact sums."""
    rf = dictenc.decode_column(flag, RETURNFLAGS)
    ls = dictenc.decode_column(status, LINESTATUSES)
    qty, base, disc_price, charge, disc, count = (
        np.asarray(s, np.int64) for s in sums)
    rows = [(rf[i], ls[i], qty[i] / 1.0, base[i] / 1e2,
             disc_price[i] / 1e4, charge[i] / 1e6,
             qty[i] / count[i], base[i] / 1e2 / count[i],
             disc[i] / 1e2 / count[i], int(count[i]))
            for i in range(len(rf))]
    return sorted(rows, key=lambda r: r[:2])


class Job:
    """One user job: fresh slices over the same rows. A job made with
    ``keep`` leaves the aggregate's own output (four rows) stored until
    the window has closed and scans it once more then: what a later
    job of the session would read is still the answer."""

    def __init__(self, sess, data: Data, keep: bool):
        self.sess, self.data, self.keep = sess, data, keep
        self.answers: dict = {}
        self.results: list = []

    def steps(self):
        return (("build", self._build), ("run", self._run),
                ("scan", self._scan))

    def _build(self):
        d = self.data
        shipped = bs.Filter(bs.Const(d.shards, *d.cols), d.shipped_by)
        priced = bs.Map(shipped, _q1_columns, out=_OUT)
        # The key is two dictionary codes: the dictionaries' sizes say
        # how few groups there can be.
        self.slice = bs.Reduce(
            bs.Prefixed(priced, 2), _add,
            dense_keys=(len(RETURNFLAGS), len(LINESTATUSES)))

    def _run(self):
        self.agg = self.sess.run(self.slice)
        self.results = [self.agg]

    def _scanned(self):
        frames = [f.to_host() for f in self.agg.frames()]
        cols = [np.concatenate([np.asarray(f.cols[j]) for f in frames])
                for j in range(len(self.agg.schema))]
        return cols[0], cols[1], cols[2:]

    def _scan(self):
        flag, status, sums = self._scanned()
        self.report = report(flag, status, sums)
        self.answers = _tables(flag * len(LINESTATUSES) + status, sums)

    def discard(self):
        # ``discard_graph(keep=[agg])`` would spare everything ``agg``
        # was computed from as well (the map-side group's output):
        # spare the aggregate's OWN stored output only.
        spared = {id(t) for t in self.agg.tasks} if self.keep else set()
        seen, stack = set(), list(self.agg.tasks)
        while stack:
            t = stack.pop()
            if id(t) in seen:
                continue
            seen.add(id(t))
            if id(t) not in spared:
                self.sess.executor.discard(t)
            stack.extend(p for d in t.deps for p in d.tasks)
        if not self.keep:
            self.results = []

    def late_answers(self) -> dict:
        try:
            flag, status, sums = self._scanned()
            return _tables(flag * len(LINESTATUSES) + status, sums)
        finally:
            self.agg.discard_graph()
            self.results = []


def lowering(sess, evidence, platform: str) -> dict:
    """What the executor picked, for an earlier line of the output:
    the per-op ``combine`` block of the map-side group."""
    ops = sess.telemetry_summary()["ops"]
    return {op.split("@")[0]: rec["combine"]
            for op, rec in ops.items() if "combine" in rec}


def counters(data: Data) -> dict:
    return {}


def close(data: Data) -> None:
    pass


def _aggregate(cols, keep) -> dict:
    """The six sums a group, in plain ``int64`` numpy on the host:
    ``np.add.at`` into one slot a code (``np.bincount(weights=)`` would
    sum in float64, inexact above 2^53)."""
    flag, status, qty, price, disc, tax, _ = (
        c[keep].astype(np.int64) for c in cols)
    code = flag * len(LINESTATUSES) + status
    disc_price = price * (100 - disc)
    slots = len(RETURNFLAGS) * len(LINESTATUSES)
    sums = []
    for v in (qty, price, disc_price, disc_price * (100 + tax), disc,
              np.ones_like(qty)):
        s = np.zeros(slots, np.int64)
        np.add.at(s, code, v)
        sums.append(s)
    present = np.flatnonzero(sums[-1])
    return _tables(present, [s[present] for s in sums])


def reference(cfg: dict, data: Data) -> dict:
    return _aggregate(data.cols, data.cols[6] <= data.cutoff)


def controls(cfg: dict, data: Data) -> dict:
    """The reference with one stated guarantee broken each: a row that
    passes the filter left out; every sum wrapped to 32 bits (what a
    32-bit device tier computes); ``<`` for ``<=`` in the filter."""
    ship = data.cols[6]
    keep = ship <= data.cutoff
    dropped = keep.copy()
    dropped[np.flatnonzero(keep)[-1]] = False
    want = reference(cfg, data)
    return {
        "row_dropped": _aggregate(data.cols, dropped),
        "sums_in_int32": {
            name: (k, v.astype(np.int32).astype(np.int64))
            for name, (k, v) in want.items()},
        "cutoff_exclusive": _aggregate(data.cols, ship < data.cutoff),
    }
