"""TPC-H Q3, the Shipping Priority Query, as a bigslice user writes it,
with its data from the seed and its plain numpy reference.

    cust = Filter(Const(2,  c_custkey, c_mktsegment), segment == BUILDING)
    ords = Filter(Const(12, o_custkey, o_orderkey, o_orderdate,
                        o_shippriority), o_orderdate < DATE)
    co   = Map(JoinLookup(ords, cust), -> (o_orderkey; o_orderdate,
                                           o_shippriority))
    li   = Filter(Const(46, l_orderkey, l_extendedprice, l_discount,
                        l_shipdate), l_shipdate > DATE)
    rev  = Map(li, (l_orderkey, price.astype(int64) * (100 - disc)),
               out=[int32, int64])
    lo   = JoinLookup(rev, co)
    agg  = sess.run(Reduce(Prefixed(Map(lo, -> (l_orderkey, o_orderdate,
                    o_shippriority; revenue)), 3), add))
    top  = the ten rows of agg by (revenue desc, o_orderdate, l_orderkey)

The system has no planner: the order of the two joins is the user's.
Both are N:1 — fifteen orders a customer, four lines an order, one row
of the unique side looked up for each — so both are ``JoinLookup``.
Revenue is in exact decimal units, 10^-4 dollars, a 64-bit column from
the ``Map`` on (one line fits 32 bits, an order's seven do not).
``c_mktsegment`` arrives dictionary-coded, as a columnar reader hands
it. Every job scans the whole aggregate (about 11,600 rows) inside the
timed path, takes the report's ten rows at the edge and then frees
everything it stored."""

from __future__ import annotations

import numpy as np

import bigslice_tpu as bs
from bigslice_tpu.slicetype import Schema

#: Op kinds of this pipeline that must run their waves on the mesh: the
#: fused map-side groups of ``customer`` and ``orders`` (one kind),
#: that of ``lineitem``, the two join groups and the reduce side.
MESH_OPS = ("const_filter", "const_filter_map", "joinlookup_map",
            "joinlookup_map_prefixed", "reduce")

#: The dictionary of ``c_mktsegment`` (sorted, as a columnar writer
#: builds it): code = position.
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
            "MACHINERY")

#: The join this pipeline stands on. Referred to when the module loads,
#: so a package without it (before PR 34) stops here with its own
#: ``AttributeError``, before any work.
_JOIN = bs.JoinLookup

_BY_ORDER = Schema([np.int32, np.int32, np.int32])
_REVENUE = Schema([np.int32, np.int64])
_GROUPED = Schema([np.int32, np.int32, np.int32, np.int64])


# Module-level functions: program caches key on a function's identity.

def _by_order(custkey, orderkey, orderdate, shippriority, segment):
    return orderkey, orderdate, shippriority


def _revenue(orderkey, price, disc, shipdate):
    return orderkey, price.astype(np.int64) * (100 - disc)


def _grouped(orderkey, revenue, orderdate, shippriority):
    return orderkey, orderdate, shippriority, revenue


def _add(a, b):
    return a + b


class Data:
    """The batch, a column an attribute. ``keys`` is ``l_orderkey``;
    ``qty`` is ``l_quantity``, which Q3 does not read: the price was
    made from it, it stays on the host and is never uploaded."""

    CUSTOMER = ("c_custkey", "c_mktsegment")
    ORDERS = ("o_custkey", "o_orderkey", "o_orderdate", "o_shippriority")
    LINEITEM = ("keys", "price", "disc", "shipdate")

    def __init__(self, customer, orders, lineitem, qty, rows_per_shard,
                 segment, date):
        for names, cols in ((self.CUSTOMER, customer),
                            (self.ORDERS, orders),
                            (self.LINEITEM, lineitem)):
            for name, col in zip(names, cols):
                setattr(self, name, col)
        self.qty = qty
        self.segment, self.date = segment, date
        self.shards = tuple(
            -(-len(t[0]) // rows_per_shard)
            for t in (customer, orders, lineitem))
        # One predicate object each for every job of the run.
        self.in_segment = lambda custkey, seg: seg == segment
        self.ordered_before = (
            lambda custkey, orderkey, orderdate, prio: orderdate < date)
        self.shipped_after = (
            lambda orderkey, price, disc, shipdate: shipdate > date)

    @property
    def customer(self) -> tuple:
        return tuple(getattr(self, n) for n in self.CUSTOMER)

    @property
    def orders(self) -> tuple:
        return tuple(getattr(self, n) for n in self.ORDERS)

    @property
    def lineitem(self) -> tuple:
        """The four columns, cut to the rows every one of them (and the
        quantity they were made from) has."""
        cols = [getattr(self, n) for n in self.LINEITEM]
        rows = min(len(c) for c in cols + [self.qty])
        return tuple(c[:rows] for c in cols)


def make_data(cfg: dict, seed: int) -> Data:
    """``customer``, ``orders`` and ``lineitem`` — the columns Q3 reads
    — populated by the specification's section 4.2.3 rules
    (``config.json``: ``shapes``, ``assumed``), rows of every table in
    random order; exactly ``4 x orders`` lines for every seed."""
    rng = np.random.default_rng([abs(int(seed)), 3])
    sf = cfg["scale_factor"]
    customers = int(cfg["customers_per_sf"] * sf)
    orders = int(cfg["orders_per_sf"] * sf)
    top = int(cfg["lines_per_order_max"])

    c_custkey = np.arange(1, customers + 1)
    c_segment = rng.integers(0, len(SEGMENTS), customers)

    o = np.arange(orders, dtype=np.int64)
    o_orderkey = ((o >> 3) << 5 | (o & 7)) + 1
    # 1, 2, 4, 5, 7, 8, ...: every custkey but the multiples of 3.
    r = rng.integers(0, customers * 2 // 3, orders)
    o_custkey = (r // 2) * 3 + 1 + r % 2
    o_orderdate = rng.integers(0, int(cfg["orderdate_days"]) + 1, orders)
    o_priority = np.zeros(orders, np.int32)

    lines = rng.integers(1, top + 1, orders)
    # Nudge distinct random orders by one line until the total is exact.
    delta = orders * (top + 1) // 2 - int(lines.sum())
    step = 1 if delta > 0 else -1
    room = np.flatnonzero(lines < top if step > 0 else lines > 1)
    lines[rng.choice(room, abs(delta), replace=False)] += step
    n = int(lines.sum())
    l_orderkey = np.repeat(o_orderkey, lines)
    shipdate = np.repeat(o_orderdate, lines) + rng.integers(
        1, int(cfg["ship_after_days_max"]) + 1, n)
    qty = rng.integers(1, int(cfg["quantity_max"]) + 1, n)
    partkey = rng.integers(1, int(cfg["parts_per_sf"] * sf) + 1, n)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    disc = rng.integers(0, int(cfg["discount_pct_max"]) + 1, n)

    def shuffled(cols, count):
        order = rng.permutation(count)
        return tuple(c[order].astype(np.int32) for c in cols)

    *lineitem, qty = shuffled(
        (l_orderkey, qty * retail, disc, shipdate, qty), n)
    return Data(
        shuffled((c_custkey, c_segment), customers),
        shuffled((o_custkey, o_orderkey, o_orderdate, o_priority), orders),
        tuple(lineitem), qty, int(cfg["rows_per_shard"]),
        SEGMENTS.index(cfg["segment"]), int(cfg["date_day"]))


def work(cfg: dict, data: Data) -> dict:
    """What one job needs whatever implements it, from shapes alone:
    every input byte of the three tables read once and the aggregate's
    rows (about ``answer_rows_per_sf`` a scale factor) written once."""
    tables = (data.customer, data.orders, data.lineitem)
    answer = sum(ct.dtype.itemsize for ct in _GROUPED)
    groups = int(cfg["answer_rows_per_sf"] * cfg["scale_factor"])
    return {
        "input_rows": sum(len(t[0]) for t in tables),
        "least_bytes": sum(len(t[0]) * sum(c.dtype.itemsize for c in t)
                           for t in tables) + groups * answer,
    }


def _tables(orderkey, orderdate, priority, revenue, limit=10) -> dict:
    """The answer as the comparison wants it: three tables keyed by
    ``l_orderkey`` in key order, and the report's rows — the first
    ``limit`` by (revenue desc, o_orderdate asc, l_orderkey asc) — as
    rank -> ``l_orderkey``."""
    order = np.argsort(orderkey, kind="stable")
    key, date, prio, rev = (np.asarray(c)[order] for c in (
        orderkey, orderdate, priority, revenue))
    rev = rev.astype(np.int64)
    top = np.lexsort((key, date, -rev))[:limit]
    return {"revenue": (key, rev), "o_orderdate": (key, date),
            "o_shippriority": (key, prio),
            "top10": (np.arange(len(top)), key[top])}


def report(answers: dict) -> list:
    """Q3's ten rows as the user reads them: (l_orderkey, revenue in
    dollars, o_orderdate, o_shippriority), best first."""
    key, rev = answers["revenue"]
    at = np.searchsorted(key, answers["top10"][1])
    return [(int(key[i]), float(rev[i]) / 1e4,
             int(answers["o_orderdate"][1][i]),
             int(answers["o_shippriority"][1][i])) for i in at]


class Job:
    """One user job: fresh slices over the same rows. A job made with
    ``keep`` leaves the aggregate's own output stored until the window
    has closed and scans it once more then: what a later job of the
    session would read is still the answer."""

    def __init__(self, sess, data: Data, keep: bool):
        self.sess, self.data, self.keep = sess, data, keep
        self.answers: dict = {}
        self.results: list = []

    def steps(self):
        return (("build", self._build), ("run", self._run),
                ("scan", self._scan))

    def _build(self):
        d = self.data
        n_cust, n_ords, n_lines = d.shards
        cust = bs.Filter(bs.Const(n_cust, *d.customer), d.in_segment)
        ords = bs.Filter(bs.Const(n_ords, *d.orders), d.ordered_before)
        co = bs.Map(_JOIN(ords, cust), _by_order, out=_BY_ORDER)
        li = bs.Filter(bs.Const(n_lines, *d.lineitem), d.shipped_after)
        rev = bs.Map(li, _revenue, out=_REVENUE)
        lo = bs.Map(_JOIN(rev, co), _grouped, out=_GROUPED)
        self.slice = bs.Reduce(bs.Prefixed(lo, 3), _add)

    def _run(self):
        self.agg = self.sess.run(self.slice)
        self.results = [self.agg]

    def _scanned(self) -> dict:
        frames = [f.to_host() for f in self.agg.frames()]
        return _tables(*(
            np.concatenate([np.asarray(f.cols[j]) for f in frames])
            if frames else np.empty(0, ct.dtype)
            for j, ct in enumerate(self.agg.schema)))

    def _scan(self):
        self.answers = self._scanned()
        self.report = report(self.answers)

    def discard(self):
        if self.keep:
            self.agg.discard_inputs()
        else:
            self.agg.discard_graph()
            self.results = []

    def late_answers(self) -> dict:
        try:
            return self._scanned()
        finally:
            self.agg.discard()
            self.results = []


def lowering(sess, evidence, platform: str) -> dict:
    """What the executor picked, for an earlier line of the output:
    the newest ``join`` block of each join op."""
    ops = sess.telemetry_summary()["ops"]
    return {op.split("@")[0]: rec["join"]
            for op, rec in ops.items() if "join" in rec}


def counters(data: Data) -> dict:
    return {}


def close(data: Data) -> None:
    pass


# ------------------------------------------- the plain reference (numpy)

def _q3(data: Data, before=np.less, after=np.greater, segment_only=True,
        one_order_a_customer=False, drop_last_line=False,
        revenue_dtype=np.int64) -> dict:
    """Q3 in plain numpy on the host: sorted unique keys and
    ``np.searchsorted`` for the two joins, ``np.add.at`` into ``int64``
    for the sums. The keywords break one guarantee each, for the
    controls."""
    c_key, c_seg = data.customer
    o_cust, o_key, o_date, o_prio = data.orders
    l_key, price, disc, ship = data.lineitem
    keep = before(o_date, data.date)
    if segment_only:
        keep &= np.isin(o_cust, c_key[c_seg == data.segment])
    if one_order_a_customer:
        # A 1:1 join keeps one order a customer: the largest key.
        at = np.flatnonzero(keep)
        at = at[np.lexsort((o_key[at], o_cust[at]))]
        last = np.r_[o_cust[at][1:] != o_cust[at][:-1], True]
        keep = np.zeros_like(keep)
        keep[at[last]] = True
    order = np.flatnonzero(keep)
    order = order[np.argsort(o_key[order], kind="stable")]
    okeys = o_key[order]
    lines = np.flatnonzero(after(ship, data.date))
    slot = np.minimum(np.searchsorted(okeys, l_key[lines]),
                      len(okeys) - 1)
    hit = okeys[slot] == l_key[lines]
    lines, slot = lines[hit], slot[hit]
    if drop_last_line:
        lines, slot = lines[:-1], slot[:-1]
    revenue = np.zeros(len(okeys), np.int64)
    np.add.at(revenue, slot,
              price[lines].astype(np.int64) * (100 - disc[lines]))
    present = np.bincount(slot, minlength=len(okeys)) > 0
    revenue = revenue.astype(revenue_dtype).astype(np.int64)
    return _tables(okeys[present], o_date[order][present],
                   o_prio[order][present], revenue[present])


def reference(cfg: dict, data: Data) -> dict:
    return _q3(data)


def controls(cfg: dict, data: Data) -> dict:
    """The reference with one stated guarantee broken each
    (``config.json``: ``controls``)."""
    return {
        "row_dropped": _q3(data, drop_last_line=True),
        "sums_in_int32": _q3(data, revenue_dtype=np.int32),
        "date_inclusive": _q3(data, before=np.less_equal,
                              after=np.greater_equal),
        "segment_ignored": _q3(data, segment_only=False),
        "one_order_a_customer": _q3(data, one_order_a_customer=True),
    }
