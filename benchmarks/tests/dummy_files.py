"""A configuration, a traffic mix, a cell and a per-layer metric added
as FILES ONLY plus entries in a ``BENCHMARK.json`` — what a later PR
does. The harness under test is the repository's own."""

import json
import os

PIPELINE = '''
import numpy as np
import bigslice_tpu as bs

MESH_OPS = ("const", "reduce")


def _add(a, b):
    return a + b


class Data:
    pass


def make_data(cfg, seed):
    d = Data()
    rng = np.random.default_rng(abs(int(seed)))
    d.keys = rng.integers(0, cfg["keys"], cfg["rows"], dtype=np.int32)
    d.vals = np.ones(cfg["rows"], np.int32)
    return d


def work(cfg, data):
    return {"input_rows": len(data.keys), "least_bytes": 8 * len(data.keys)}


class Job:
    def __init__(self, sess, data, keep):
        self.sess, self.data = sess, data
        self.answers, self.results = {}, []

    def steps(self):
        return (("run", self._run),)

    def _run(self):
        res = self.sess.run(bs.Reduce(
            bs.Const(4, self.data.keys, self.data.vals), _add))
        frames = [f.to_host() for f in res.frames()]
        cols = [np.concatenate([np.asarray(f.cols[j]) for f in frames])
                for j in range(2)]
        order = np.argsort(cols[0])
        self.answers["counts"] = (cols[0][order], cols[1][order])
        self.res = res

    def discard(self):
        self.res.discard_graph()

    def late_answers(self):
        return {}


def lowering(sess, evidence, platform):
    return {}


def counters(data):
    return {"dummy_seen": 7}


def close(data):
    pass


def reference(cfg, data):
    uniq, counts = np.unique(data.keys, return_counts=True)
    return {"counts": (uniq, counts.astype(np.int32))}


def controls(cfg, data):
    uniq, counts = np.unique(data.keys[:-1], return_counts=True)
    return {"row_dropped": {"counts": (uniq, counts.astype(np.int32))}}
'''

FLOAT_PIPELINE = '''
import ml_dtypes
import numpy as np
import bigslice_tpu as bs

MESH_OPS = ("const_map", "reduce")
SCALE = 0.5


def _add(a, b):
    return a + b


def _scale(k, v):
    return k, v * SCALE


class Data:
    pass


def make_data(cfg, seed):
    d = Data()
    rng = np.random.default_rng(abs(int(seed)))
    d.keys = rng.integers(0, cfg["keys"], cfg["rows"], dtype=np.int32)
    d.vecs = rng.standard_normal((cfg["rows"], cfg["dim"]), np.float32)
    return d


def work(cfg, data):
    return {"input_rows": len(data.keys), "least_bytes": data.vecs.nbytes}


class Job:
    def __init__(self, sess, data, keep):
        self.sess, self.data = sess, data
        self.answers, self.results = {}, []

    def steps(self):
        return (("run", self._run),)

    def _run(self):
        res = self.sess.run(bs.Reduce(bs.Map(
            bs.Const(4, self.data.keys, self.data.vecs), _scale), _add))
        frames = [f.to_host() for f in res.frames()]
        cols = [np.concatenate([np.asarray(f.cols[j]) for f in frames])
                for j in range(2)]
        order = np.argsort(cols[0])
        self.answers["sums"] = (cols[0][order], cols[1][order])
        self.res = res

    def discard(self):
        self.res.discard_graph()

    def late_answers(self):
        return {}


def lowering(sess, evidence, platform):
    return {}


def counters(data):
    return {"dummy_seen": 7}


def close(data):
    pass


def _sums(keys, vecs, dtype):
    uniq = np.unique(keys)
    out = np.zeros((len(uniq), vecs.shape[1]), dtype)
    np.add.at(out, np.searchsorted(uniq, keys),
              vecs.astype(dtype) * dtype(SCALE))
    return uniq, out


def reference(cfg, data):
    return {"sums": _sums(data.keys, data.vecs, np.float64)}


def controls(cfg, data):
    uniq, low = _sums(data.keys, data.vecs, ml_dtypes.bfloat16)
    return {"lower_precision_bf16": {"sums": (uniq, low.astype(np.float32))},
            "row_dropped": {"sums": _sums(data.keys[:-1], data.vecs[:-1],
                                          np.float64)}}
'''

#: Float sums compared within a tolerance: float32 against the float64
#: reference, tight enough that bfloat16 arithmetic fails it.
FLOAT_COMPARE = {"sums": {
    "rtol": 1e-5, "atol": 1e-5,
    "why": "float32 sums of about 64 vectors in an order the sort "
           "pipeline picks, against float64"}}

METRIC = '''
def read(r):
    return r.window.counters["dummy_seen"] * 6
'''

RATE = '''
def read(r):
    return r.stats.window_rate(
        len(r.window.jobs) * r.work["input_rows"], r.window.window_s)
'''

SETUP = '''
def read(r):
    return r.window.setup_s
'''


def write(root: str) -> None:
    def put(rel, text):
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fp:
            fp.write(text)

    put("bm/configs/dummy/config.json", json.dumps(
        {"rows": 4096, "keys": 64, "rehearsal": {"rows": 512}}))
    put("bm/configs/dummy/pipeline.py", PIPELINE)
    put("bm/configs/dummyf/config.json", json.dumps(
        {"rows": 4096, "keys": 64, "dim": 8, "compare": FLOAT_COMPARE,
         "rehearsal": {"rows": 512}}))
    put("bm/configs/dummyf/pipeline.py", FLOAT_PIPELINE)
    put("bm/traffic/one.json", json.dumps(
        {"loop": "closed", "clients": 1}))
    put("bm/metrics/dummy_metric.py", METRIC)
    put("bm/metrics/dummy_rate.py", RATE)
    put("bm/metrics/setup_s.py", SETUP)
    put("BENCHMARK.json", json.dumps({
        "command": ["python3", "bm/run.py"], "paths": ["bm"],
        "run_seconds": 1,
        "configs": [{"name": "dummy", "source": "none",
                     "file": "bm/configs/dummy/config.json",
                     "reduced": [], "why": "discovery test"},
                    {"name": "dummyf", "source": "none",
                     "file": "bm/configs/dummyf/config.json",
                     "reduced": [], "why": "float answers"}],
        "workloads": [{"name": "dummy.cell", "config": "dummy",
                       "traffic": "one", "chips": 1, "why": "test"},
                      {"name": "dummy.float", "config": "dummyf",
                       "traffic": "one", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "dummy_rate", "unit": "rows/s", "better": "higher",
             "bound": 0.1, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "dummy_metric", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "dummy",
             "moves": "dummy_rate"},
            {"name": "elsewhere", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "dummy",
             "moves": "dummy_rate", "workloads": ["another.cell"]}],
    }))
