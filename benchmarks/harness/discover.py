"""Find a cell's files by the names in ``BENCHMARK.json``.

    cell ``workloads[i]``        -> its ``config`` and ``traffic`` names
    config ``configs[j].file``   -> the configuration as it is run (JSON);
                                    its ``pipeline`` key (default
                                    ``pipeline.py``, relative to the file)
                                    names the data generator, job builder
                                    and plain reference beside it; its
                                    ``compare`` block the tables compared
                                    within a tolerance (harness/compare.py)
    traffic ``<name>``           -> ``<path>/traffic/<name>.json``
    metric ``<name>``            -> ``<path>/metrics/<name>.py`` with
                                    ``read(reading) -> number | None``
                                    (end-to-end and per-layer alike)

``<path>`` is each directory of ``paths`` in turn. Adding a
configuration, a cell or a metric is adding files and entries — never
an edit here."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from . import compare


class NotFound(LookupError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    cfg: dict            # the configuration as it is run
    tolerances: dict     # table -> compare.Tolerance; the rest is exact
    pipeline: object     # module: make_data / Job / reference / ...
    traffic_name: str
    traffic: dict
    end_to_end: list     # (entry, reader module) pairs for this cell
    per_layer: list      # the same, for the per-layer metrics


def _load_json(path: str) -> dict:
    with open(path) as fp:
        return json.load(fp)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise NotFound(f"{path} is not a module")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _find(root: str, bench: dict, *parts: str) -> str:
    tried = []
    for p in bench["paths"]:
        path = os.path.join(root, p, *parts)
        if os.path.exists(path):
            return path
        tried.append(path)
    raise NotFound(f"none of {tried} exists")


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise NotFound(f"BENCHMARK.json names no {what} {name!r}: "
                   f"{[e['name'] for e in entries]}")


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def find_cell(root: str, name: str, rehearsal: bool = False) -> Cell:
    """Everything ``name`` needs, from ``<root>/BENCHMARK.json`` down.
    ``rehearsal`` overlays the configuration's own ``rehearsal`` sizes
    (tiny, for a CPU run that proves control flow only). A ``compare``
    block that ``compare.tolerances`` refuses raises ``ValueError``
    here, before any work."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = _by_name(bench["workloads"], name, "workload")
    config = _by_name(bench["configs"], entry["config"], "config")
    cfg_path = os.path.join(root, config["file"])
    cfg = _load_json(cfg_path)
    if rehearsal:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
    tolerances = compare.tolerances(cfg)
    pipeline = _load_module(
        os.path.join(os.path.dirname(cfg_path),
                     cfg.get("pipeline", "pipeline.py")),
        "bench_pipeline_" + entry["config"].replace("-", "_"))
    traffic = _load_json(
        _find(root, bench, "traffic", entry["traffic"] + ".json"))

    def readers(metrics):
        return [
            (m, _load_module(
                _find(root, bench, "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_")))
            for m in metrics if _in_cell(m, name)
        ]

    return Cell(
        name=name, chips=int(entry["chips"]),
        config_name=entry["config"], cfg=cfg, tolerances=tolerances,
        pipeline=pipeline,
        traffic_name=entry["traffic"], traffic=traffic,
        end_to_end=readers(bench["end_to_end"]),
        per_layer=readers(bench["per_layer"]),
    )
