"""Finds the benchmark's parts by name: a cell in ``BENCHMARK.json``, its
configuration in the file the entry names, its traffic in
``traffic/<traffic>.json``, and code in ``<kind>/<name>.py`` beside this
file (drivers, analytics, references, per-layer metric readers)."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class UnknownName(LookupError):
    pass


def load(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise UnknownName(f"no {kind}/{name}.py in {HERE}")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(spec: dict, workload: str, root: Path = ROOT) -> dict:
    """The workload entry named ``workload``, with ``config_file`` (the
    configuration's file, read), ``traffic_file`` (its traffic, read),
    ``end_to_end`` and ``per_layer`` (the metrics that this cell
    reports)."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise UnknownName(f"no workload {workload!r} in BENCHMARK.json")
    w = dict(by_name[workload])
    config = {c["name"]: c for c in spec["configs"]}[w["config"]]
    w["config_file"] = read_json(root / config["file"])
    traffic = HERE / "traffic" / f"{w['traffic']}.json"
    if not traffic.is_file():
        raise UnknownName(f"no traffic/{w['traffic']}.json in {HERE}")
    w["traffic_file"] = read_json(traffic)

    def mine(m):
        return workload in m.get("workloads", [workload])
    w["end_to_end"] = [m for m in spec["end_to_end"] if mine(m)]
    w["per_layer"] = [m for m in spec["per_layer"] if mine(m)]
    return w
