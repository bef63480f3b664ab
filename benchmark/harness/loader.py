"""Finds everything by the names in BENCHMARK.json. A cell names its
configuration and its traffic mix; a configuration names its family and its
driver; a traffic mix names its generator; a per-layer metric is a module of
its own name. Nothing here knows a cell, a model or a metric by name."""
import importlib.util
import json
import os
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PACKAGE = "benchmark"


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list = field(default_factory=list)   # metric entries it reports
    per_layer: list = field(default_factory=list)
    root: str = ROOT

    def module(self, kind, name):
        """benchmark/<kind>/<name>.py beside this cell's BENCHMARK.json, else
        the repository's own (a fixture tree adds files and shares the rest)."""
        for root in dict.fromkeys((self.root, ROOT)):
            try:
                return load_module(kind, name, root)
            except FileNotFoundError:
                if root == ROOT:
                    raise


def load_benchmark(root=ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(root, rel):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def traffic_path(name) -> str:
    return f"{PACKAGE}/traffic/{name}.json"


def _in_cell(metric, cell_name) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name, root=ROOT, bench=None) -> Cell:
    bench = bench or load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"it has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {name!r} names configuration "
                       f"{w['config']!r}, which BENCHMARK.json lacks")
    return Cell(
        name=name, chips=int(w["chips"]), why=w["why"],
        config_name=w["config"],
        config=_read_json(root, configs[w["config"]]["file"]),
        traffic_name=w["traffic"],
        traffic=_read_json(root, traffic_path(w["traffic"])),
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in bench["per_layer"] if _in_cell(m, name)],
        root=root)


def load_module(kind, name, root=ROOT):
    """benchmark/<kind>/<name>.py as a module. By path, because a metric's
    name may hold a dot (``host_share.batch``)."""
    path = os.path.join(root, PACKAGE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    mod_name = f"{PACKAGE}.{kind}.{name.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
