"""Find a cell, its configuration, its traffic mix and its metrics by name.

Everything the harness runs is named in ``BENCHMARK.json`` at the root of
the checkout and lives in files of its own under ``chipbench/``:

* ``configs/<config>.json``  — the sizes as run (``file`` in the config
  entry), with ``runner`` naming the code in ``runners/`` that runs it and
  ``reference`` naming its plain reference in ``reference/``;
* ``traffic/<traffic>.json`` — the parameters of one traffic mix;
* ``layer_metrics/<metric>.py`` — one reader per per-layer metric.

A later cell, mix or metric is added by adding files and entries; nothing
here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH_DIR = ROOT / "chipbench"


@dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict          # the configuration file's contents
    mix: dict             # the traffic file's contents
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if e2e_names is None:
        return True
    return metric["moves"] in e2e_names


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    with open(root / centry["file"]) as f:
        config = json.load(f)
    with open(root / "chipbench" / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, config_name=w["config"], traffic_name=w["traffic"],
                chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=per_layer)


def _load_file(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric_reader(name: str, root: Path = ROOT):
    """The module ``layer_metrics/<name>.py``; it defines ``read(ctx)``."""
    return _load_file(root / "chipbench" / "layer_metrics" / f"{name}.py",
                      f"chipbench_metric_{name.replace('.', '_')}")


def load_runner(name: str):
    return importlib.import_module(f"chipbench.runners.{name}")


def load_reference(name: str):
    return importlib.import_module(f"chipbench.reference.{name}")
