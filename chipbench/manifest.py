"""``BENCHMARK.json`` and the data files it names, resolved by name.

A cell is one entry of ``workloads``: a configuration file
``configs/<config>.json``, a traffic file ``traffic/<traffic>.json`` whose
``driver`` names ``drivers/<driver>.py``, and the per-layer metrics whose
files ``metrics/<metric>.json`` name a reader ``reducers/<reducer>.py``.
Adding a cell, a mix or a metric is adding files and entries: nothing here
lists them.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MODULE_RE = re.compile(r"^[a-z][a-z0-9_]*$")


def load_json(path: str) -> dict:
    """Read one JSON file."""
    with open(path) as f:
        return json.load(f)


def data_file(kind: str, name: str, bench_dir: str = BENCH_DIR) -> str:
    """Path of the data file of ``kind`` (configs, traffic, metrics)."""
    if not NAME_RE.match(name):
        raise ValueError(f"{kind} name {name!r} is not a valid name")
    return os.path.join(bench_dir, kind, name + ".json")


def module(package: str, name: str):
    """Import ``chipbench.<package>.<name>`` (a driver, reducer, generator)."""
    if not MODULE_RE.match(name):
        raise ValueError(f"{package} module name {name!r} is not valid")
    return importlib.import_module(f"chipbench.{package}.{name}")


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # manifest entries of the end-to-end metrics it reports
    per_layer: list  # (manifest entry, metric file) of its per-layer metrics


def reports(metric: dict, cell: str) -> bool:
    """Whether a metric entry is reported in ``cell``."""
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: str = ROOT) -> dict:
    """The parsed ``BENCHMARK.json`` at ``root``."""
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _one(entries: list, name: str, what: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"no single {what} named {name!r} in "
                         "BENCHMARK.json")
    return found[0]


def cell(bench: dict, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    """Resolve one workload by name; raises if any file it names is absent.

    The configuration is read from the ``file`` its manifest entry gives
    (relative to the directory above ``bench_dir``); traffic and metric
    files are found by name.
    """
    w = _one(bench["workloads"], name, "workload")
    conf = _one(bench["configs"], w["config"], "configuration")
    config = load_json(os.path.join(os.path.dirname(bench_dir),
                                    conf["file"]))
    traffic = load_json(data_file("traffic", w["traffic"], bench_dir))
    e2e = [m for m in bench["end_to_end"] if reports(m, name)]
    per_layer = [(m, load_json(data_file("metrics", m["name"], bench_dir)))
                 for m in bench["per_layer"] if reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)
