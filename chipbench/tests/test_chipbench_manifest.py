"""BENCHMARK.json and the data files it names hold together."""

import hashlib
import json
import os
import shutil

import pytest

from chipbench import manifest

BENCH = manifest.load()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve_by_name(name):
    cell = manifest.cell(BENCH, name)
    assert cell.chips in (1, 4)
    assert cell.config["data"]["num_series"] > 0
    manifest.module("drivers", cell.traffic["driver"])
    for entry, spec in cell.per_layer:
        manifest.module("reducers", spec["reducer"])
        for key in ("layer", "unit", "moves"):
            assert spec[key] == entry[key], (entry["name"], key)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_names_and_units_use_only_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [w["config"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    for n in names:
        assert manifest.NAME_RE.match(n), n
    for m in metrics:
        assert manifest.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for group in (BENCH["configs"], BENCH["workloads"], metrics):
        assert len({e["name"] for e in group}) == len(group)
    texts = ([e["why"] for e in BENCH["configs"] + BENCH["workloads"]]
             + [c["source"] for c in BENCH["configs"]]
             + [m["layer"] for m in BENCH["per_layer"]])
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert manifest.reports(moved, cell), (m["name"], cell)


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_need_no_edit(tmp_path):
    """A cell, a mix and a metric are added as new files plus entries."""
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(manifest.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digest(bench_dir)
    bench = json.loads(json.dumps(BENCH))
    base = manifest.cell(bench, CELLS[0])
    config = dict(base.config, name="rw512-n2m")
    config["data"] = dict(config["data"], series_length=512,
                          num_series=2_097_152)
    traffic = dict(base.traffic, rate_qps=10.0)
    metric = {"layer": "device", "unit": "%", "moves": "latency_p95_ms",
              "reducer": "device_idle"}
    (bench_dir / "configs" / "rw512-n2m.json").write_text(json.dumps(config))
    (bench_dir / "traffic" / "open-mixed-k10-q10.json").write_text(
        json.dumps(traffic))
    (bench_dir / "metrics" / "device_idle_pct.new.json").write_text(
        json.dumps(metric))
    bench["configs"].append({"name": "rw512-n2m", "source": "test",
                             "file": "chipbench/configs/rw512-n2m.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "rw512-n2m.new", "config": "rw512-n2m",
                               "traffic": "open-mixed-k10-q10", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("latency_p50_ms", "latency_p95_ms"):
            m["workloads"].append("rw512-n2m.new")
    bench["per_layer"].append(dict(metric, name="device_idle_pct.new",
                                   better="lower", source="device_trace",
                                   workloads=["rw512-n2m.new"]))
    cell = manifest.cell(bench, "rw512-n2m.new", str(bench_dir))
    assert cell.config["data"]["series_length"] == 512
    assert cell.traffic["rate_qps"] == 10.0
    assert [e["name"] for e, _ in cell.per_layer] == ["device_idle_pct.new"]
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "latency_p50_ms", "latency_p95_ms"}
    after = _digest(bench_dir)
    assert {k: after[k] for k in before} == before
