"""The readers of the program's named scopes and spans, against numbers
worked out apart from them.

Small traces laid out event by event (times in ns) check the interval
arithmetic; a TPU trace recorded before the scopes existed checks the
op-path decoder, and one recorded with them every new metric of the open
cell; a traced run of the harness on the CPU checks that the batcher's
and the build's spans reach the readers with their arguments.
"""

import argparse
import gzip
import json
import os
import time

import pytest

from chipbench import harness, manifest, spans, trace, xplane_meta
from chipbench.reducers import flush_args, idle_in_span, scoped_time, \
    span_time
from chipbench.tests.test_chipbench_rehearsal import small  # noqa: F401

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"
OPEN = "rw256-n4m.open-mixed-k10"
BUILD = "rw256-n4m.build"
CELL = argparse.Namespace(name=OPEN)


def _ev(name, start, end, where=DEV, **stats):
    return trace.Event(name, float(start), float(end), stats, where)


def _spec(metric):
    return manifest.load_json(manifest.data_file("metrics", metric))


def test_interval_arithmetic():
    assert spans.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [[0, 3],
                                                              [5, 10]]
    assert spans.length([(0, 4), (2, 6), (10, 11)]) == 7
    assert spans.overlap([(0, 4), (6, 10)], [(3, 7), (9, 20)]) == 3


@pytest.fixture
def engine_trace(monkeypatch):
    """Two batches: a while op (no op path, as on the TPU) over its body's
    ops, a selection op, and one fallback op in the second batch."""
    paths = {
        "sort.2": "jit(_index_engine)/paris.select/top_k",
        "fusion.7": "jit(_index_engine)/paris.rdc/while/body/gather",
        "euclid": "jit(_index_engine)/paris.rdc/while/body/pallas_call",
        "cond.1": "jit(_index_engine)/cond",
        "fb.3": "jit(_index_engine)/cond/branch_1_fun/paris.fallback/while"
                "/body/gather",
        "lbc": "jit(_index_engine)/jit(lower_bound)/pallas_call",
    }
    monkeypatch.setattr(xplane_meta, "for_cell", lambda cell: paths)
    ops = [
        _ev("lbc", 0, 100), _ev("sort.2", 100, 400),
        _ev("while.10", 400, 700),  # no path: its body stands for it
        _ev("fusion.7", 410, 500), _ev("euclid", 480, 690),
        _ev("cond.1", 700, 710),
        _ev("lbc", 1000, 1100), _ev("sort.2", 1100, 1400),
        _ev("fusion.7", 1400, 1450), _ev("cond.1", 1450, 1700),
        _ev("fb.3", 1460, 1690),
    ]
    host = [_ev("chipbench.window", 0, 2000, where="main")]
    return trace.Trace(ops, [], host)


def test_scoped_time_is_the_union_per_batch(engine_trace):
    counters = {"batches": 2}
    read = lambda m: scoped_time.read(  # noqa: E731
        _spec(m), engine_trace, counters, CELL, "TPU v5 lite")
    assert read("select_ms_per_batch.open") == pytest.approx(600 / 2 / 1e6)
    # [410, 690] once, though fusion.7 and euclid overlap, then 50 ns.
    assert read("rdc_ms_per_batch.open") == pytest.approx(330 / 2 / 1e6)
    assert read("fallback_ms_per_batch.open") == pytest.approx(
        230 / 2 / 1e6)


def test_scoped_time_reads_zero_or_nothing(engine_trace, monkeypatch):
    spec = _spec("fallback_ms_per_batch.closed")
    no_fallback = trace.Trace(
        [e for e in engine_trace.ops if e.name != "fb.3"], [],
        [_ev("chipbench.window", 0, 2000, where="main")])
    assert scoped_time.read(spec, no_fallback, {"batches": 2}, CELL,
                            "TPU v5 lite") == 0.0
    # A program without the scopes: nothing to read, not 0.
    monkeypatch.setattr(xplane_meta, "for_cell", lambda cell: {
        "sort.2": "jit(_index_engine)/top_k"})
    assert scoped_time.read(spec, engine_trace, {"batches": 2}, CELL,
                            "TPU v5 lite") is None
    assert scoped_time.read(spec, None, {"batches": 2}, CELL, "") is None


@pytest.fixture
def flush_trace():
    ops = [_ev("sort.2", 150, 400), _ev("sort.2", 620, 900)]
    host = [
        _ev("chipbench.window", 0, 1000, where="main"),
        _ev("paris.flush", 100, 500, where="t", qn=3, bucket=4,
            wait_ms_sum=6.0, wait_ms_max=3.0),
        _ev("paris.flush.resolve", 450, 500, where="t", qn=3, reads=300,
            rounds=2, rows=1000),
        _ev("paris.flush", 600, 950, where="t", qn=1, bucket=4,
            wait_ms_sum=10.0, wait_ms_max=10.0),
        _ev("paris.flush.resolve", 920, 950, where="t", qn=1, reads=500,
            rounds=5, rows=1000),
    ]
    return trace.Trace(ops, [], host)


def test_flush_span_arguments(flush_trace):
    read = lambda m: flush_args.read(  # noqa: E731
        _spec(m), flush_trace, {}, CELL, "TPU v5 lite")
    assert read("rdc_rounds_per_batch.open") == pytest.approx(3.5)
    assert read("raw_reads_pct.closed") == pytest.approx(
        100 * 800 / (4 * 1000))
    assert read("queue_wait_ms.open") == pytest.approx(16 / 4)
    # No spans (a program without them): nothing to read.
    bare = trace.Trace(flush_trace.ops, [],
                       [_ev("chipbench.window", 0, 1000, where="main")])
    assert flush_args.read(_spec("queue_wait_ms.open"), bare, {}, CELL,
                           "") is None


def test_idle_inside_flush_spans(flush_trace):
    # Idle gaps [0, 150], [400, 620], [900, 1000]; flushes cover
    # [100, 500] and [600, 950]: 50 + 100 + 20 + 50 = 220 ns of 1,000.
    got = idle_in_span.read(_spec("idle_in_flush_pct.open"), flush_trace,
                            {}, CELL, "TPU v5 lite")
    assert got == pytest.approx(22.0)


def test_build_span_time_per_build():
    host = [_ev("chipbench.window", 0, 1000, where="main"),
            _ev("paris.build.read", 0, 50, where="main"),
            _ev("paris.build.convert", 50, 300, where="w1"),
            _ev("paris.build.convert", 200, 400, where="w2"),
            _ev("paris.build.construct", 400, 450, where="main"),
            _ev("paris.build.flush", 450, 500, where="main"),
            _ev("paris.build.finalize", 500, 600, where="main"),
            _ev("paris.build.assemble", 600, 800, where="main")]
    tr = trace.Trace([], [], host)
    counters = {"builds": 2}
    assert span_time.read(_spec("build_convert_s.build"), tr, counters,
                          CELL, "") == pytest.approx(350 / 2 / 1e9)
    assert span_time.read(_spec("build_tail_s.build"), tr, counters, CELL,
                          "") == pytest.approx(400 / 2 / 1e9)
    assert span_time.read(_spec("build_tail_s.build"), tr, {}, CELL,
                          "") is None


def test_decoder_maps_recorded_ops_to_their_paths():
    with gzip.open(os.path.join(DATA, "rw256-open-k10.xplane.pb.gz")) as f:
        paths = xplane_meta.from_bytes(f.read())
    sort = [n for n in paths if n.startswith("%sort.2 = ")]
    assert len(sort) == 1
    assert paths[sort[0]] == "jit(_index_engine)/top_k"
    lbc = [p for n, p in paths.items()
           if n.startswith("%lower_bound_sq_batch_pallas.1 = ")]
    assert lbc == ["jit(_index_engine)/jit(lower_bound_sq_batch_pallas)"
                   "/pallas_call"]


def test_trace_file_is_the_newest_of_the_cell(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    assert xplane_meta.trace_file(OPEN) is None
    assert xplane_meta.for_cell(OPEN) == {}
    for stamp in ("2026_01_01_00_00_00", "2026_01_02_00_00_00"):
        d = tmp_path / OPEN / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
    assert xplane_meta.trace_file(OPEN).endswith(
        os.path.join("2026_01_02_00_00_00", "host.xplane.pb"))
    assert xplane_meta.for_cell(OPEN) == {}  # an empty XSpace


def _traced_run(capsys, workload, seconds):
    args = argparse.Namespace(workload=workload, seed=2**31 + 29,
                              seconds=seconds, trace=1)
    assert harness.run(args, time.perf_counter(),
                       check=lambda chips: {"platform": "cpu",
                                            "kind": "TPU v5 lite",
                                            "count": chips}) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload, names", [
    (OPEN, ["rdc_rounds_per_batch.open", "raw_reads_pct.open",
            "queue_wait_ms.open"]),
    (BUILD, ["build_convert_s.build", "build_tail_s.build"]),
])
def test_traced_rehearsal_reads_the_program_spans(small, capsys,
                                                   tmp_path, monkeypatch,
                                                   workload, names):
    # On the CPU the trace holds no TPU plane: only the host spans'
    # readers find something, and the device readers stay silent.
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    res = _traced_run(capsys, workload, 2.0 if workload == OPEN else 1.0)
    assert res["correct"] is True, res["checks"]
    for name in names:
        value = res["metrics"][name]["value"]
        assert value > 0 and value == value, (name, value)
    for name in ("select_ms_per_batch.open", "idle_in_flush_pct.open"):
        assert name not in res["metrics"]


SCOPED = os.path.join(DATA, "rw256-open-k10-scoped.xplane.pb.gz")


@pytest.fixture(scope="module")
def scoped_recorded():
    from jax.profiler import ProfileData
    with gzip.open(SCOPED) as f:
        buf = f.read()
    return (trace.from_profile(ProfileData.from_serialized_xspace(buf)),
            xplane_meta.from_bytes(buf))


def test_recorded_scoped_trace_reads_every_new_metric(scoped_recorded,
                                                      monkeypatch):
    # The --trace 1 window of rw256-n4m.open-mixed-k10 on the instrumented
    # program, 4 s at 44 queries/s on one TPU v5 lite: 7 batches, none of
    # which took the fallback. Numbers from a plain loop over
    # ProfileData's events and the decoded op paths: the union of the ops
    # under paris.select is 4,236,857,006 ns (%sort.2 alone 4,200,491,076
    # ns; the rest is the negation, iota and padding of the selection);
    # under paris.rdc 755,608,702 ns (%while.10, which carries no path,
    # 755,670,400 ns); no op under paris.fallback. The 7 paris.flush
    # spans hold 176 queries that waited 66,095 ms in all; their
    # resolve spans 2,231,980 reads of 176 x 4,194,304 rows in 126 rounds.
    tr, paths = scoped_recorded
    monkeypatch.setattr(xplane_meta, "for_cell", lambda cell: paths)
    cell = manifest.cell(manifest.load(), OPEN)
    counters = {"batches": 7}
    want = {
        "select_ms_per_batch.open": 4236.857006 / 7,
        "rdc_ms_per_batch.open": 755.608702 / 7,
        "fallback_ms_per_batch.open": 0.0,
        "rdc_rounds_per_batch.open": 126 / 7,
        "raw_reads_pct.open": 100 * 2231980 / (176 * 4194304),
        "queue_wait_ms.open": 375.5395049659133,
        "idle_in_flush_pct.open": 100 * 46787281 / 5422366337,
    }
    for entry, spec in cell.per_layer:
        if entry["name"] not in want:
            continue
        reader = manifest.module("reducers", spec["reducer"])
        got = reader.read(spec, tr, counters, cell, "TPU v5 lite")
        assert got == pytest.approx(want.pop(entry["name"]), rel=1e-9,
                                    abs=1e-12), entry["name"]
    assert not want  # every new metric of the cell was read
