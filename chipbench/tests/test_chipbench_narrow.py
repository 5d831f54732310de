"""The reader of the engine's narrow rounds, ``rdc_narrow_pct.*``, on
resolve spans laid out by hand and on a traced run of the harness on the
CPU."""

import argparse

import pytest

from chipbench import harness, manifest, trace
from chipbench.reducers import narrow_share
from chipbench.tests.test_chipbench_rehearsal import small  # noqa: F401
from chipbench.tests.test_chipbench_spans import _traced_run

OPEN = "rw256-n4m.open-mixed-k10"
CELL = argparse.Namespace(name=OPEN)


def _read(metric, tr):
    spec = manifest.load_json(manifest.data_file("metrics", metric))
    return narrow_share.read(spec, tr, {}, CELL, "TPU v5 lite")


def _trace(*resolves):
    host = [trace.Event("chipbench.window", 0.0, 1000.0, {}, "main")]
    host += [trace.Event("paris.flush.resolve", 100.0 * i, 100.0 * i + 50,
                         dict(qn=3, reads=300, rows=1000, **stats), "t")
             for i, stats in enumerate(resolves)]
    return trace.Trace([], [], host)


@pytest.mark.parametrize("metric", ["rdc_narrow_pct.open",
                                    "rdc_narrow_pct.closed"])
def test_narrow_share_of_the_rounds(metric):
    tr = _trace(dict(rounds=4, narrow_rounds=3),
                dict(rounds=280, narrow_rounds=250),
                dict(rounds=1, narrow_rounds=0))
    assert _read(metric, tr) == pytest.approx(100 * 253 / 285)


def test_narrow_share_reads_nothing_without_the_count():
    # The program before narrow rounds: resolve spans without the count.
    assert _read("rdc_narrow_pct.open", _trace(dict(rounds=7))) is None
    assert _read("rdc_narrow_pct.open", _trace()) is None
    assert _read("rdc_narrow_pct.open", None) is None


def test_traced_rehearsal_reads_the_narrow_share(small, capsys, tmp_path,
                                                 monkeypatch):
    # Batches of 16 rows, most holding at most 8 real queries: their
    # rounds run narrow from the first.
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    res = _traced_run(capsys, OPEN, 2.0)
    assert res["correct"] is True, res["checks"]
    value = res["metrics"]["rdc_narrow_pct.open"]["value"]
    assert 0 < value <= 100
