"""Trace reduction against numbers worked out apart from it.

A small trace laid out event by event (times in ns): two device ops that
overlap, three launches of the distance kernel, two runs of the engine's
program, and host spans, inside a 1,000 ns window.

A recorded TPU trace: the ``--trace 1`` window of
``rw256-n4m.open-mixed-k10`` at 52 queries/s for 10 s on one TPU v5 lite
(13 batches of 64). Its numbers were read from the file with a plain
loop over ``jax.profiler.ProfileData``, not with this module.
"""

import gzip
import os

import pytest

from chipbench import manifest, trace
from chipbench.reducers import device_idle, lbc_roofline

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "rw256-open-k10.xplane.pb.gz")

DEV = "/device:TPU:0"


def _ev(name, start, end, where=DEV, **stats):
    return trace.Event(name, float(start), float(end), stats, where)


@pytest.fixture
def tr():
    ops = [
        _ev("fusion.1", 100, 300, hlo_category="loop fusion",
            tf_op="jit(_index_engine)/while/body/sub"),
        _ev("_lb_kernel_batch", 200, 400, hlo_category="custom-call"),
        _ev("sort.7", 450, 600, hlo_category="sort"),
        _ev("_euclid_kernel", 600, 650, hlo_category="custom-call",
            tf_op="jit(_index_engine)/while/body/pallas_call"),
        _ev("_euclid_kernel", 700, 750, hlo_category="custom-call",
            tf_op="jit(_index_engine)/while/body/pallas_call"),
        _ev("_euclid_kernel", 760, 800, hlo_category="custom-call",
            tf_op="jit(_index_engine)/while/body/pallas_call"),
        _ev("fusion.9", 1200, 1300),  # after the window: not counted
    ]
    modules = [_ev("jit__index_engine", 100, 450),
               _ev("jit__index_engine", 450, 800),
               _ev("jit_other", 900, 950)]
    host = [_ev("chipbench.window", 0, 1000, where="main"),
            _ev("chipbench.sleep", 0, 90, where="main"),
            _ev("chipbench.wait", 800, 1000, where="main"),
            _ev("PjitFunction(_index_engine)", 420, 460, where="batcher")]
    return trace.Trace(ops, modules, host)


def test_busy_and_idle(tr):
    # Union of [100, 400], [450, 600], [600, 650], [700, 750], [760, 800]
    # = 300 + 150 + 50 + 50 + 40 = 590 ns of a 1,000 ns window.
    assert tr.window_s() == pytest.approx(1e-6)
    assert tr.busy_s() == pytest.approx(590e-9)
    assert device_idle.read({}, tr, {}, {}, "TPU v5 lite") == \
        pytest.approx(41.0)


def test_breakdown_names_gaps_by_host_span(tr):
    b = tr.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(200e-9)]
    assert len(b["device_ops"]) == 4  # fusion.9 lies after the window
    # Gaps: [0, 100] 100 ns (sleep covers 90), [800, 1000] 200 ns (wait),
    # [400, 450] 50 ns (the engine dispatch), [650, 700] and [750, 760].
    assert b["idle_gaps"][0] == ["chipbench.wait", pytest.approx(200e-9)]
    assert b["idle_gaps"][1] == ["chipbench.sleep", pytest.approx(100e-9)]
    assert b["idle_gaps"][2] == ["PjitFunction(_index_engine)",
                                 pytest.approx(50e-9)]
    assert [g[1] for g in b["idle_gaps"]] == pytest.approx(
        [200e-9, 100e-9, 50e-9, 50e-9, 10e-9])


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with gzip.open(RECORDED) as f:
        return trace.from_profile(ProfileData.from_serialized_xspace(
            f.read()))


def test_recorded_tpu_trace_busy_and_idle(recorded):
    # The chipbench.window span runs 49,890,539 ns to 11,127,802,544 ns;
    # all 51,031 ops of /device:TPU:0's "XLA Ops" line lie in it, and the
    # union of their intervals is 10,873,534,493 ns.
    assert recorded.devices == ["/device:TPU:0"]
    assert len(recorded.ops) == 51031
    assert recorded.window_s() == pytest.approx(11.077912005, abs=1e-9)
    assert recorded.busy_s() == pytest.approx(10.873534493, abs=1e-9)
    assert device_idle.read({}, recorded, {}, None, "TPU v5 lite") == \
        pytest.approx(100 * (1 - 10.873534493 / 11.077912005))


def test_recorded_tpu_trace_kernel_launches(recorded):
    # One LBC launch per batch (13), 606,783,206 ns in all; 1,336 launches
    # of the distance kernel in the RDC rounds.
    spec = manifest.load_json(manifest.data_file("metrics", "lbc_roofline"))
    lbc = recorded.op_seconds(spec["op"])
    assert len(lbc) == 13
    assert sum(lbc) == pytest.approx(0.606783206, abs=1e-9)
    assert len(recorded.op_seconds(r"^%vmap_jit_euclid_sq_pallas")) == 1336
    sort = recorded.op_seconds(r"^%sort\.2 ")
    assert (len(sort), sum(sort)) == (13, pytest.approx(7.801072389))


def test_recorded_tpu_trace_lbc_roofline(recorded):
    # A launch: Q = 64, N = 4,194,304, w = 16. Operations 4 * Q * N * w =
    # 1.7180e10 take 87.21 us at 197 TFLOP/s; bytes N * w = 67,108,864
    # take 81.94 us at 819 GB/s. Operations bound it: 13 launches need
    # 1.1337 ms of the 606.78 ms they took, 0.18684%.
    cell = manifest.cell(manifest.load(), "rw256-n4m.open-mixed-k10")
    spec = manifest.load_json(manifest.data_file("metrics", "lbc_roofline"))
    got = lbc_roofline.read(spec, recorded, {}, cell, "TPU v5 lite")
    assert got == pytest.approx(100 * 13 * (4 * 64 * 4194304 * 16 / 197e12)
                                / 0.606783206)
    assert got == pytest.approx(0.18684, abs=5e-5)


def test_recorded_tpu_trace_breakdown(recorded):
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    name, seconds = b["device_ops"][0]
    assert name.startswith("%sort.2 = (f32[64,4194304]")
    assert len(name) == trace.NAME_CHARS
    assert seconds == pytest.approx(7.801072389)
    assert all(g[1] > 0 for g in b["idle_gaps"])
