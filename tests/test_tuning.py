"""Autotuner + tuning-table properties.

Everything timer-dependent runs against STUBBED timers (deterministic
cost surfaces), so the suite pins the search logic, the key algebra, the
resolution precedence (explicit kwarg > table entry > registry default)
and the validator without a single real measurement. The bit-exactness
property — tuned block shapes never change answers, only tiling — is
checked for real: reference vs pallas-interpret at several block
configurations must agree to the bit.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isax, search, tuning
from repro.kernels import ops
from repro.launch.hillclimb import coordinate_descent, snap_to_lattice


@pytest.fixture
def clean_table():
    """Install an empty table for the test; restore lazy loading after."""
    tuning.set_table(tuning.TuningTable())
    yield
    tuning.set_table(None)


def _table_with(kernel, backend, q, n, **params):
    t = tuning.TuningTable()
    entry = dict(params)
    entry.update(us_per_call=1.0, default_us_per_call=2.0,
                 impl="auto", evals=1)
    t.entries[tuning.make_key(kernel, backend, "f32", q, n)] = entry
    return t


# ------------------------------------------------------------- key algebra
def test_make_key_buckets_like_jit_cache():
    # 3000 queries bucket to 4096, 50000 rows to 65536 — one entry per
    # compiled-engine bucket, exactly the batch-shape rule.
    key = tuning.make_key("lb_batch", "cpu", "f32", 3000, 50000)
    assert key == "lb_batch|cpu|f32|q4096|n65536"


def test_parse_key_round_trips():
    for kernel in tuning.KERNELS:
        for q, n in tuning.KERNELS[kernel].canonical:
            key = tuning.make_key(kernel, "tpu", "f32", q, n)
            assert tuning.parse_key(key) == (
                kernel, "tpu", "f32", tuning._pow2(q), tuning._pow2(n))


def test_parse_key_rejects_malformed():
    for bad in ("nope", "a|b|c|d", "k|b|f32|qx|n8", "k|b|f32|q3|n8",
                "k|b|f32|q8|n8|extra"):
        with pytest.raises(ValueError):
            tuning.parse_key(bad)


def test_table_save_load_round_trip(tmp_path):
    t = _table_with("lb_batch", "cpu", 8, 65536, block_q=4, block_n=2048)
    path = str(tmp_path / "TUNING.json")
    t.save(path)
    back = tuning.TuningTable.load(path)
    assert back.version == tuning.TABLE_VERSION
    assert back.entries == t.entries
    # the file is stable JSON (sorted keys, trailing newline) — the
    # committed artifact must diff cleanly
    raw = open(path).read()
    assert raw.endswith("\n") and json.loads(raw)["version"] == 1


# ------------------------------------------------------------- resolution
def test_miss_falls_back_to_registry_defaults(clean_table):
    for kernel, spec in tuning.KERNELS.items():
        assert tuning.resolve_blocks(
            kernel, q=8, n=4096, backend="cpu") == spec.defaults


def test_table_hit_supplies_tuned_shape():
    tuning.set_table(
        _table_with("lb_batch", "cpu", 8, 65536, block_q=16, block_n=2048))
    try:
        got = tuning.resolve_blocks("lb_batch", q=8, n=65536, backend="cpu")
        assert got == {"block_q": 16, "block_n": 2048}
        # a different bucket still misses -> defaults
        other = tuning.resolve_blocks(
            "lb_batch", q=8, n=1024, backend="cpu")
        assert other == tuning.KERNELS["lb_batch"].defaults
    finally:
        tuning.set_table(None)


def test_explicit_kwarg_beats_table():
    tuning.set_table(
        _table_with("lb_batch", "cpu", 8, 65536, block_q=16, block_n=2048))
    try:
        got = tuning.resolve_blocks(
            "lb_batch", q=8, n=65536, backend="cpu", block_q=2)
        assert got == {"block_q": 2, "block_n": 2048}  # partial override
    finally:
        tuning.set_table(None)


def test_unknown_knob_rejected(clean_table):
    with pytest.raises(ValueError, match="no tunable"):
        tuning.resolve_blocks("euclid", q=1, n=64, backend="cpu",
                              block_q=8)


def test_missing_table_file_degrades_to_defaults(monkeypatch, tmp_path):
    monkeypatch.setenv(tuning.TABLE_ENV, str(tmp_path / "absent.json"))
    tuning.set_table(None)
    try:
        assert tuning.get_table().entries == {}
        assert tuning.resolve_blocks(
            "euclid", q=1, n=64, backend="cpu") == {"block_b": 256}
    finally:
        tuning.set_table(None)


# -------------------------------------------------------------- the search
def test_hillclimb_converges_to_planted_optimum():
    lattice = (64, 128, 256, 512, 1024, 2048)

    def cost(params):  # V-shaped around 512, big (>>min_gain) steps
        return 1.0 + abs(np.log2(params["block_n"]) - np.log2(512))

    best, best_cost, history = coordinate_descent(
        cost, {"block_n": 64}, {"block_n": lattice}, min_gain=0.03)
    assert best == {"block_n": 512} and best_cost == 1.0
    # evaluation cache: distinct evals only, never more than the lattice
    assert len(history) <= len(lattice)


def test_hillclimb_noise_below_min_gain_stays_at_defaults():
    # a dead knob (CPU reference path): +-1% "noise", deterministic
    def cost(params):
        return 100.0 * (1.0 + 0.01 * ((hash(params["block_n"]) % 3) - 1))

    best, _, _ = coordinate_descent(
        cost, {"block_n": 1024},
        {"block_n": (256, 512, 1024, 2048)}, min_gain=0.03)
    assert best == {"block_n": 1024}


def test_snap_to_lattice():
    assert snap_to_lattice(300, (64, 256, 1024)) == 256
    assert snap_to_lattice(640, (256, 1024)) == 256  # tie -> smaller


def test_autotune_with_stub_timer_plants_optimum():
    def timer(params):
        return 10.0 + abs(params["block_q"] - 32) + \
            abs(np.log2(params["block_n"]) - np.log2(4096))

    res = tuning.autotune("lb_batch", q=8, n=65536, backend="cpu",
                          timer=timer)
    assert res.params == {"block_q": 32, "block_n": 4096}
    assert res.key == "lb_batch|cpu|f32|q8|n65536"
    assert res.evals >= 1 and res.default_us_per_call >= res.us_per_call
    entry = res.entry("auto")
    assert entry["block_q"] == 32 and entry["impl"] == "auto"


def test_retune_covers_canonical_grid_and_diffs(tmp_path):
    def timer_for(kernel, *, q, n):
        return lambda params: 100.0  # flat surface: stays at defaults

    table, diffs = tuning.retune(
        table=tuning.TuningTable(), backend="cpu", timer_for=timer_for)
    want = sum(len(s.canonical) for s in tuning.KERNELS.values())
    assert len(diffs) == want == len(table.entries)
    assert all(d["old"] is None for d in diffs)
    for name, spec in tuning.KERNELS.items():
        for q, n in spec.canonical:
            entry = table.lookup(name, "cpu", "f32", q, n)
            for knob, default in spec.defaults.items():
                assert entry[knob] == default  # flat timer -> defaults
    # a fresh full retune validates clean (the CI drift gate)
    assert tuning.validate(table) == []
    # second retune reports the committed entry as old
    table2, diffs2 = tuning.retune(
        table=table, backend="cpu", timer_for=timer_for)
    assert all(d["old"] is not None for d in diffs2)


# -------------------------------------------------------------- validation
def test_validate_flags_stale_and_malformed():
    # empty table: every canonical cell is uncovered
    problems = tuning.validate(tuning.TuningTable())
    want = sum(len(s.canonical) for s in tuning.KERNELS.values())
    assert len(problems) == want
    assert all("stale table" in p for p in problems)

    # unknown kernel entry
    t = _table_with("no_such_kernel", "cpu", 8, 65536, block_q=8)
    assert any("not in the registry" in p for p in tuning.validate(t))

    # off-lattice knob value (registry moved; table did not)
    t = _table_with("lb_batch", "cpu", 8, 65536, block_q=3, block_n=1024)
    assert any("not in the candidate lattice" in p
               for p in tuning.validate(t))

    # missing knob
    t = _table_with("lb_batch", "cpu", 8, 65536, block_q=8)
    assert any("missing knob 'block_n'" in p for p in tuning.validate(t))

    # version drift
    t = tuning.TuningTable(version=0)
    assert any("version" in p for p in tuning.validate(t))


# ----------------------------------------------------- bit-exactness + ops
def _lb_inputs(n=700, n_q=5, segments=16, seed=3):
    rng = np.random.default_rng(seed)
    bpp = isax.padded_breakpoints()
    sax = jnp.asarray(
        rng.integers(0, bpp.shape[0] - 1, size=(n, segments)), jnp.uint8)
    qp = jnp.asarray(rng.standard_normal((n_q, segments)), jnp.float32)
    return qp, sax, bpp


def test_tuned_blocks_bit_exact_within_impl(clean_table):
    """Block shapes only re-tile: every config gives IDENTICAL bits for
    the same impl (and stays allclose to the reference oracle, whose
    accumulation order legitimately differs in the last ulp)."""
    qp, sax, bpp = _lb_inputs()
    ref = ops.lower_bound_sq_batch(qp, sax, bpp, 256, impl="ref")
    outs = [np.asarray(ops.lower_bound_sq_batch(
        qp, sax, bpp, 256, impl="pallas", block_q=bq, block_n=bn))
        for bq, bn in ((1, 256), (8, 1024), (16, 512))]
    for got in outs[1:]:
        np.testing.assert_array_equal(outs[0], got)
    np.testing.assert_allclose(np.asarray(ref), outs[0], rtol=1e-5)


def test_table_entry_drives_pallas_call(monkeypatch):
    """ops consults the table: the tuned shape reaches the kernel."""
    qp, sax, bpp = _lb_inputs(n=1000, n_q=8)
    tuning.set_table(
        _table_with("lb_batch", "cpu", 8, 1024, block_q=2, block_n=512))
    seen = {}
    from repro.kernels import lower_bound as _lb
    real = _lb.lower_bound_sq_batch_pallas

    def spy(*args, **kwargs):
        seen.update(kwargs)
        return real(*args, **kwargs)

    monkeypatch.setattr(ops._lb, "lower_bound_sq_batch_pallas", spy)
    try:
        got = ops.lower_bound_sq_batch(qp, sax, bpp, 256, impl="pallas")
        assert seen["block_q"] == 2 and seen["block_n"] == 512
        ref = ops.lower_bound_sq_batch(qp, sax, bpp, 256, impl="ref")
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(got), rtol=1e-5)
    finally:
        tuning.set_table(None)


def test_engine_override_parity_and_distinct_cache_keys(small_index,
                                                        clean_table,
                                                        monkeypatch):
    """make_batch_engine: explicit blocks give bit-identical answers and
    a DISTINCT jit-cache key (historical statics tuples unchanged)."""
    rng = np.random.default_rng(7)
    queries = jnp.asarray(
        rng.standard_normal((4, 256)).cumsum(axis=1), jnp.float32)
    keys = []
    real = search._engine_for

    def spy(index, statics):
        keys.append(statics)
        return real(index, statics)

    monkeypatch.setattr(search, "_engine_for", spy)
    base = search.make_batch_engine(small_index, k=5)
    tuned = search.make_batch_engine(
        small_index, k=5, block_q=4, block_n=512)
    d0, p0 = base(queries)
    d1, p1 = tuned(queries)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    has_blocks = [s for s in keys if len(s) > 8 and s[8] == (4, 512)]
    plain = [s for s in keys if len(s) <= 8]
    assert has_blocks and plain


def test_pack_components_resolves_block_via_table(small_index):
    tuning.set_table(
        _table_with("lb_multi", "cpu", 8,
                    int(small_index.num_series), block_q=8, block_n=256))
    try:
        packed = search.pack_components([(small_index, 0)])
        assert packed.block == 256
    finally:
        tuning.set_table(None)
    packed = search.pack_components([(small_index, 0)], block=128)
    assert packed.block == 128
