"""The roofline analyzer must extract correct FLOPs/collective bytes from
real compiled HLO — verified against hand-computable programs."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import roofline


def test_dot_flops_counted_exactly():
    m, k, n = 64, 128, 32

    def f(a, b):
        return a @ b

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32)).compile()
    rep = roofline.analyze(comp.as_text(), 1)
    assert rep.flops == 2 * m * k * n


def test_scan_body_multiplied_by_trip_count():
    trips, d = 9, 32

    def f(c, xs):
        def body(h, x):
            return h @ x, ()
        h, _ = jax.lax.scan(body, c, xs)
        return h

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((d, d), jnp.float32),
        jax.ShapeDtypeStruct((trips, d, d), jnp.float32)).compile()
    rep = roofline.analyze(comp.as_text(), 1)
    # XLA's own cost_analysis sees the body once — ours must see it trips x.
    xla_flops = comp.cost_analysis()["flops"]
    assert abs(xla_flops - 2 * d ** 3) < 4 * d * d  # body counted once
    assert abs(rep.flops - trips * 2 * d ** 3) < trips * 4 * d * d


def test_nested_scan_multiplies_transitively():
    t1, t2, d = 3, 5, 16

    def f(c, xs):
        def outer(h, x):
            def inner(h2, y):
                return h2 @ y, ()
            h2, _ = jax.lax.scan(inner, h, x)
            return h2, ()
        h, _ = jax.lax.scan(outer, c, xs)
        return h

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((d, d), jnp.float32),
        jax.ShapeDtypeStruct((t1, t2, d, d), jnp.float32)).compile()
    rep = roofline.analyze(comp.as_text(), 1)
    want = t1 * t2 * 2 * d ** 3
    assert abs(rep.flops - want) / want < 0.05


def test_collective_bytes_and_groups(tmp_path):
    """All-reduce over an 8-device mesh: ring term 2(n-1)/n * bytes."""
    import os
    import subprocess
    import sys
    import textwrap
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import roofline
        mesh = jax.make_mesh((8,), ("d",))
        def f(x):
            return jax.lax.with_sharding_constraint(
                x.sum(0, keepdims=True), NamedSharding(mesh, P()))
        comp = jax.jit(
            f, in_shardings=NamedSharding(mesh, P("d", None))).lower(
            jax.ShapeDtypeStruct((8, 1024), jnp.float32)).compile()
        rep = roofline.analyze(comp.as_text(), 8)
        print(json.dumps({"coll": rep.collective_bytes,
                          "ops": rep.collective_by_op}))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    import json as j
    rec = j.loads(out.stdout.strip().splitlines()[-1])
    # one all-reduce of (1,1024) f32 = 4096 bytes, ring: 2*(7/8)*4096 = 7168
    assert rec["coll"] > 0
    assert abs(rec["coll"] - 7168) / 7168 < 0.5, rec


def _contract_report(cells, backend="cpu", shapes="tiny"):
    from benchmarks import perf_contract as pc

    entries = []
    for kernel, q, n, us in cells:
        cost = pc.kernel_cost(kernel, q, n)
        entries.append(dict(
            key=f"{kernel}|{backend}|f32|q{q}|n{n}", kernel=kernel,
            q=q, n=n, us=us, gflops=cost["flops"] / us * 1e-3,
            ai=cost["ai"], flops=cost["flops"], bytes=cost["bytes"],
            roofline_frac=cost["roofline_frac"]))
    return dict(backend=backend, dtype="f32", shapes=shapes,
                entries=entries)


def _refs(cells, band=(0.25, 4.0), scope="tiny"):
    return {f"{kernel}|cpu|f32|q{q}|n{n}": dict(us=us, band=band,
                                                scope=scope)
            for kernel, q, n, us in cells}


def test_contract_cost_model_seeds_from_roofline_constants():
    from benchmarks import perf_contract as pc

    cost = pc.kernel_cost("lb_batch", 8, 65536)
    assert cost["ai"] == cost["flops"] / cost["bytes"]
    balance = roofline.PEAK_FLOPS / roofline.HBM_BW
    assert cost["roofline_frac"] == min(cost["ai"] / balance, 1.0)
    # the lower-bound kernels are memory-bound on the target chip: their
    # attainable fraction of peak is well under 1
    assert 0 < cost["roofline_frac"] < 0.5
    with __import__("pytest").raises(ValueError):
        pc.kernel_cost("nope", 1, 1)


def test_contract_check_passes_in_band_and_normalizes():
    from benchmarks import perf_contract as pc

    cells = [("lb_batch", 8, 16384, 1000.0), ("lb_multi", 8, 16384, 800.0),
             ("paa_isax", 1, 4096, 40000.0)]
    refs = {"cpu": _refs(cells)}
    assert pc.check(_contract_report(cells), refs) == []
    # a uniformly 3x slower runner cancels via the suite median
    slow = [(k, q, n, 3 * us) for k, q, n, us in cells]
    assert pc.check(_contract_report(slow), refs) == []
    # ONE cell regressing 8x relative to the rest trips its band
    one = [("lb_batch", 8, 16384, 8000.0)] + cells[1:]
    problems = pc.check(_contract_report(one), refs)
    assert len(problems) == 1 and "lb_batch" in problems[0]


def test_contract_check_fails_loudly_not_silently():
    from benchmarks import perf_contract as pc

    cells = [("lb_batch", 8, 16384, 1000.0), ("lb_multi", 8, 16384, 800.0)]
    refs = {"cpu": _refs(cells)}
    # no references for the backend at all
    assert "no committed perf references" in pc.check(
        _contract_report(cells, backend="tpu"), refs)[0]
    # a referenced cell silently dropped from the report
    problems = pc.check(_contract_report(cells[:1]), refs)
    assert any("missing from the report" in p for p in problems)
    # a measured cell nobody wrote a reference for
    extra = cells + [("euclid", 1, 1024, 100.0)]
    problems = pc.check(_contract_report(extra), refs)
    assert any("no committed reference" in p for p in problems)
    # full-scope references only bind full-shape reports
    full_refs = {"cpu": dict(_refs(cells),
                             **_refs([("euclid", 1, 4096, 50.0)],
                                     scope="full"))}
    assert pc.check(_contract_report(cells), full_refs) == []
    problems = pc.check(_contract_report(cells, shapes="full"), full_refs)
    assert any("missing from the report" in p for p in problems)


def test_contract_check_catches_cost_model_drift():
    from benchmarks import perf_contract as pc

    cells = [("lb_batch", 8, 16384, 1000.0)]
    refs = {"cpu": _refs(cells)}
    rep = _contract_report(cells)
    rep["entries"][0]["ai"] *= 1.2  # stale generator recorded a stale AI
    problems = pc.check(rep, refs)
    assert any("drifted from the cost model" in p for p in problems)


def test_contract_check_exempts_noise_floor_cells():
    from benchmarks import perf_contract as pc

    # a 6us reference cell 20x slower must NOT trip: below MIN_US the
    # band is unenforceable timer noise (presence still checked above)
    cells = [("lb_single", 1, 16384, 6.0), ("lb_batch", 8, 16384, 1000.0)]
    refs = {"cpu": _refs(cells)}
    noisy = [("lb_single", 1, 16384, 120.0), cells[1]]
    assert pc.check(_contract_report(noisy), refs) == []


def test_committed_references_are_self_consistent():
    """Every committed reference key parses against the tuning registry
    and every tiny/full measurement cell has a cpu reference."""
    from benchmarks import perf_contract as pc
    from repro.core import tuning

    for backend, refs in pc.REFERENCES.items():
        for key, ref in refs.items():
            kernel, b, dtype, q, n = tuning.parse_key(key)
            assert b == backend and kernel in tuning.KERNELS
            assert ref["us"] > 0 and ref.get("scope") in ("tiny", "full")
            lo, hi = ref.get("band", pc.DEFAULT_BAND)
            assert 0 < lo <= 1 <= hi
    cpu = pc.REFERENCES["cpu"]
    for kernel, q, n in pc._cells(full=True):
        assert tuning.make_key(kernel, "cpu", "f32", q, n) in cpu


def test_hlo_parser_handles_tuples_and_params():
    text = """
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

ENTRY %main (x: f32[4,8]) -> (f32[4,8], f32[]) {
  %x = f32[4,8]{1,0} parameter(0)
  %y = f32[4,8]{1,0} multiply(%x, %x)
  %z = f32[] reduce(%y, %x), dimensions={0,1}, to_apply=%add
  ROOT %t = (f32[4,8]{1,0}, f32[]) tuple(%y, %z)
}
"""
    comps = roofline.parse_hlo(text)
    assert "main" in comps and "add" in comps
    rep = roofline.analyze(text, 1)
    assert rep.flops == 0  # no dots
    assert rep.hbm_bytes > 0
