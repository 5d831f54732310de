"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each module's docstring
for the paper mapping). ``--quick``/``--tiny`` shrinks datasets for
CI-speed runs. ``--json PATH`` additionally writes the rows (plus any
failures) as a JSON report — the artifact CI uploads — and
``--strict-parity`` turns any ``parity=False`` row or crashed bench into
a non-zero exit: the benchmark-parity gate. ``--retune`` skips the
benches and instead re-runs the kernel block-shape autotuner over the
canonical grid on this backend, printing the committed-vs-measured diff
and rewriting ``TUNING.json``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def retune_table() -> None:
    """``--retune``: autotune the canonical grid, diff, rewrite TUNING.json.

    Runs the block-shape search (``repro.core.tuning.retune``) for every
    registered kernel's canonical (Q, N) cells on the CURRENT backend,
    prints each cell as committed-vs-measured (so the diff reviews like
    a table even before git does), and writes the merged table back to
    the committed path. Other backends' rows are preserved — re-tuning
    on a TPU never touches the cpu rows CI validates.
    """
    import jax

    from repro.core import tuning

    path = tuning.default_table_path()
    table, diffs = tuning.retune()
    print(f"# retuned {len(diffs)} cells on backend="
          f"{jax.default_backend()}", file=sys.stderr)
    print("key,committed,measured,us_per_call,default_us_per_call")
    for d in sorted(diffs, key=lambda d: d["key"]):
        old, new = d["old"], d["new"]
        knobs = sorted(k for k in new if k in
                       tuning.KERNELS[tuning.parse_key(d["key"])[0]].defaults)

        def fmt(e):
            return ("-" if e is None else
                    " ".join(f"{k}={e[k]}" for k in knobs))

        mark = "" if (old and all(old.get(k) == new[k] for k in knobs)) \
            else "  <- changed"
        print(f"{d['key']},{fmt(old)},{fmt(new)},{new['us_per_call']},"
              f"{new['default_us_per_call']}{mark}")
    table.save(path)
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", "--tiny", action="store_true", dest="quick",
                    help="small datasets (fast smoke run)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (e.g. query,build)")
    ap.add_argument("--filter", default=None, metavar="SUBSTR",
                    help="run benches whose name contains SUBSTR (CI legs "
                         "and local runs select benches without editing the "
                         "registry; composes with --only)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows + failures as a JSON report")
    ap.add_argument("--strict-parity", action="store_true",
                    help="exit non-zero if any bench crashes or reports "
                         "parity=False (the CI gate)")
    ap.add_argument("--retune", action="store_true",
                    help="re-run the kernel block-shape autotuner over the "
                         "canonical grid on THIS backend, print the "
                         "committed-vs-measured diff table, and rewrite "
                         "TUNING.json (commit the result); skips the "
                         "benches")
    args = ap.parse_args()

    if args.retune:
        retune_table()
        return

    from benchmarks import (bench_batch_query, bench_build, bench_classifier,
                            bench_coldtier, bench_ingest, bench_knn_topk,
                            bench_lower_bound, bench_pruning, bench_query,
                            bench_router_faults, bench_search_batcher,
                            bench_tiers, perf_contract, roofline_table)
    from benchmarks.common import emit

    # Each registry entry returns (rows, parity): parity is the bench's own
    # exactness verdict (None when the bench has no parity concept) — the
    # gate checks this bool structurally, not the derived-text columns.
    def _batch_query(quick):
        # quick maps onto these benches' own --tiny smoke configs (the
        # sizes the CI gate is meant to run), not their mid-size "quick".
        rows, report = bench_batch_query.run(tiny=quick)
        return rows, all(e["parity"] for e in report["results"])

    def _knn_topk(quick):
        rows, report = bench_knn_topk.run(tiny=quick)
        return rows, all(e["parity"] for e in report["results"])

    def _tiers(quick):
        # parity here is the tier GUARANTEE (epsilon bound holds, budget
        # certificate honest, exact tier bit-identical) — see the module
        # docstring.
        rows, report = bench_tiers.run(tiny=quick)
        return rows, all(e["parity"] for e in report["results"])

    def _ingest(quick):
        rows, report = bench_ingest.run(tiny=quick)
        # Keep the scalar report: check_regression's machine-independent
        # ingest ratio gates (durability tax, under-ingest spike) read it
        # from the JSON artifact.
        reports["ingest"] = report
        return rows, all(e["parity"] for e in report["results"])

    def _coldtier(quick):
        rows, report = bench_coldtier.run(tiny=quick)
        # Keep the scalar report: check_regression's machine-independent
        # bytes-read-ratio gate (--max-bytes-read-ratio) reads it from
        # the JSON artifact. Parity here is the cache-budget matrix —
        # identical bits at budgets {0, raw/8, unlimited}.
        reports["coldtier"] = report
        return rows, all(e["parity"] for e in report["results"])

    def _contract(quick):
        rows, report = perf_contract.run(tiny=quick)
        # check_regression --contract gates this against the committed
        # per-backend references (perf_contract.REFERENCES) with
        # suite-median normalization; no parity concept here.
        reports["contract"] = report
        return rows, None

    benches = {
        "lower_bound":
            lambda quick: (bench_lower_bound.run(quick=quick), None),
        "build": lambda quick: (bench_build.run(quick=quick), None),
        "query": lambda quick: (bench_query.run(quick=quick), None),
        "batch_query": _batch_query,
        "knn_topk": _knn_topk,
        "tiers": _tiers,
        "search_batcher": lambda quick: bench_search_batcher.run(tiny=quick),
        "router_faults": lambda quick: bench_router_faults.run(tiny=quick),
        "ingest": _ingest,
        "coldtier": _coldtier,
        "contract": _contract,
        "pruning": lambda quick: (bench_pruning.run(quick=quick), None),
        "classifier": lambda quick: (bench_classifier.run(quick=quick), None),
        "roofline": lambda quick: (roofline_table.run(quick=quick), None),
    }
    only = set(args.only.split(",")) if args.only else None
    if only:
        # A typo'd name inside a multi-name --only must not be silently
        # dropped: the remaining benches would run, --strict-parity would
        # pass, and the missing bench's gate would be vacuous.
        unknown = only - set(benches)
        if unknown:
            print(f"# --only names not registered: {sorted(unknown)}; "
                  f"known: {','.join(benches)}", file=sys.stderr)
            raise SystemExit(2)
    all_rows = []
    failures = []
    reports = {}
    selected = 0
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if only and name not in only:
            continue
        if args.filter and args.filter not in name:
            continue
        selected += 1
        t0 = time.time()
        try:
            rows, parity = fn(args.quick)
            emit(rows)
            all_rows += [
                dict(bench=name, name=r, us_per_call=us, derived=derived)
                for r, us, derived in rows
            ]
            if parity is False:
                failures.append(f"{name}: non-exact parity")
            for r, _, derived in rows:  # belt and braces for text-only rows
                if "parity=False" in derived.replace(" ", ""):
                    failures.append(f"{name}/{r}: non-exact parity")
        except Exception as e:  # keep the harness going
            print(f"{name}_FAILED,0.0,{type(e).__name__}: {e}",
                  file=sys.stdout)
            failures.append(f"{name}: {type(e).__name__}: {e}")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    if selected == 0:
        # A selection that matches nothing must NOT look like a clean run:
        # with --strict-parity an empty run would silently "pass" the CI
        # gate (e.g. a typo'd --filter after a bench rename).
        print(f"# selection (--only={args.only!r} --filter={args.filter!r})"
              f" matched no registered bench; known: "
              f"{','.join(benches)}", file=sys.stderr)
        raise SystemExit(2)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(dict(quick=args.quick, rows=all_rows,
                           failures=failures, reports=reports), f,
                      indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        for msg in failures:
            print(f"# PARITY-GATE: {msg}", file=sys.stderr)
        if args.strict_parity:
            raise SystemExit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
