"""Benchmark harness: one module per paper table/figure + roofline.

Prints each table (human-readable) and finishes with the canonical
``name,us_per_call,derived`` CSV. ``--reduced`` trims data-collection sizes
for quick runs; ``--only t3,t5`` selects modules; ``--json <path>`` also
writes the rows as machine-readable JSON (``BENCH_runtime.json`` in CI — the
perf trajectory consumed by dashboards and regression tooling).
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
import traceback


def write_json(sink, path: str, smoke: bool, reduced: bool) -> None:
    """Dump the sink's rows as ``{scenario: {us_per_call, speedup?, derived}}``.

    ``speedup`` is parsed out of the derived field (``speedup=12.3x``) when a
    benchmark reported one, so perf floors are first-class numbers.
    """
    rows = {}
    for name, us, derived in sink.rows:
        row = {"us_per_call": round(us, 3), "derived": derived}
        m = re.search(r"speedup=([0-9.]+)x", derived)
        if m:
            row["speedup"] = float(m.group(1))
        rows[name] = row
    payload = {
        "schema": "bench_runtime/v1",
        "smoke": smoke,
        "reduced": reduced,
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"(json → {path}: {len(rows)} rows)")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--reduced", action="store_true",
                   help="smaller measurement sets (quick run)")
    p.add_argument("--only", default="",
                   help="comma list: t1,t2,t3,t4,t5,fig5,fig6,beyond,runtime,roofline")
    p.add_argument("--skip-live", action="store_true",
                   help="skip the real-compile live prototype (t5)")
    p.add_argument("--smoke", action="store_true",
                   help="seconds-long fleet perf smoke (CI): columnar "
                        "decisions, array-native serve, vectorized twin "
                        "execution + fleet-vs-single-edge scenario")
    p.add_argument("--json", default="",
                   help="also write results as JSON to this path "
                        "(BENCH_runtime.json in CI)")
    args = p.parse_args()

    from repro import compile_cache

    compile_cache.configure()
    from benchmarks import common
    if args.reduced or args.smoke:
        common.REDUCED = True

    if args.smoke:
        from benchmarks import bench_runtime

        sink = common.CsvSink()
        t0 = time.time()
        bench_runtime.run_smoke(sink)
        print(f"\n# smoke wall: {time.time() - t0:.1f}s")
        print(sink.dump())
        if args.json:
            write_json(sink, args.json, smoke=True, reduced=common.REDUCED)
        return 0

    from benchmarks import (
        bench_runtime,
        beyond_paper,
        fig5_delta_sweep,
        fig6_alpha_sweep,
        roofline,
        table1_components,
        table2_mape,
        table3_costmin,
        table4_latmin,
        table5_live,
    )

    # t5 (the live prototype) runs FIRST: its latencies are wall-clock
    # measurements and the cleanest process state gives the fairest numbers
    # (running it after the numpy-heavy fits adds ~2-3x noise to sub-100ms
    # measurements — both orderings are honest, this one is reproducible).
    modules = {
        "t5": table5_live.run,
        "t1": table1_components.run,
        "t2": table2_mape.run,
        "t3": table3_costmin.run,
        "t4": table4_latmin.run,
        "fig5": fig5_delta_sweep.run,
        "fig6": fig6_alpha_sweep.run,
        "beyond": beyond_paper.run,
        "runtime": bench_runtime.run,
        "roofline": roofline.run,
    }
    selected = [s.strip() for s in args.only.split(",") if s.strip()] or list(modules)
    if args.skip_live and "t5" in selected:
        selected.remove("t5")

    sink = common.CsvSink()
    failures = []
    t0 = time.time()
    for name in selected:
        try:
            if name == "roofline":
                modules[name](sink)
                modules[name](sink, mesh="multipod")
                path = roofline.write_markdown()
                print(f"(roofline markdown → {path})")
            else:
                modules[name](sink)
        except Exception:
            failures.append(name)
            print(f"\nBENCHMARK {name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)

    print(f"\n# total wall: {time.time()-t0:.1f}s")
    print(sink.dump())
    if args.json:
        write_json(sink, args.json, smoke=False, reduced=common.REDUCED)
    if failures:
        print(f"\nFAILED benchmarks: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
