"""Benchmark harness: one module per paper table/figure (+roofline/kernels).

Prints ``name,value,derived`` CSV per row. ``--full`` runs the paper-scale
configurations (slower); default is the quick CI-sized pass. ``--json PATH``
additionally dumps the rows to a ``BENCH_*.json``-style file so successive
PRs accumulate a perf trajectory.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.launch.cache import enable_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names (e.g. accuracy,roofline)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump rows to a BENCH_*.json-style file")
    args = ap.parse_args(argv)
    quick = not args.full
    enable_compile_cache()

    from benchmarks import (accuracy, analysis_audit, chaos_soak, comm_time,
                            compression_sweep, kernel_bench, lq_sweep,
                            roofline, scale_sweep, stragglers, theory_bound,
                            topology_gain)
    modules = {
        "accuracy": lambda: accuracy.run(quick=quick)[0],   # Table 1 + Fig 2
        "comm_time": lambda: comm_time.run(quick=quick),    # Fig 3
        "stragglers": lambda: stragglers.run(quick=quick),  # Fig 4
        "lq_sweep": lambda: lq_sweep.run(quick=quick),      # Fig 5
        "theory_bound": lambda: theory_bound.run(quick=quick),  # §3.3
        "topology_gain": lambda: topology_gain.run(quick=quick),  # §5
        "kernels": lambda: kernel_bench.run(quick=quick),
        # dense-vs-sparse mixing round time/memory vs client count D
        "scale": lambda: scale_sweep.run(quick=quick),
        # accuracy-vs-bits frontier of the quantized-exchange codecs
        "compression": lambda: compression_sweep.run(quick=quick)[0],
        "roofline": lambda: roofline.run(quick=quick),      # deliverable (g)
        # jaxpr auditor summary (programs/rules/errors) from ANALYSIS.json
        "analysis": lambda: analysis_audit.run(quick=quick),
        # fault-injection soak: bounded degradation + store stays clean
        "faults": lambda: chaos_soak.run(quick=quick),
    }
    only = set(args.only.split(",")) if args.only else None
    if only and not only <= set(modules):
        ap.error(f"unknown module(s) {sorted(only - set(modules))}; "
                 f"available: {', '.join(modules)}")

    print("name,value,derived")
    failures = []
    records = []
    for name, fn in modules.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            for row_name, val, derived in fn():
                print(f"{row_name},{val:.6g},{derived}")
                records.append({"module": name, "name": row_name,
                                "value": float(val), "derived": derived})
            dt = time.time() - t0
            print(f"_meta/{name}/seconds,{dt:.1f},")
            records.append({"module": name, "name": f"_meta/{name}/seconds",
                            "value": round(dt, 1), "derived": ""})
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"_meta/{name}/FAILED,0,{e!r}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"quick": quick, "rows": records,
                       "failures": [{"module": m, "error": e}
                                    for m, e in failures]}, f, indent=1)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
