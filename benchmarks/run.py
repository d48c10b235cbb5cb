"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus per-bench extras to
JSON files under experiments/bench/).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

BENCHES = [
    ("cost", "Fig. 10 interconnect cost"),
    ("dedicated", "Fig. 11 dedicated 128-server cluster"),
    ("alltoall", "Fig. 12/13 all-to-all impact + bandwidth tax"),
    ("pathlen", "Fig. 14/15 path length + link utilization"),
    ("shared", "Fig. 16 shared 432-server cluster"),
    ("reconfig", "Fig. 17 reconfiguration latency"),
    ("online", "Online re-optimization: static vs reactive replanning"),
    ("multitenant", "Multi-tenant shared fabric: JobSet churn + fairness"),
    ("planner", "Compiled plan evaluator: reference vs compiled planner speed"),
    ("planner_jax", "JAX planner backend: batched chains vs NumPy pricing"),
    ("placement", "Placement co-search + churn-priced migration vs greedy"),
    ("collectives_sched", "Collective-schedule co-optimization vs ring-only"),
    ("roofline", "Roofline dry-run terms"),
    ("fleet", "Fleet-scale pricing: sparse vs dense at 256-1024 nodes"),
    ("faults", "Chaos: MTBF storm sweep, availability + hardened replanning"),
    ("admission_jax", "Fused admission co-search: candidate x ladder grid"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--out", default="experiments/bench")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for CI (benches that support it)")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    os.makedirs(args.out, exist_ok=True)
    print("name,us_per_call,derived")
    failures = 0
    for bench, desc in BENCHES:
        if only and bench not in only:
            continue
        try:
            mod = __import__(f"benchmarks.bench_{bench}", fromlist=["run"])
            import inspect

            kwargs = (
                {"smoke": True}
                if args.smoke
                and "smoke" in inspect.signature(mod.run).parameters
                else {}
            )
            rows = mod.run(**kwargs)
            # One canonical record per bench: modules with a PERF_RECORD
            # write their own BENCH_<name>.json (rich derived metrics);
            # for the rest the harness writes the row dump under the same
            # naming scheme.  (The harness used to always dump a stray
            # lowercase <name>.json that shadowed the canonical record.)
            if not hasattr(mod, "PERF_RECORD"):
                record = os.path.join(args.out, f"BENCH_{bench}.json")
                with open(record, "w") as f:
                    json.dump(rows, f, indent=1, default=str)
            for row in rows:
                print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
        except Exception:
            failures += 1
            print(f"{bench},0,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
