"""Benchmark harness — one module per paper table/figure + systems benches.

Prints ``name,us_per_call,derived`` CSV.  Select with --only <substring>;
``--json <path>`` additionally writes a machine-readable
``{name: {"us_per_call": float, "derived": str}}`` dump (e.g.
``BENCH_kernels.json``) so the perf trajectory is tracked across PRs —
CI runs ``--only kernels --json BENCH_kernels.json`` and uploads it.
"""
import argparse
import importlib
import json
import sys
import traceback

from repro.launch.compile_cache import setup_compile_cache

MODULES = [
    "benchmarks.bench_superres",    # §5.2 / fig. 7
    "benchmarks.bench_lenet",       # §5.3 / figs. 8-9
    "benchmarks.bench_binarize",    # table 2
    "benchmarks.bench_tradeoff",    # §5.1 / fig. 6
    "benchmarks.bench_deepnet",     # §5.4
    "benchmarks.bench_al_vs_qp",    # §5 AL-vs-QP + §4.2 fn.2 prune+quant
    "benchmarks.bench_cstep",       # systems: C-step throughput, fig. 10
    "benchmarks.bench_kernels",     # systems: kernel micro
    "benchmarks.bench_engine",      # systems: continuous-batching serving
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run modules whose name contains one of these "
                         "comma-separated substrings")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON "
                         "(name → us_per_call + derived)")
    args = ap.parse_args()
    setup_compile_cache()

    only = args.only.split(",") if args.only else None
    if only:
        # A typo'd group used to select nothing and exit green — CI then
        # "passed" while benchmarking nothing.  Every token must match
        # at least one module.
        groups = [m.rsplit(".bench_", 1)[-1] for m in MODULES]
        bad = [t for t in only
               if not any(t and t in m for m in MODULES)]
        if bad:
            print(f"error: --only {','.join(bad)!r} matches no benchmark "
                  f"module; valid groups: {', '.join(groups)}",
                  file=sys.stderr)
            sys.exit(2)

    print("name,us_per_call,derived")
    results = {}
    failures = 0
    for modname in MODULES:
        if only and not any(tok and tok in modname for tok in only):
            continue
        try:
            mod = importlib.import_module(modname)
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}", flush=True)
                results[name] = {"us_per_call": round(float(us), 1),
                                 "derived": str(derived)}
        except Exception:                          # noqa: BLE001
            failures += 1
            err = traceback.format_exc(limit=3)
            print(f"{modname},ERROR,{err!r}", flush=True)
            results[modname] = {"us_per_call": None, "derived": f"ERROR: {err}"}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print(f"# wrote {len(results)} rows to {args.json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
