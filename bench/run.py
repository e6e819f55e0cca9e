#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window's first seconds.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``checks``: each number compared with its
limit, also printed as the last lines of standard error.  A run that
finds no TPU, or fewer chips than the cell asks for, exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit(f"bench/run.py: the program (src/repro) is not in "
                 f"{ROOT}")
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    from harness import cell as C
    from harness import spec

    C.setup_jax(ROOT)
    try:
        work = spec.workload(args.workload)
        out = C.run_cell(work, spec.config_file(work["config"]),
                         spec.traffic_file(work["traffic"]), args.seed,
                         args.seconds, bool(args.trace), T_START,
                         log=lambda m: print(m, flush=True))
    except C.BenchError as e:
        sys.exit(f"bench/run.py: {e}")
    C.print_checks(out["checks"])
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
