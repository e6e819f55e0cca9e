#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness check, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one process runs the cell as ``bench/run.py`` does (a
short window is enough: it serves the same mix at the same load) and
prints one JSON line: the program's readings and each control's, the
reference computed in a lower precision and put in the program's place
(``reference.compare``), each judged by the checks that decide
``correct``.  The limits in the configuration file lie between the
program's largest readings and the control's smallest.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="fp8,bf16")
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    from harness import cell as C
    from harness import spec

    C.setup_jax(ROOT)
    work = spec.workload(args.workload)
    config = spec.config_file(work["config"])
    mix = spec.traffic_file(work["traffic"])
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        lines = []
        out = C.run_cell(work, config, mix, seed, args.seconds, False,
                         time.perf_counter(), controls=controls,
                         log=lines.append)
        ref = [m for m in lines if m.startswith("reference:")]
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "controls": out.get("controls", {}),
                          "attempted": out["attempted"],
                          "failed": out["failed"]}), flush=True)
        print(*ref, sep="\n", flush=True)


if __name__ == "__main__":
    main()
