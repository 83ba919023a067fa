#!/usr/bin/env python3
"""Read the two ends of each correctness limit of a cell on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 ... \
        [--control-seeds 1 2 3]

For every seed, in one process: build the cell's workload, run one whole
job through the program (the first compiles), compute the reference's
checked rows and print the program's gap per metric (the lower reading).
For every control seed, also put the control (the reference computed in
bfloat16) in the program's place and print its gaps (the upper reading).
One JSON line per reading; the benchmark's own runs never run this.
"""

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax

    from bench import check, gen, harness
    from bench.jobs import Job
    from bench.spans import NoSpans
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    keys = list(cell.limits)
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        data = gen.generate(cell.config, seed)
        n = len(cell.traffic.get("points", [{}]))
        points = check.checked_points(
            n, cell.traffic.get("check_points", n), seed)
        ref = harness.reference_rows(cell, data, points)
        rec = {"cell": cell.name, "seed": seed, "points": points}
        if seed in args.seeds:
            t = time.perf_counter()
            rows = Job(cell.config, cell.traffic, cell.chips, data,
                       NoSpans(), cell.root)()
            rec["job_s"] = time.perf_counter() - t
            rec["program"] = check.job_gaps(rows, ref, points, n, keys)
        if seed in args.control_seeds:
            ctl = harness.reference_rows(cell, data, points,
                                         dtype="bfloat16")
            full = [None] * n
            for i, r in zip(points, ctl):
                full[i] = r
            rec["control"] = check.job_gaps(full, ref, points, n, keys)
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
