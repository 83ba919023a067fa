#!/usr/bin/env python3
"""Run one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``; with ``--trace 1`` also ``breakdown``; ``checks`` last); the
numbers compared, each beside its limit, are the last lines of standard
error.  Without a TPU, with fewer chips than the cell asks, or without the
repository's ``src/`` the run exits non-zero and prints no result.  See
``bench/harness.py`` for what a run does.
"""

import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    try:
        import repro.core.simjax  # noqa: F401
    except ImportError as e:
        print(f"bench: cannot import the engine from {ROOT / 'src'} ({e})",
              file=sys.stderr)
        return 2
    from bench.harness import run
    return run(t0=T0)


if __name__ == "__main__":
    raise SystemExit(main())
