#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on the machine that holds the cell's chips.
The last line of stdout is the result, one JSON object; with `--trace 0`
its metrics are the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics. Anything but a TPU with the cell's chip count ends
the run with a non-zero exit code and no result line.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402  (starts the set-up clock)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    cell = common.Cell(ROOT, args.workload)
    try:
        import code2vec_tpu  # noqa: F401
    except ImportError as e:
        raise common.NoResult(f"the program is not in this checkout: {e}")
    cell.run_module().run(cell, args.seed, args.seconds, bool(args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
