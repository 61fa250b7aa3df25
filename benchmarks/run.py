#!/usr/bin/env python3
"""The benchmark's command:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips, brings the deployment up, warms
the cell's own shapes, measures for ``--seconds``, decides ``correct``,
tears everything down and prints the result as the last line of standard
output. Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import harness

    line = asyncio.run(harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        T_PROCESS_START))
    harness.print_result(line)


if __name__ == "__main__":
    main()
