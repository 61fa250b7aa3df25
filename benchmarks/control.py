#!/usr/bin/env python3
"""The control of a cell: the same run with one of the configuration's
guarantees broken underneath (``sabotage.CONTROLS``, by the mix's kind).
It has to come out as NOT correct; exits 0 when it did, 1 when the
comparison let it pass. The benchmark's own runs never run this.

    python benchmarks/control.py --workload <name> --seed <n> --seconds <s>
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
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from benchmarks import harness, sabotage

    kind = harness.load_cell(args.workload)["mix"]["kind"]
    line = asyncio.run(harness.run_cell(
        args.workload, args.seed, args.seconds, False, T_PROCESS_START,
        sabotage=sabotage.CONTROLS[kind]()))
    harness.print_result(line)
    sys.exit(0 if line["correct"] is False else 1)


if __name__ == "__main__":
    main()
