#!/usr/bin/env python3
"""``control.py`` for a cell whose kind of traffic brings its own control:
the kind's module registers it in ``sabotage.CONTROLS`` when imported, and
``control.py`` looks the kind up before anything imports that module. This
imports it first, then hands over; arguments and exit code are
``control.py``'s.

    python benchmarks/control_of_kind.py --workload <name> --seed <n> --seconds <s>
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    args, _rest = ap.parse_known_args(argv)

    from benchmarks import control, harness

    kind = harness.load_cell(args.workload)["mix"]["kind"]
    importlib.import_module(f"benchmarks.traffic.{kind}")
    control.T_PROCESS_START = T_PROCESS_START
    control.main(argv)


if __name__ == "__main__":
    main()
