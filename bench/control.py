#!/usr/bin/env python3
"""Run one cell with a break planted under its timed path.

  python bench/control.py --break control --workload <name> --seed <n> \\
      --seconds <s> --trace 0

Takes the arguments of ``bench/run.py`` plus ``--break`` (one of
``bench/faults.py``'s ``BREAKS``), plants the break and runs the cell as
``run.py`` would. Its result line reads ``correct: false`` where the
comparison catches the break; the numbers compared, beside their limits,
are what the limits in ``bench/configs/`` were set from.
"""
from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from bench import faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--break", dest="brk", required=True,
                    choices=faults.BREAKS)
    args, rest = ap.parse_known_args(argv)
    faults.plant(args.brk)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
