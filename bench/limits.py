#!/usr/bin/env python3
"""Readings that a cell's ``correct`` limits are set from, on the chip, in one
process (one set-up of the compile cache for all seeds).

    python3 bench/limits.py --workload <name> --seeds 1,2,3 --control-seeds 1,2,3

One JSON line per seed on standard output: the program's numbers against the
plain reference (the lower readings), and for the control seeds the control's
and each planted fault's (the upper readings).  Not part of the driver's
command; ``PERF.md`` records what it printed.
"""

import argparse
import importlib
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for _p in (str(BENCH.parent), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None):
    from lib import common

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    manifest, entry, cell, config = common.load_cell(args.workload)
    driver = importlib.import_module(f"lib.{cell['kind']}")
    kw = {} if args.seconds is None else {"seconds": args.seconds}
    driver.readings(seeds, control, manifest, entry, cell, config, args.rehearse, **kw)
    return 0


if __name__ == "__main__":
    sys.exit(main())
