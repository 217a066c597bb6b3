#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` at the root of the checkout, its sizes in
``bench/workloads/<name>.json`` and ``bench/configs/``, and hands it to the
driver of its kind (``bench/lib/<kind>.py``).  The last line of standard output
is the one result object; on anything but the chips the cell asks for the run
exits non-zero and prints none.  ``--rehearse`` (never given by the driver) runs
the same code at the cell's tiny ``rehearse`` sizes on whatever JAX finds and
prints its line to standard error only: a rehearsal is no measurement.
"""

import sys
import time

_STARTED = time.time()

import argparse
import importlib
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for _p in (str(BENCH.parent), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def parse(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--keep-trace", action="store_true")
    return parser.parse_args(argv)


def main(argv=None):
    from lib import common

    args = parse(argv)
    manifest, entry, cell, config = common.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    driver = importlib.import_module(f"lib.{cell['kind']}")
    driver.run(args, manifest, entry, cell, config, common.process_start() or _STARTED)
    return 0


if __name__ == "__main__":
    sys.exit(main())
