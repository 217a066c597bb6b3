#!/usr/bin/env python3
"""The one-process rate sweep that finds a serve cell's knee.

    python3 bench/sweep.py --workload <serve cell> --seed 1 --start 0.5 --factor 1.25 --steps 12 --seconds 20

One set-up; then the cell's traffic mix at rates rising by ``factor`` from
``start`` requests/s, ``seconds`` each, the service drained between steps.
One JSON line per rate: what was offered, tokens/s completed, the requests in
flight at half time and at the close (a backlog that grows through the step
means the rate is over capacity), time to first token and token gaps.  The
knee is the highest rate at which the backlog does not grow; the cells' rates
are written into their files by hand, with this table in
``PERF.md``.  Not part of the driver's command.
"""

import argparse
import importlib
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for _p in (str(BENCH.parent), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def in_flight(calls, t):
    return sum(1 for c in calls if c.due <= t and (not c.arrivals or not c.done or c.arrivals[-1] > t))


def main(argv=None):
    import json

    from lib import common, serve, traffic

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--start", type=float, default=0.5)
    parser.add_argument("--factor", type=float, default=1.25)
    parser.add_argument("--steps", type=int, default=12)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--stop-backlog", type=int, default=24,
                        help="stop after the step whose backlog at the close passes this")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    manifest, entry, cell, config = common.load_cell(args.workload)
    common.setup_cache(entry["name"])
    devices = common.require_chips(entry["chips"], args.rehearse)
    engine_kw, mix, published, fields = serve.sized(cell, config, args.rehearse)
    reference = importlib.import_module(f"reference.{config['reference']['module']}")
    t_setup = time.perf_counter()
    engine, router, frontdoor, server = serve.build_service(args.seed, engine_kw, published, fields, reference)
    try:
        serve.warm_up(server, engine, engine_kw, mix, published["vocab_size"], args.seed)
        common.log(event="setup", seconds=time.perf_counter() - t_setup, device=devices[0].device_kind,
                   memory_peak_bytes=common.memory_peak_bytes(devices))
        rate = args.start
        for step in range(args.steps):
            requests = traffic.schedule(dict(mix, rate_per_s=rate, initial_burst=0), args.seed + step, args.seconds,
                                        published["vocab_size"])
            calls, t0, lateness, abandoned = serve.drive(server, requests, args.seconds, 90.0)
            seen = serve.client_metrics(calls, t0, args.seconds)
            row = {
                "rate_per_s": rate, "sent": len(calls), "failed": sum(c.status != 200 for c in calls),
                "abandoned": abandoned,
                "offered_tokens_per_s": sum(r["max_tokens"] for r in requests) / args.seconds,
                "tokens_per_s": seen["tokens_in_window"] / args.seconds,
                "in_flight_half": in_flight(calls, t0 + args.seconds / 2),
                "in_flight_close": in_flight(calls, t0 + args.seconds),
                "ttft_ms_p50": common.percentile(seen["ttft_ms"], 50),
                "ttft_ms_p90": common.percentile(seen["ttft_ms"], 90),
                "gap_ms_p50": common.percentile(seen["gaps_ms"], 50),
                "gap_ms_p95": common.percentile(seen["gaps_ms"], 95),
                "generator_late_ms_max": 1e3 * max(lateness, default=0.0),
                "memory_peak_bytes": common.memory_peak_bytes(devices),
            }
            print(json.dumps(row), flush=True)
            if row["in_flight_close"] > args.stop_backlog:
                break
            rate *= args.factor
    finally:
        server.stop()
        frontdoor.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
