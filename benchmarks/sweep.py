#!/usr/bin/env python3
"""Find the rate an open-loop cell is pinned to: one boot, one warm state,
one window per offered rate. Run once, when a cell is defined; the cell's
mix then holds ``rate`` as a number and no run searches for one.

    python benchmarks/sweep.py --workload <cell> --seed <n> --seconds 10 \\
        --rates 4000,5200,6400,8000,9600

A step holds when nothing failed, good answers per second are at least 99%
of the offered rate, and the requests in flight at its end are no more than
at its middle (a quarter and 8 of room: the count is a Poisson reading). The knee is the highest step that holds.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import reduce  # noqa: E402
import run  # noqa: E402


def in_flight(rec: dict[str, np.ndarray], at: float) -> int:
    return int(((rec["due"] <= at) & (rec["done"] > at)).sum())


def main() -> int:
    ap = run.parser(__doc__.split("\n\n")[0])
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    work = Path(tempfile.mkdtemp(prefix="benchmark-sweep-"))
    rig = run.Rig(args, work)
    steps = []
    try:
        rig.boot()
        for rate in (float(r) for r in args.rates.split(",")):
            win = rig.window(args.seconds, rate=rate)
            rec, stats, t0 = win["rec"], win["stats"], win["t0"]
            good = int(rec["good"].sum())
            step = {
                "offered_per_s": rate, "attempted": len(win["records"]),
                "failed": len(win["records"]) - good,
                "good_per_s": stats["reviews_per_s"],
                "p50_ms": stats["latency_p50_ms"],
                "p99_ms": stats["latency_p99_ms"],
                "late_p99_ms": stats["late_p99_ms"],
                "in_flight_middle": in_flight(rec, t0 + args.seconds / 2),
                "in_flight_end": in_flight(rec, t0 + args.seconds),
                "connections": stats["connections"],
            }
            ctx = {"before": win["before"], "after": win["after"]}
            for name in ("rows_per_batch", "queue_wait_ms_per_req",
                         "dispatch_wait_ms_per_batch", "compiles_in_window"):
                step[name] = reduce.read_layer_metric(name, ctx)
            step["holds"] = bool(
                step["failed"] == 0
                and step["good_per_s"] >= 0.99 * len(win["records"]) / args.seconds
                and step["in_flight_end"] <= 1.25 * step["in_flight_middle"] + 8)
            steps.append(step)
            print(json.dumps(step), flush=True)
    finally:
        rig.stop()
        shutil.rmtree(work, ignore_errors=True)
    knee = max((s["offered_per_s"] for s in steps if s["holds"]), default=None)
    print(json.dumps({"knee_per_s": knee,
                      "rate": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
