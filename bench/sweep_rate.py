"""Find the highest rate an open-loop cell sustains: a sweep on the chip.

    python bench/sweep_rate.py --workload <name> --rates 100,200,400 \
        --seconds 10 --seed <n> [--write]

Each rate runs in a process of its own (one process holds the chip),
from the lowest up, and the sweep stops after the first rate that is not
sustained.  A rate is sustained when every request due in the window
was answered, the window closed (its last request answered) within
three turns of the driver's loop (its ``turn_s`` counter; at least one
second) of its nominal end, and the mean latency of the last quarter of
requests is at most twice that of the second quarter plus 100 ms: no
backlog grows.  One rule decides every cell.
Prints the table as JSON lines; ``--write`` stores 4/5 of the highest
sustained rate as ``rate_per_s`` in the cell's traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np

from run import BENCH, chips, execute, load_spec, resolve


def sustained(counters: dict, failed: int, seconds: float
              ) -> tuple[bool, float, float, float]:
    lat = np.asarray(counters["latency_s"], float)
    over = counters["window_s"] - seconds
    n = lat.shape[0]
    q2 = float(np.mean(lat[n // 4:n // 2]))
    q4 = float(np.mean(lat[3 * n // 4:]))
    turn = float(np.median(counters["turn_s"]))
    ok = failed == 0 and over <= max(1.0, 3 * turn) and q4 <= 2 * q2 + 0.1
    return ok, q2, q4, over


def child(args) -> int:
    spec = load_spec()
    wl, cfg, traffic = resolve(spec, args.workload)
    devices = chips(wl)
    if devices is None:
        return 2
    traffic = dict(traffic, rate_per_s=args.rate)
    result, counters = execute(spec, wl, cfg, traffic, seed=args.seed,
                               seconds=args.seconds, trace=False,
                               devices=devices)
    ok, q2, q4, over = sustained(counters, result["failed"], args.seconds)
    print(json.dumps({"rate_per_s": args.rate, "sustained": ok,
                      "attempted": result["attempted"],
                      "failed": result["failed"],
                      "mean_q2_ms": q2 * 1e3, "mean_q4_ms": q4 * 1e3,
                      "overrun_s": over,
                      "generator_late_ms": counters["late_s"] * 1e3,
                      "correct": result["correct"],
                      "metrics": result["metrics"]}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates")
    ap.add_argument("--rate", type=float)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    if args.rate is not None:
        return child(args)
    best = None
    for rate in (float(r) for r in args.rates.split(",")):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--rate", str(rate), "--seconds",
             str(args.seconds), "--seed", str(args.seed)],
            capture_output=True, text=True, timeout=600)
        lines = out.stdout.strip().splitlines()
        if out.returncode or not lines:
            print(out.stderr[-2000:], file=sys.stderr)
            return 1
        row = json.loads(lines[-1])
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
        best = rate
    print(json.dumps({"workload": args.workload, "highest_sustained": best,
                      "rate_per_s": None if best is None else 0.8 * best}))
    if args.write and best is not None:
        _, _, traffic = resolve(load_spec(), args.workload)
        name = {w["name"]: w for w in load_spec()["workloads"]}[
            args.workload]["traffic"]
        traffic["rate_per_s"] = round(0.8 * best, 3)
        with open(os.path.join(BENCH, "traffic", name + ".json"), "w") as f:
            f.write(json.dumps(traffic) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
