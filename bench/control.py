"""Run a cell's control on the chip, on several seeds in one process.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds <s> \
        [--fault <name>]

The control is the program with one stated guarantee broken
(``bench/controls.py``, chosen by the cell's driver); with ``--fault``
one of the driver's planted faults runs instead.  Every run must come
out ``correct: false``.  Prints one result line per seed, then a
summary line.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from run import ROOT, chips, execute, load_spec, resolve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault")
    args = ap.parse_args(argv)
    spec = load_spec()
    wl, cfg, traffic = resolve(spec, args.workload)
    devices = chips(wl)
    if devices is None:
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.controls import CONTROLS, FAULTS
    broken = (FAULTS[traffic["driver"]][args.fault] if args.fault
              else CONTROLS[traffic["driver"]])
    failed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        with broken():
            result, _ = execute(spec, wl, cfg, traffic, seed=seed,
                                seconds=args.seconds, trace=False,
                                devices=devices)
        print(json.dumps({"seed": seed, **result}), flush=True)
        failed.append(not result["correct"])
    print(json.dumps({"control": args.workload, "fault": args.fault,
                      "all_incorrect": all(failed)}), flush=True)
    return 0 if all(failed) else 1


if __name__ == "__main__":
    sys.exit(main())
