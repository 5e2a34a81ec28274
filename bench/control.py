#!/usr/bin/env python3
"""Readings of the correctness check's control: the plain reference in
the program's place at one precision below the configuration's, on the
checked streams or reads of a cell at its own size (the traffic kind's
``control``).  Pure NumPy; it needs no chip.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 \
        [--seconds <s>] [--minutes <m>]

Prints one JSON line per seed: the readings beside their limits.  One
reading over its limit is enough for the check to be worth its name.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from benchkit import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="window length whose schedule is checked")
    ap.add_argument("--minutes", type=int, default=10,
                    help="minutes each checked device uploaded")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    kind = spec.traffic_kind(ROOT, cell.mix["kind"])
    for seed in args.seeds:
        r = kind.control(cell.cfg, cell.mix, seed, args.seconds,
                         args.minutes)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": {k: {"value": v,
                                           "limit": kind.LIMITS[k]}
                                       for k, v in r.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
