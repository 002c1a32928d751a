#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, over many seeds in ONE process
(set-up is long), for the program and for the controls: the reference in
the precision below the configuration's, put in the program's place,
and for the faults a cell can have, planted in what the window produced.
Every reading goes through the run's own comparison (``compare.verdict``
over the cell's limits): the program has to read correct, each control
and each fault NOT correct.  The limits in ``limits/<cell>.json`` are set
from what this prints (PERF.md gives the readings).  Not part of a
benchmark run.  Exits 1 where any verdict is the wrong way round.

    python3 benchmark/readings.py --workload <cell> --seeds 1,2,3 \\
        --seconds 8 --control-seeds 3 [--faults altered_token] [--dry-run]
"""

import argparse
import json
import sys

import compare
import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", default="int8")
    ap.add_argument("--faults", default="")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds, from the first, also read "
                         "the controls and the faults")
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args(argv)
    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        one = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0,
                                 dry_run=args.dry_run)
        broken = [b for names in (args.controls, args.faults)
                  for b in names.split(",") if b and n < args.control_seeds]
        result, low = run.execute(one, broken)
        row = {"seed": seed, "correct": result["correct"],
               "program": {k: v[0] for k, v in result["compared"].items()},
               "broken": {b: {"correct": compare.verdict(numbers),
                              "compared": {k: [x["value"], x["limit"]]
                                           for k, x in numbers.items()}}
                          for b, numbers in low.items()}}
        rows.append(row)
        print("READING " + json.dumps(row), flush=True)
    sound = all(r["correct"] for r in rows)
    caught = not any(b["correct"] for r in rows for b in r["broken"].values())
    print("READINGS program correct on every seed: {}; every control and "
          "fault not correct: {}".format(sound, caught), flush=True)
    return 0 if sound and caught else 1


if __name__ == "__main__":
    sys.exit(main())
