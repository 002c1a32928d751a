#!/usr/bin/env python3
"""``readings.py`` for a cell whose configuration has conv layers: the
same readings over many seeds in ONE process, with the conv layers'
fault read beside the int8 control, and each run's end-to-end metrics
printed with its numbers.

``shifted_window``: the reference with every convolution reaching one
position further back (``reference_lfm2``'s ``precision``), put in the
program's place as a control is: what a window taken one row late would
serve.  It has to read NOT correct under the cell's limits, as the int8
control and an altered token do.  ``bf16``: the reference with every
linear layer's operands rounded to the configuration's own bfloat16, put
there the same way: what rounding alone costs, printed beside the
program's reading and judged by neither side.  Not part of a benchmark
run.  Exits 1 where the program reads not correct, or a control or a
fault reads correct.

    python3 benchmark/readings_conv.py --workload lfm2.short_chat_c32 \\
        --seeds 1,2,3 --seconds 12 --faults altered_token \\
        --control-seeds 2 [--dry-run]
"""

import argparse
import json
import sys

import compare
import run
from kinds import generate_pinned

CONV_FAULTS = ("shifted_window",)
PEERS = ("bf16",)       # read as a control is, and only printed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds, from the first, also read "
                         "the controls and the faults")
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args(argv)
    # read through the kind's control path: the reference in the
    # program's place, here with the fault in it
    generate_pinned.CONTROLS = generate_pinned.CONTROLS + CONV_FAULTS + PEERS
    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        one = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0,
                                 dry_run=args.dry_run)
        broken = [b for b in generate_pinned.CONTROLS + tuple(
            f for f in args.faults.split(",") if f)
            if n < args.control_seeds]
        result, low = run.execute(one, broken)
        row = {"seed": seed, "correct": result["correct"],
               "failed": result["failed"],
               "metrics": {k: v["value"] for k, v in
                           result["metrics"].items()},
               "memory_peak_bytes": result["device"].get(
                   "memory_peak_bytes"),
               "program": {k: v[0] for k, v in result["compared"].items()},
               "broken": {b: {"correct": compare.verdict(numbers),
                              "compared": {k: [x["value"], x["limit"]]
                                           for k, x in numbers.items()}}
                          for b, numbers in low.items()}}
        rows.append(row)
        print("READING " + json.dumps(row), flush=True)
    sound = all(r["correct"] for r in rows)
    caught = not any(b["correct"] for r in rows for name, b in
                     r["broken"].items() if name not in PEERS)
    print("READINGS program correct on every seed: {}; every control and "
          "fault not correct: {}".format(sound, caught), flush=True)
    return 0 if sound and caught else 1


if __name__ == "__main__":
    sys.exit(main())
