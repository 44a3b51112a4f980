"""Readings of the correctness numbers, the program's and the control's.

    python bench/control.py --workload <cell> --seconds <s> --seeds 11,12,13

Runs the cell once per seed in one process, each with a window of
``--seconds`` at the cell's own load, and judges the served requests
twice through the run's own comparison: the program's outputs (what a
benchmark run judges) and the control's, the plain reference one
precision lower (bfloat16 operands, float32 accumulation) put in the
program's place on the same served states. Prints one JSON line per seed.
The limits in ``bench/configs/`` are set between the two: above the
program's readings, below the control's. The benchmark's own runs do not
run this. Needs the chip, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    harness, cell = run.prepare(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run(cell, seed, args.seconds, False,
                        time.perf_counter(), control=True,
                        log=run.log)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "program": {k: c["value"]
                                      for k, c in r["checks"].items()},
                          "control_correct": r["control"]["correct"],
                          "control": {k: c["value"] for k, c
                                      in r["control"]["checks"].items()},
                          "metrics": r["metrics"], "device": r["device"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
