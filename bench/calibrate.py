#!/usr/bin/env python3
"""Read the two ends a limit is set between, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 --control-seeds 1,2,3

For each of ``--seeds`` it makes one whole run of the cell (set-up,
window, check) and prints the numbers compared; for each of
``--control-seeds`` it puts the configuration's control (the reference in
the precision below the stated one, or with a stated guarantee broken) in
the program's place on the same requests and prints the same numbers.
The benchmark's own runs never run the control; ``PERF.md`` gives the
readings and the limits set from them.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: requests the control answers per seed
CONTROL_REQUESTS = 64


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.find_cell(args.workload)
    harness.check_device(cell.chips, require_tpu=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        work = harness.workload(cell, seed)
        indices = [i for i in range(1 << 20) if work.keep(cell.op, i)]
        indices = indices[:CONTROL_REQUESTS]
        t = time.perf_counter()
        checks = work.check(cell.op, work.control(cell.op, indices))
        print(json.dumps({"control_seed": seed, "requests": len(indices),
                          "seconds": time.perf_counter() - t,
                          "checks": checks}), flush=True)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        line, notes = harness.run(cell.name, seed, args.seconds, False,
                                  t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "checks": line["checks"],
                          "metrics": line["metrics"],
                          "done": notes["requests_done"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
