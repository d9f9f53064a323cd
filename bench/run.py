#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the compared numbers beside their limits as the last lines of
standard error, and one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``.  Without a TPU, with
fewer chips than the cell asks for, or without the repository's program
beside it, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        from bench import harness
        import repro.service  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"bench: cannot import the program: {e}", file=sys.stderr)
        return 2
    try:
        line, notes = harness.run(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(f"[notes] {json.dumps(notes, sort_keys=True)}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
