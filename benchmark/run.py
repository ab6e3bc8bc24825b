"""Run one benchmark cell and print its result as one JSON line.

    python3 benchmark/run.py --workload ycsb_a.bulk_load --seed 7 \\
        --seconds 30 --trace 0

from the root of a checkout, on a machine with the chips the cell asks
for. The last line of standard output is the result; the compared
numbers and their limits are also the last lines of standard error.
Without a TPU, or with fewer chips than the cell needs, it prints no
result and exits non-zero.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except harness.Failure as err:
        sys.exit(f'benchmark: {err}')
    for name, c in out['checks'].items():
        print(f'check {name}: {c["value"]} (limit {c["limit"]})',
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == '__main__':
    main()
