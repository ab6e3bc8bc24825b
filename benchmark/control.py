"""The control: the plain reference put in the program's place, with one
guarantee of the configuration broken.

The configuration guarantees that every update applied is readable once
the call that applied it returns. The control answers each read from the
reference state as it stood before the last change applied to that
document: a stale read, the step a faster server would be tempted to
take. The comparison that decides ``correct`` has to fail it.

    python3 benchmark/control.py --workload ycsb_a.target_ticks \\
        --seed 7 8 9 --seconds 30

runs one cell with the control in place, once per seed, on the machine's
chip like a benchmark run, and prints one line of compared numbers per
seed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import reference  # noqa: E402


def _stale_apply(doc, changes):
    """Apply ``changes`` to a reference doc; return its plain state from
    before the last of them."""
    for c in changes[:-1]:
        doc.apply(c)
    before = doc.plain()
    doc.apply(changes[-1])
    return before


class StaleDocSetPath:
    """``served.DocSetPath`` answered by the stale reference."""

    class _Set:
        def __init__(self):
            self.docs = {}
            self.views = {}

    def new(self, n_docs):
        return self._Set()

    def apply(self, doc_set, batch):
        for doc_id, changes in batch.items():
            doc = doc_set.docs.setdefault(doc_id, reference.RefDoc())
            doc_set.views[doc_id] = _stale_apply(doc, changes)

    def read(self, doc_set, doc_ids):
        return [doc_set.views[d] for d in doc_ids]


def run(workload, seed, seconds, t_start, root=harness.ROOT,
        require_tpu=True):
    return harness.run(workload, seed, seconds, False, t_start, root=root,
                       require_tpu=require_tpu, path=StaleDocSetPath())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, nargs='+', required=True)
    ap.add_argument('--seconds', type=float, required=True)
    args = ap.parse_args(argv)
    t_start = T_START
    for seed in args.seed:
        try:
            out = run(args.workload, seed, args.seconds, t_start)
        except harness.Failure as err:
            sys.exit(f'control: {err}')
        print(json.dumps({'workload': args.workload, 'seed': seed,
                          'correct': out['correct'],
                          'checks': out['checks']}), flush=True)
        t_start = time.perf_counter()


if __name__ == '__main__':
    main()
