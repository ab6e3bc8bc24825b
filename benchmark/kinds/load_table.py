"""Request kind ``load_table`` (``loop: bulk``): YCSB's load phase.

Each iteration loads every record of the configuration into a fresh
``GeneralDocSet`` in one ``apply_changes_batch`` and reads back a seeded
sample of ``SAMPLE_DOCS`` documents (the last record among them) through
``materialize_many``. ``check()`` compares every sample with the
reference and counts the documents that differ.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import reference  # noqa: E402
import served  # noqa: E402
from gen import ycsb  # noqa: E402

SAMPLE_DOCS = 64


def build(config, mix, seed, seconds, rec, path=None, devices=None):
    # one chip: the doc set stays on the default device, devices[0]
    return LoadTable(config, mix, seed, rec, path or served.DocSetPath())


class LoadTable:
    def __init__(self, config, mix, seed, rec, path):
        self.config, self.mix, self.seed = config, mix, seed
        self.rec = rec
        self.path = path
        self.rng = np.random.default_rng([seed, 3])
        self.n_docs = config['recordcount']
        self.setup_split = {}

    def prepare(self, watch):
        t0 = time.perf_counter()
        self.ids, self.history = ycsb.load_changes(
            self.n_docs, self.config['fieldcount'],
            self.config['fieldlength'], self.seed)
        self.batch = dict(zip(self.ids, self.history))
        self.n_ops = sum(len(c['ops']) for cs in self.history for c in cs)
        t1 = time.perf_counter()
        self.answers = []
        self.iteration()
        self.answers = []
        self.setup_split = {'data_s': t1 - t0,
                            'warmup_s': time.perf_counter() - t1}

    def iteration(self):
        with self.rec.span('new'):
            doc_set = self.path.new(self.n_docs)
        with self.rec.span('apply'):
            self.path.apply(doc_set, self.batch)
        k = SAMPLE_DOCS
        picks = sorted(self.rng.choice(self.n_docs - 1, k - 1,
                                       replace=False).tolist())
        idx = picks + [self.n_docs - 1]
        ids = [self.ids[i] for i in idx]
        with self.rec.span('read'):
            views = self.path.read(doc_set, ids)
        self.answers.append((idx, views))
        return self.n_ops

    def release(self):
        pass

    def check(self):
        want = {}
        bad = 0
        for idx, views in self.answers:
            if len(views) != len(idx):
                bad += len(idx)
                continue
            for i, view in zip(idx, views):
                if i not in want:
                    want[i] = reference.replay(self.history[i]).plain()
                bad += view != want[i]
        return {'docs_wrong': (bad, 0)}
