"""Request kind ``op_ticks`` (``loop: fixed_rate``): YCSB's run phase.

Set-up loads every record into one ``GeneralDocSet``, which stays
resident. Each request is one tick of ``ops_per_tick`` operations drawn
by ``gen.ycsb.run_ticks``: the tick's updates go in one
``apply_changes_batch``, then its reads are answered by one
``materialize_many``, so a read sees every update that came before it.

Warm-up serves the window's ticks, in order, on a scratch doc set until
``WARM_QUIET_TICKS`` ticks in a row have compiled nothing; past that it
serves only the later ticks whose shape it has not met yet, each with
its changes renumbered to follow what the scratch set holds. A tick's
shape is what the program pads its arrays by: its counts of updates,
updated fields, updated docs and reads, each rounded up as the program
rounds them, to the next 2^k or 3 * 2^(k-1). A shape first met in the
window would compile there; the window's compile count is in every
result line.

``check()`` compares with ``reference.py`` the answers of
``SAMPLE_TICKS`` ticks drawn from the seed and of the last tick: it
counts the documents read whose view differs from the reference, and
every document read by a sampled tick that was never answered.
"""

import gc
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import reference  # noqa: E402
import served  # noqa: E402
from gen import ycsb  # noqa: E402

WARM_QUIET_TICKS = 64
SAMPLE_TICKS = 64


def _bucket(n):
    p = 1
    while p < n:
        p <<= 1
    half = (p >> 1) + (p >> 2)
    return half if n <= half and half % 8 == 0 else p


def _shape(updates, reads):
    changes = [c for cs in updates.values() for c in cs]
    fields = {(d, c['ops'][0]['key']) for d, cs in updates.items()
              for c in cs}
    return tuple(_bucket(n) for n in (len(changes), len(fields),
                                      len(changes) + len(fields),
                                      len(updates), len(reads)))


def _renumber(updates, seqs):
    """``updates`` with each doc's changes numbered on from ``seqs``."""
    out = {}
    for doc_id, cs in updates.items():
        out[doc_id] = []
        for c in cs:
            seqs[doc_id] = seqs.get(doc_id, 0) + 1
            out[doc_id].append(dict(c, seq=seqs[doc_id]))
    return out


def build(config, mix, seed, seconds, rec, path=None):
    return OpTicks(config, mix, seed, seconds, rec,
                   path or served.DocSetPath())


class OpTicks:
    def __init__(self, config, mix, seed, seconds, rec, path):
        self.config, self.mix, self.seed = config, mix, seed
        self.rec = rec
        self.path = path
        self.n_docs = config['recordcount']
        self.n_requests = int(round(mix['rate_per_s'] * seconds))
        self.setup_split = {}

    def prepare(self, watch):
        t0 = time.perf_counter()
        cfg = self.config
        self.ids, self.history = ycsb.load_changes(
            self.n_docs, cfg['fieldcount'], cfg['fieldlength'], self.seed)
        self.batch = dict(zip(self.ids, self.history))
        self.ticks = ycsb.run_ticks(cfg, self.n_requests,
                                    self.mix['ops_per_tick'], self.seed)
        rng = np.random.default_rng([self.seed, 4])
        k = min(SAMPLE_TICKS, self.n_requests - 1)
        self.sampled = set(rng.choice(self.n_requests - 1, k,
                                      replace=False).tolist())
        self.sampled.add(self.n_requests - 1)
        self.answers = {}
        t1 = time.perf_counter()
        self.warm_ticks = self._warm(watch)
        gc.collect()
        t2 = time.perf_counter()
        self.doc_set = self.path.new(self.n_docs)
        self.path.apply(self.doc_set, self.batch)
        self.path.read(self.doc_set, self.ids[-1:])
        self.setup_split = {'data_s': t1 - t0, 'warmup_s': t2 - t1,
                            'warm_ticks': self.warm_ticks,
                            'preload_s': time.perf_counter() - t2}

    def _warm(self, watch):
        scratch = self.path.new(self.n_docs)
        self.path.apply(scratch, self.batch)
        self.path.read(scratch, self.ids[-1:])
        seqs = {}                        # doc -> the client's seq there
        seen = set()
        quiet = served_ticks = 0
        for updates, reads, _ in self.ticks:
            shape = _shape(updates, reads)
            if quiet >= WARM_QUIET_TICKS and shape in seen:
                continue
            seen.add(shape)
            before = watch.count
            if updates:
                self.path.apply(scratch, _renumber(updates, seqs))
            if reads:
                self.path.read(scratch, reads)
            served_ticks += 1
            quiet = quiet + 1 if watch.count == before else 0
        return served_ticks

    def serve(self, k):
        updates, reads, n_ops = self.ticks[k]
        if updates:
            with self.rec.span('apply'):
                self.path.apply(self.doc_set, updates)
        views = []
        if reads:
            with self.rec.span('read'):
                views = self.path.read(self.doc_set, reads)
        if k in self.sampled:
            self.answers[k] = views
        return n_ops

    def release(self):
        self.doc_set = None

    def check(self):
        need = {d for k in self.sampled for d in self.ticks[k][1]}
        index = {doc_id: i for i, doc_id in enumerate(self.ids)}
        docs = {d: reference.replay(self.history[index[d]]) for d in need}
        bad = 0
        for k, (updates, reads, _) in enumerate(self.ticks):
            for doc_id, changes in updates.items():
                if doc_id in docs:
                    for c in changes:
                        docs[doc_id].apply(c)
            if k not in self.sampled:
                continue
            views = self.answers.get(k)
            if views is None or len(views) != len(reads):
                bad += len(reads)          # a sampled tick never answered
                continue
            for doc_id, view in zip(reads, views):
                bad += view != docs[doc_id].plain()
        return {'docs_wrong': (bad, 0)}
