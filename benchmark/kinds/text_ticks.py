"""Request kind ``text_ticks`` (``loop: fixed_rate``): concurrent words
reaching a sync server that holds a fleet of shared texts.

Set-up makes every document's history with ``gen.text_b2`` and loads
the first ``chars_per_doc`` characters of each (the text's creation and
whole rounds of words) into one ``GeneralDocSet``, which stays resident.
The served fleet is resumed from a snapshot, as a restarted server
resumes its store: the histories go into a doc set on the host's CPU
device in one ``apply_changes_batch``, whose ``save_snapshot`` the chip's
doc set loads (``GeneralDocSet.load_snapshot`` builds the device mirror
from the host columns, with no program). Replaying ~2M ops through the
chip instead compiles the fused programs once per capacity and text
size they pass, ~19 shapes and ~10 minutes of a cold start. A stand-in
path (the control, the tests) that cannot load a snapshot gets the
histories as one batch.

Each request is one tick: ``changes_per_tick`` distinct documents drawn
from the seed each receive their next change, one word, in one
``apply_changes_batch``; then one ``materialize_many`` reads those
documents, the texts the writers are sent back. A document's changes
arrive in round order, writer by writer, so the second and third words
of a round reach it after a concurrent word already applied: late
concurrent inserts, which the program orders by re-ordering the text
whole.

Warm-up serves ticks on the preloaded doc set itself, the first of the
drawn ticks, so the window serves the fleet as it stands after them. A
tick's shape is what the program pads for it: its counts of changes,
ops, documents and reads and the node count of its longest text, each
rounded up as the program rounds them (to the next 2^k or
3 * 2^(k-1)), and whether any of its words is late. Warm-up goes on
until it has met the shape of every tick of the window and then
``WARM_QUIET_TICKS`` ticks in a row have compiled nothing, or until
``WARM_MAX_TICKS``. A shape first met in the window would compile
there; the window's compile count is in every result line.

``check()`` compares with ``reference_text.py`` the answers of
``SAMPLE_TICKS`` ticks drawn from the seed and of the last tick, each
document read against the reference's replay of the history it had
received: it counts the documents whose view differs, and every
document read by a sampled tick that was never answered.
"""

import gc
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import reference_text  # noqa: E402
import served  # noqa: E402
from gen import text_b2  # noqa: E402

SAMPLE_TICKS = 16
WARM_QUIET_TICKS = 4
WARM_MAX_TICKS = 48


def build(config, mix, seed, seconds, rec, path=None, devices=None):
    # one chip: the doc set stays on the default device, devices[0]
    return TextTicks(config, mix, seed, seconds, rec,
                     path or ResumedDocSetPath())


class ResumedDocSetPath(served.DocSetPath):
    """The served path, whose fleet is resumed from a snapshot."""

    def load(self, n_docs, batch):
        import jax
        from automerge_tpu.sync.general_doc_set import GeneralDocSet
        host = GeneralDocSet(n_docs, device=jax.devices('cpu')[0])
        host.apply_changes_batch(batch)
        return GeneralDocSet.load_snapshot(host.save_snapshot())


def _bucket(n):
    p = 1
    while p < n:
        p <<= 1
    half = (p >> 1) + (p >> 2)
    return half if n <= half and half % 8 == 0 else p


def make_ticks(config, n_ticks, per_tick, seed):
    """``(ids, history, preload, ticks)``: each document's changes in
    the order it receives them, how many of them the preload holds, and
    per tick ``(updates, reads, n_ops, shape)``."""
    n_docs, n_w = config['docs'], config['writers']
    k = config['word_chars']
    rounds, rest = divmod(config['chars_per_doc'], n_w * k)
    if rest:
        raise ValueError('chars_per_doc is not whole rounds of words')
    tick_docs = text_b2.ticks(n_docs, n_ticks, per_tick, seed)
    picks = np.bincount(np.asarray(tick_docs, np.int64).ravel(),
                        minlength=n_docs)
    ids = text_b2.doc_ids(n_docs)
    history = []
    for d in range(n_docs):
        h = text_b2.TextHistory(d, n_w, k, seed)
        h.add_rounds(rounds + -(-int(picks[d]) // n_w))
        history.append(h.changes)
    preload = 1 + rounds * n_w
    cursor = [preload] * n_docs
    chars = [rounds * n_w * k] * n_docs
    ticks = []
    for docs in tick_docs:
        updates = {}
        late = False
        for d in docs:
            c = history[d][cursor[d]]
            updates[ids[d]] = [c]
            late |= c['actor'] != history[d][0]['actor']
            chars[d] += k
            cursor[d] += 1
        n_ops = sum(len(c['ops']) for cs in updates.values() for c in cs)
        shape = tuple(_bucket(n) for n in (
            len(docs), n_ops, max(chars[d] + 1 for d in docs))) + (late,)
        ticks.append((updates, [ids[d] for d in docs], n_ops, shape))
    return ids, history, preload, ticks


class TextTicks:
    def __init__(self, config, mix, seed, seconds, rec, path):
        self.config, self.mix, self.seed = config, mix, seed
        self.rec = rec
        self.path = path
        self.n_docs = config['docs']
        self.n_requests = int(round(mix['rate_per_s'] * seconds))
        self.setup_split = {}

    def prepare(self, watch):
        t0 = time.perf_counter()
        self.ids, self.history, self.preload, ticks = make_ticks(
            self.config, WARM_MAX_TICKS + self.n_requests,
            self.mix['changes_per_tick'], self.seed)
        rng = np.random.default_rng([self.seed, 4])
        k = min(SAMPLE_TICKS, self.n_requests - 1)
        self.sampled = set(rng.choice(self.n_requests - 1, k,
                                      replace=False).tolist())
        self.sampled.add(self.n_requests - 1)
        self.answers = {}
        t1 = time.perf_counter()
        batch = {doc_id: h[:self.preload]
                 for doc_id, h in zip(self.ids, self.history)}
        load = getattr(self.path, 'load', None)
        if load is not None:
            self.doc_set = load(self.n_docs, batch)
        else:
            self.doc_set = self.path.new(self.n_docs)
            self.path.apply(self.doc_set, batch)
        del batch
        self.path.read(self.doc_set, self.ids[-1:])
        t2 = time.perf_counter()
        warm = self._warm(watch, ticks)
        self.warmed = ticks[:warm]
        self.ticks = ticks[warm:warm + self.n_requests]
        gc.collect()
        self.setup_split = {'data_s': t1 - t0, 'preload_s': t2 - t1,
                            'warm_ticks': warm,
                            'warmup_s': time.perf_counter() - t2}

    def _warm(self, watch, ticks):
        """Serve ticks until every shape of the window has been met and
        the last ``WARM_QUIET_TICKS`` compiled nothing; returns how many
        were served."""
        quiet = 0
        for w in range(WARM_MAX_TICKS):
            window = {t[3] for t in ticks[w:w + self.n_requests]}
            if quiet >= WARM_QUIET_TICKS and window <= {
                    t[3] for t in ticks[:w]}:
                return w
            updates, reads, _, _ = ticks[w]
            before = watch.count
            self.path.apply(self.doc_set, updates)
            self.path.read(self.doc_set, reads)
            quiet = quiet + 1 if watch.count == before else 0
        return WARM_MAX_TICKS

    def serve(self, k):
        updates, reads, n_ops, _ = self.ticks[k]
        with self.rec.span('apply'):
            self.path.apply(self.doc_set, updates)
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
        docs = {d: reference_text.replay(
            self.history[index[d]][:self.preload]) for d in need}
        bad = 0
        # the warm-up's ticks come first, numbered below 0
        for k, (updates, reads, _, _) in enumerate(
                self.warmed + self.ticks, -len(self.warmed)):
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
