"""Concurrent words in shared texts, as Automerge changes to a fleet.

The op shape is crdt-benchmarks B2.3 (github.com/dmonad/crdt-benchmarks,
"concurrently insert N words at random positions"), whose clients are
two; the writer count and the word length are the caller's (BASELINE
config 2 has three writers). Here each document is one
``Automerge.Text`` under root key ``text``, typed by
``writers`` actors (``a1``, ``a2``, ...). The first change, by ``a1``,
makes the text; then, round after round, every writer inserts one word
of ``word_chars`` characters at a uniform random position of the text as
it stood at the end of the previous round. A word is one change: an
``ins`` and a ``set`` per character, each character inserted after the
one before it. Each change depends on every writer's change of the
previous round, so a round's words are concurrent with one another.

The order follows from the ids alone, with no RGA replay. Every writer
has seen the same text, so each round's words take the same element
counters, all above every counter in the text: a new word goes directly
after its parent element, ahead of the parent's older children, and the
words of one round on one parent lie in descending actor order. The
text is kept as a list of element ids, and a round's words are inserted
right after their parents in ascending actor order.

Random draws come from numpy's PCG64, seeded per document from the
run's seed.
"""

import numpy as np

ROOT_ID = '00000000-0000-0000-0000-000000000000'
ALPHABET = 'abcdefghijklmnopqrstuvwxyz'


def doc_ids(n_docs):
    return [f'text{i}' for i in range(n_docs)]


def text_obj(doc):
    """The text object's id in document ``doc``: unique in the fleet."""
    return f'{doc:08x}-7e47-4000-8000-000000000000'


class TextHistory:
    """One document's history, made round by round.

    ``changes`` lists the changes in the order a server receives them:
    the text's creation, then each round's words, writer by writer.
    ``order`` is the text's element ids in document order, and ``chars``
    maps each to its character."""

    def __init__(self, doc, n_writers, word_chars, seed):
        self.doc = doc
        self.obj = text_obj(doc)
        self.actors = [f'a{w + 1}' for w in range(n_writers)]
        self.word_chars = word_chars
        self.rng = np.random.default_rng([seed, 27, doc])
        self.rounds = 0
        self.order = []
        self.chars = {}
        self.changes = [{'actor': self.actors[0], 'seq': 1, 'deps': {},
                         'ops': [{'action': 'makeText', 'obj': self.obj},
                                 {'action': 'link', 'obj': ROOT_ID,
                                  'key': 'text', 'value': self.obj}]}]

    def _seq(self, w, r):
        """Writer ``w``'s seq for its word of round ``r`` (from 1);
        round 0 is the text's creation, by writer 0 alone."""
        return r + 1 if w == 0 else r

    def add_rounds(self, n):
        k, n_w = self.word_chars, len(self.actors)
        for _ in range(n):
            r = self.rounds + 1
            base = k * (r - 1)               # the text's highest counter
            after = self.rng.integers(0, len(self.order) + 1, n_w) - 1
            letters = self.rng.integers(0, len(ALPHABET), (n_w, k))
            words = []
            for w, a in enumerate(self.actors):
                parent = self.order[after[w]] if after[w] >= 0 \
                    else '_head'
                if r == 1:
                    deps = {} if w == 0 else {self.actors[0]: 1}
                else:
                    deps = {b: self._seq(v, r - 1)
                            for v, b in enumerate(self.actors) if v != w}
                ops = []
                key = parent
                for i in range(k):
                    elem = base + i + 1
                    c = ALPHABET[letters[w, i]]
                    ops.append({'action': 'ins', 'obj': self.obj,
                                'key': key, 'elem': elem})
                    key = f'{a}:{elem}'
                    ops.append({'action': 'set', 'obj': self.obj,
                                'key': key, 'value': c})
                    self.chars[key] = c
                words.append((int(after[w]), w))
                self.changes.append({'actor': a, 'seq': self._seq(w, r),
                                     'deps': deps, 'ops': ops})
            # from the last parent back, so earlier positions hold; on
            # one parent, ascending actors leave them in descending order
            for pos, w in sorted(words, key=lambda x: (-x[0], x[1])):
                a = self.actors[w]
                self.order[pos + 1:pos + 1] = [f'{a}:{base + i + 1}'
                                               for i in range(k)]
            self.rounds = r

    def text(self):
        return ''.join(self.chars[e] for e in self.order)


def histories(n_docs, n_writers, word_chars, rounds, seed):
    """``TextHistory`` of each document of the fleet, ``rounds`` rounds
    made."""
    out = []
    for d in range(n_docs):
        h = TextHistory(d, n_writers, word_chars, seed)
        h.add_rounds(rounds)
        out.append(h)
    return out



def ticks(n_docs, n_ticks, per_tick, seed):
    """The docs of each tick: ``per_tick`` distinct documents drawn
    uniformly from the fleet."""
    rng = np.random.default_rng([seed, 28])
    return [rng.choice(n_docs, per_tick, replace=False).tolist()
            for _ in range(n_ticks)]
