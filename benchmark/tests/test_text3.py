"""The text cell: its tiny cut, its traced counter, and the comparison
that decides ``correct`` against two planted faults and a stale read.

``control.py``'s stale path holds map-only ``reference.RefDoc``s, which
refuse texts; ``_ReferencePath`` puts the text reference, stale and
fresh, in the program's place here instead."""

import json
import os

import pytest

import reference_text
import served
from conftest import CELLS, SPEC, TICK_CELLS, program_metrics, run_cell

CELL = 'text3.concurrent'


def _read(root, *parts):
    with open(os.path.join(root, 'benchmark', *parts)) as f:
        return json.load(f)


def test_cell_is_read_from_benchmark_json():
    assert CELL in CELLS and CELL in TICK_CELLS
    assert program_metrics(SPEC, CELL) == {
        'rebuild_nodes.text', 'stage_ms.tick', 'dispatch_ms.tick',
        'read_ms.tick'}


def test_tiny_cut_takes_the_files_own_values(tiny_root):
    config = _read(tiny_root, 'configs', 'text3.json')
    assert (config["docs"], config["chars_per_doc"]) == (8, 1008)
    assert (config['writers'], config['word_chars']) == (3, 6)
    mix = _read(tiny_root, 'traffic', 'concurrent.json')
    assert (mix['rate_per_s'], mix['changes_per_tick']) == (10, 4)


def _capture(monkeypatch, path=None):
    """The systems the harness builds; with ``path``, each serves
    through ``path(system)`` once set-up is done."""
    import harness
    built = []
    build = harness.build_system

    def keep(*args, **kwargs):
        system = build(*args, **kwargs)
        built.append(system)
        if path is not None:
            prepare = system.prepare

            def prepare_then_break(watch):
                prepare(watch)
                system.path = path(system)
            system.prepare = prepare_then_break
        return system
    monkeypatch.setattr(harness, 'build_system', keep)
    return built


def test_traced_counter_counts_the_rebuilt_trees(tiny_root, monkeypatch):
    """A tick re-orders its texts whole unless every word in it is its
    round's first (``a1``'s, a front insert): then the incremental arm
    takes it and nothing is rebuilt."""
    built = _capture(monkeypatch)
    out = run_cell(tiny_root, CELL, trace=True)
    assert out['correct'], out['checks']
    system = built[0]
    chars = {d: 1008 for d in system.ids}
    for updates, _, _, _ in system.warmed:
        for doc_id, cs in updates.items():
            chars[doc_id] += sum(op['action'] == 'ins'
                                 for c in cs for op in c['ops'])
    nodes = 0
    for updates, _, _, _ in system.ticks:
        changes = [c for cs in updates.values() for c in cs]
        for doc_id, cs in updates.items():
            chars[doc_id] += sum(op['action'] == 'ins'
                                 for c in cs for op in c['ops'])
        if any(c['actor'] != 'a1' for c in changes):
            nodes += sum(chars[d] + 1 for d in updates)   # and the head
    # the 1 s window lies inside the traced seconds: every tick is traced
    assert out['metrics']['rebuild_nodes.text']['value'] == \
        pytest.approx(nodes / len(system.ticks))


class _Faulty(served.DocSetPath):
    """The doc set's path, with the reference of what it was sent kept
    alongside: one view of each read is changed by ``fault``."""

    def __init__(self, system, fault):
        self.fault = fault
        self.docs = {d: reference_text.replay(h[:system.preload])
                     for d, h in zip(system.ids, system.history)}
        for updates, _, _, _ in system.warmed:
            for doc_id, changes in updates.items():
                for c in changes:
                    self.docs[doc_id].apply(c)

    def apply(self, doc_set, batch):
        super().apply(doc_set, batch)
        for doc_id, changes in batch.items():
            for c in changes:
                self.docs[doc_id].apply(c)

    def read(self, doc_set, doc_ids):
        views = list(super().read(doc_set, doc_ids))
        doc = self.docs[doc_ids[0]]
        obj = next(iter(doc.texts))
        assert views[0] == {'text': doc.text(obj)}
        views[0] = {'text': self.fault(doc, obj)}
        return views


def _wrong_character(doc, obj):
    text = doc.text(obj)
    return ('b' if text[0] == 'a' else 'a') + text[1:]


def _swapped_words(doc, obj):
    """The text with the words of two writers of the latest round in
    which two wrote swapped, character for character."""
    text = list(doc.text(obj))
    rounds = {}
    for i, e in enumerate(doc.elements(obj)):
        actor, elem = e.rsplit(':', 1)
        rounds.setdefault((int(elem) - 1) // 6, {}).setdefault(
            actor, []).append(i)
    words = rounds[max(r for r, w in rounds.items() if len(w) >= 2)]
    a, b = (words[k] for k in sorted(words)[:2])
    wa, wb = [text[i] for i in a], [text[i] for i in b]
    assert wa != wb
    for i, c in zip(a + b, wb + wa):
        text[i] = c
    return ''.join(text)


@pytest.mark.parametrize('fault', [_wrong_character, _swapped_words],
                         ids=['wrong_character', 'swapped_words'])
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, fault):
    _capture(monkeypatch, lambda system: _Faulty(system, fault))
    out = run_cell(tiny_root, CELL)
    assert out['failed'] == 0
    assert not out['correct']
    assert out['checks']['docs_wrong']['value'] > 0


class _ReferencePath:
    """The plain reference in the program's place; ``stale`` answers
    each read from before the last change its document received."""

    def __init__(self, stale):
        self.stale = stale

    def new(self, n_docs):
        return {}

    def apply(self, docs, batch):
        for doc_id, changes in batch.items():
            doc, _ = docs.setdefault(doc_id, (reference_text.RefTextDoc(),
                                              None))
            for c in changes[:-1]:
                doc.apply(c)
            before = doc.plain()
            doc.apply(changes[-1])
            docs[doc_id] = (doc, before if self.stale else doc.plain())

    def read(self, docs, doc_ids):
        return [docs[d][1] for d in doc_ids]


@pytest.mark.parametrize('stale', [True, False], ids=['stale', 'fresh'])
def test_reference_in_the_programs_place(tiny_root, stale):
    out = run_cell(tiny_root, CELL, path=_ReferencePath(stale))
    assert out['correct'] is not stale, out['checks']
    assert (out['checks']['docs_wrong']['value'] > 0) is stale
