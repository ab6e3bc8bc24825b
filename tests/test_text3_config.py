"""Three writers' concurrent words in shared texts, at a small size on
the CPU: through ``GeneralDocSet`` they equal the plain reference of
Automerge 0.9 list semantics, the generator's own order equals the
reference's, and the rebuild arm of the sequence index counts the
nodes it re-orders."""

import importlib.util
import os

import jax
import pytest

from automerge_tpu.sync.general_doc_set import GeneralDocSet
from automerge_tpu.utils.metrics import metrics

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    name = 'text3_' + parts[-1][:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


text_b2 = _load('gen', 'text_b2.py')
reference_text = _load('reference_text.py')

WRITERS, WORD = 3, 6


def _histories(n_docs, rounds, seed):
    return text_b2.histories(n_docs, WRITERS, WORD, rounds, seed)


def _staggered(hs):
    """The mix's delivery: each doc's changes one apply at a time."""
    n = len(hs[0].changes)
    for i in range(n):
        yield {text_b2.doc_ids(len(hs))[d]: [h.changes[i]]
               for d, h in enumerate(hs)}


def _in_round(hs):
    """A round's words, concurrent, in one apply."""
    ids = text_b2.doc_ids(len(hs))
    yield {d: h.changes[:1] for d, h in zip(ids, hs)}
    for r in range(hs[0].rounds):
        lo = 1 + r * WRITERS
        yield {d: h.changes[lo:lo + WRITERS] for d, h in zip(ids, hs)}


def _reversed_rounds(hs):
    """Rounds delivered last first: the doc set holds each until the
    rounds it depends on arrive."""
    return reversed(list(_in_round(hs)))


@pytest.mark.parametrize('deliver', [_staggered, _in_round,
                                     _reversed_rounds],
                         ids=['staggered', 'in_round', 'reversed_rounds'])
def test_doc_set_equals_reference(deliver):
    hs = _histories(3, 12, seed=2_700_000_011)
    ids = text_b2.doc_ids(len(hs))
    ds = GeneralDocSet(len(hs))
    for batch in deliver(hs):
        ds.apply_changes_batch(batch)
    views = ds.materialize_many(ids)
    for view, h in zip(views, hs):
        assert view == reference_text.replay(h.changes).plain()
        assert len(view['text']) == 12 * WRITERS * WORD


@pytest.mark.parametrize('seed', [0, 7, 2_700_000_123])
def test_generator_order_is_the_references(seed):
    for h in _histories(4, 40, seed):
        assert reference_text.replay(h.changes).plain() == \
            {'text': h.text()}


def test_reference_orders_concurrent_inserts_by_actor():
    """Three concurrent words on one parent: descending actor after the
    parent; a later word on that parent goes before all three."""
    obj = text_b2.text_obj(0)

    def word(actor, seq, deps, key, elem, chars):
        ops = []
        for i, c in enumerate(chars):
            ops.append({'action': 'ins', 'obj': obj, 'key': key,
                        'elem': elem + i})
            key = f'{actor}:{elem + i}'
            ops.append({'action': 'set', 'obj': obj, 'key': key,
                        'value': c})
        return {'actor': actor, 'seq': seq, 'deps': deps, 'ops': ops}

    h = text_b2.TextHistory(0, 3, 1, seed=0)
    changes = [h.changes[0],
               word('a1', 2, {}, '_head', 1, 'x'),
               word('a2', 1, {'a1': 2}, 'a1:1', 2, 'AA'),
               word('a3', 1, {'a1': 2}, 'a1:1', 2, 'BB'),
               word('a1', 3, {}, 'a1:1', 2, 'CC'),
               word('a1', 4, {'a2': 1, 'a3': 1}, 'a1:1', 4, 'D')]
    assert reference_text.replay(changes).plain() == {'text': 'xDBBAACC'}
    ds = GeneralDocSet(1)
    ds.apply_changes_batch({'t': changes})
    assert ds.materialize_many(['t']) == [{'text': 'xDBBAACC'}]


def test_reference_refuses_a_change_before_its_deps():
    h = _histories(1, 2, seed=1)[0]
    doc = reference_text.replay(h.changes[:2])
    with pytest.raises(ValueError, match='has not arrived'):
        doc.apply(h.changes[4])


def _counted(fn):
    """Run ``fn``; return how far the sequence index's counters moved."""
    names = ('device_idx_rebuild_nodes', 'device_idx_rebuild_applies',
             'device_idx_incremental_applies')
    before = {n: metrics.counters.get(n, 0) for n in names}
    fn()
    return {n: metrics.counters.get(n, 0) - before[n] for n in names}


def test_rebuild_counter():
    rounds = 4
    hs = _histories(2, rounds + 1, seed=5)
    ds = GeneralDocSet(3)
    ds.apply_changes_batch({d: h.changes[:1 + rounds * WRITERS]
                            for d, h in zip(text_b2.doc_ids(2), hs)})
    nxt = 1 + rounds * WRITERS
    chars = rounds * WRITERS * WORD

    # a1's word of the next round: a front insert, the incremental arm
    moved = _counted(lambda: ds.apply_changes_batch(
        {'text0': [hs[0].changes[nxt]]}))
    assert moved == {'device_idx_rebuild_nodes': 0,
                     'device_idx_rebuild_applies': 0,
                     'device_idx_incremental_applies': 1}

    # a2's word, concurrent with a1's: late, so both texts it touches
    # re-order whole; a tree counts its characters and its head
    moved = _counted(lambda: ds.apply_changes_batch(
        {'text0': [hs[0].changes[nxt + 1]],
         'text1': [hs[1].changes[nxt]]}))
    nodes = (chars + 2 * WORD + 1) + (chars + WORD + 1)
    assert moved == {'device_idx_rebuild_nodes': nodes,
                     'device_idx_rebuild_applies': 1,
                     'device_idx_incremental_applies': 0}

    # a map-only apply re-orders no sequence
    root = text_b2.ROOT_ID
    moved = _counted(lambda: ds.apply_changes_batch(
        {'map0': [{'actor': 'm', 'seq': 1, 'deps': {}, 'ops': [
            {'action': 'set', 'obj': root, 'key': 'k', 'value': 1}]}]}))
    assert set(moved.values()) == {0}


def test_warm_text_ticks_compile_nothing():
    """Once warm, a tick of late words and its read compile nothing:
    the read's fetch of the device planes does not depend on the node
    count, which grows every tick. (Rounds 9 and 10 keep every tree
    and the doc set's node capacity inside one padding bucket.)"""
    hs = _histories(2, 10, seed=9)
    ids = text_b2.doc_ids(2)
    ds = GeneralDocSet(2)
    ds.apply_changes_batch({d: h.changes[:1 + 8 * WRITERS]
                            for d, h in zip(ids, hs)})
    ds.materialize_many(ids)
    compiles = []

    def on(event, secs, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        for i in range(1 + 8 * WRITERS, len(hs[0].changes)):
            if i == 1 + 9 * WRITERS:
                compiles.clear()        # round 9 warmed both arms
            ds.apply_changes_batch({d: [h.changes[i]]
                                    for d, h in zip(ids, hs)})
            assert ds.materialize_many(ids) == [
                reference_text.replay(h.changes[:i + 1]).plain()
                for h in hs]
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert compiles == []
