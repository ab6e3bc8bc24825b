"""The dict edge of the general engine, ``GeneralStore.encode_changes``.

A change whose ops are all set/del/link on map objects with string keys
is encoded a column at a time; every other change op by op. These tests
hold the columnar path to a plain op-at-a-time reference encoder (map
keys only, written here), hold mixed batches end to end to the host
oracle (``backend/op_set.py``) through ``GeneralDocSet``, check every
validation error's type and message, and read the two path counters.
"""

import numpy as np
import pytest

from automerge_tpu import backend as Backend
from automerge_tpu import frontend as Frontend
from automerge_tpu.common import ROOT_ID
from automerge_tpu.device import general
from automerge_tpu.sync.general_doc_set import GeneralDocSet
from automerge_tpu.text import Text
from automerge_tpu.utils.metrics import metrics

_CODES = {'set': 0, 'del': 1, 'link': 3}
_COLUMNS = {'doc': np.int32, 'actor': np.int32, 'seq': np.int32,
            'dep_ptr': np.int32, 'dep_actor': np.int32,
            'dep_seq': np.int32, 'op_ptr': np.int32, 'action': np.int8,
            'key': np.int32, 'value': np.int32, 'obj': np.int32,
            'key_kind': np.int8, 'key_elem': np.int32, 'elem': np.int32}


def _reference_encode(changes_per_doc):
    """Op by op, map keys only: every key a string key of its object."""
    actors, keys, objs, values = [], [], [ROOT_ID], []
    col = {name: [] for name in _COLUMNS}
    col['dep_ptr'].append(0)
    col['op_ptr'].append(0)
    dup = False

    def intern(table, item):
        if item not in table:
            table.append(item)
        return table.index(item)

    for d, changes in enumerate(changes_per_doc):
        for change in changes:
            col['doc'].append(d)
            col['actor'].append(intern(actors, change['actor']))
            col['seq'].append(change['seq'])
            for dep_actor, dep_seq in change['deps'].items():
                col['dep_actor'].append(intern(actors, dep_actor))
                col['dep_seq'].append(dep_seq)
            col['dep_ptr'].append(len(col['dep_actor']))
            fields = []
            for op in change['ops']:
                col['action'].append(_CODES[op['action']])
                col['obj'].append(intern(objs, op['obj']))
                col['key'].append(intern(keys, op['key']))
                col['key_kind'].append(0)
                col['key_elem'].append(0)
                col['elem'].append(0)
                if op['action'] == 'del':
                    col['value'].append(-1)
                else:
                    col['value'].append(len(values))
                    values.append(op.get('value'))
                dup = dup or (op['obj'], op['key']) in fields
                fields.append((op['obj'], op['key']))
            col['op_ptr'].append(len(col['action']))
    arrays = {name: np.asarray(v, _COLUMNS[name]) for name, v in col.items()}
    return arrays, actors, keys, objs, values, dup


def _assert_block_is(block, want):
    arrays, actors, keys, objs, values, dup = want
    for name, dtype in _COLUMNS.items():
        got = getattr(block, name)
        assert got.dtype == dtype, name
        np.testing.assert_array_equal(got, arrays[name], err_msg=name)
    assert block.actors == actors
    assert block.keys == keys
    assert block.objs == objs
    assert list(block.values) == values
    assert block._dup_keys is dup


def _set(key, value, obj=ROOT_ID):
    return {'action': 'set', 'obj': obj, 'key': key, 'value': value}


def _change(actor, seq, ops, deps=None):
    return {'actor': actor, 'seq': seq, 'deps': deps or {}, 'ops': ops}


NESTED = 'aaaaaaaa-0000-0000-0000-000000000001'
OTHER = 'aaaaaaaa-0000-0000-0000-000000000002'

MAP_BATCHES = {
    'one_record': [[_change('load', 1, [_set(f'field{i}', 'x' * 10)
                                        for i in range(10)])]],
    'records_of_one_schema': [
        [_change('load', 1, [_set(f'f{i}', d * 10 + i) for i in range(4)])]
        for d in range(6)],
    'schema_changes_midway': [
        [_change('load', 1, [_set('a', 1), _set('b', 2)])],
        [_change('load', 1, [_set('a', 3), _set('b', 4)])],
        [_change('load', 1, [_set('b', 5), _set('a', 6)])],
        [_change('load', 1, [_set('c', 7), _set('a', 8)])]],
    'actors_and_deps': [
        [_change('x', 1, [_set('k', 1)]),
         _change('y', 1, [_set('k', 2)], {'x': 1}),
         _change('x', 2, [_set('j', 3)], {'x': 1, 'y': 1})],
        [_change('z', 1, [_set('k', 4)], {'w': 7})]],
    'del_and_link': [[
        _change('a', 1, [_set('k', 1),
                         {'action': 'link', 'obj': ROOT_ID, 'key': 'l',
                          'value': NESTED},
                         {'action': 'del', 'obj': ROOT_ID, 'key': 'gone'},
                         _set('m', None)]),
        _change('a', 2, [{'action': 'del', 'obj': ROOT_ID, 'key': 'k'}],
                {'a': 1})]],
    'duplicate_key_in_one_change': [
        [_change('a', 1, [_set('k', 1), _set('j', 2), _set('k', 3)])]],
    'same_key_in_two_changes': [
        [_change('a', 1, [_set('k', 1)]), _change('a', 2, [_set('k', 2)])]],
    'set_without_value': [[_change('a', 1, [
        {'action': 'set', 'obj': ROOT_ID, 'key': 'k'}])]],
    'element_id_keys_on_a_map': [[_change('a', 1, [
        _set('_head', 1), _set('a:3', 2)])]],
    'unknown_objects': [[_change('a', 1, [
        _set('k', 1, NESTED), _set('k', 2, OTHER), _set('k', 3)])]],
    'same_key_on_two_objects': [[_change('a', 1, [
        _set('k', 1), _set('k', 2, NESTED)])]],
    'empty_change': [[_change('a', 1, []), _change('a', 2, [_set('k', 1)])]],
    'empty_documents': [[], [_change('a', 1, [_set('k', 1)])], []],
}


class TestColumnarParity:
    @pytest.mark.parametrize('name', sorted(MAP_BATCHES))
    def test_map_batch_matches_reference(self, name):
        batch = MAP_BATCHES[name]
        block = general.init_store(len(batch)).encode_changes(batch)
        _assert_block_is(block, _reference_encode(batch))

    def test_map_object_known_to_the_store(self):
        store = general.init_store(2)
        store.obj_row(1, NESTED, create_type=general._TYPE_MAP)
        batch = [[_change('a', 1, [_set('k', 1)])],
                 [_change('b', 1, [_set('k', 2, NESTED), _set('j', 3)])]]
        _assert_block_is(store.encode_changes(batch),
                         _reference_encode(batch))

    def test_extra_types_map_keeps_string_keys(self):
        batch = [[_change('a', 1, [_set('a:1', 1, NESTED)])]]
        block = general.init_store(1).encode_changes(
            batch, extra_types={(0, NESTED): general._TYPE_MAP})
        _assert_block_is(block, _reference_encode(batch))

    def test_extra_types_text_takes_element_keys(self):
        batch = [[_change('b', 1, [_set('a:1', 'x', NESTED)])]]
        block = general.init_store(1).encode_changes(
            batch, extra_types={(0, NESTED): general._TYPE_TEXT})
        assert block.key_kind.tolist() == [general._KEY_ELEM]
        assert block.key_elem.tolist() == [1]
        assert block.actors == ['b', 'a'] and block.keys == []

    def test_creation_later_in_the_batch_resolves_earlier_ops(self):
        make = _change('a', 1, [{'action': 'makeText', 'obj': NESTED}])
        edit = _change('a', 2, [
            {'action': 'ins', 'obj': NESTED, 'key': '_head', 'elem': 1},
            _set('a:1', 'h', NESTED)], {'a': 1})
        ahead = general.init_store(1).encode_changes([[edit, make]])
        behind = general.init_store(1).encode_changes([[make, edit]])
        assert ahead.key_kind.tolist() == [
            general._KEY_HEAD, general._KEY_ELEM, general._KEY_NONE]
        assert behind.key_kind.tolist() == [
            general._KEY_NONE, general._KEY_HEAD, general._KEY_ELEM]

    def test_n_docs_widens_the_block(self):
        batch = [[_change('a', 1, [_set('k', 1)])]]
        block = general.init_store(1).encode_changes(batch, n_docs=9)
        assert block.n_docs == 9
        _assert_block_is(block, _reference_encode(batch))


# -- mixed batches, end to end against the host oracle ----------------------

def _history(actor, edits, base=()):
    doc = Frontend.init({'backend': Backend})
    doc = Frontend.set_actor_id(doc, actor)
    if base:
        state, patch = Backend.apply_changes(
            Frontend.get_backend_state(doc), list(base))
        patch['state'] = state
        doc = Frontend.apply_patch(doc, patch)
    for edit in edits:
        doc, _ = Frontend.change(doc, edit)
    return Backend.get_changes_for_actor(
        Frontend.get_backend_state(doc), actor)


def _plain(value):
    name = type(value).__name__
    if name == 'Text':
        return ''.join(str(c) for c in value)
    if name == 'AmList':
        return [_plain(v) for v in value]
    if hasattr(value, 'items'):
        return {k: _plain(v) for k, v in value.items()}
    return value


def _oracle(changes):
    state, _ = Backend.apply_changes(Backend.init(), list(changes))
    doc = Frontend.apply_patch(Frontend.init('viewer'), {
        'clock': {}, 'deps': {}, 'canUndo': False, 'canRedo': False,
        'diffs': Backend.get_patch(state)['diffs']})
    return _plain(doc)


def _rich_edits():
    return [
        lambda d: d.update({'title': 'doc', 'meta': {'v': 1}}),
        lambda d: d.__setitem__('items', ['a', 'b', 'c']),
        lambda d: d['items'].insert(1, 'x'),
        lambda d: d.__setitem__('text', Text()),
        lambda d: d['text'].insert_at(0, *'hello'),
        lambda d: d['items'].__delitem__(0),
        lambda d: d['meta'].__setitem__('deep', {'q': [1, 2]}),
        lambda d: d.__delitem__('title'),
    ]


def _two_writers():
    base = _history('base', [lambda d: d.update({'k': 0, 'n': {'a': 1}})])
    low = _history('aaa', [lambda d: d.__setitem__('k', 'low'),
                           lambda d: d['n'].__setitem__('b', 2)], base)
    high = _history('zzz', [lambda d: d.__setitem__('k', 'high'),
                            lambda d: d.__delitem__('n')], base)
    return base + low + high


MIXED_DOCS = {
    'rich': lambda: _history('author', _rich_edits()),
    'nested_make_map': lambda: _history('m', [
        lambda d: d.__setitem__('a', {'b': {'c': {'d': 1}}}),
        lambda d: d['a']['b'].__setitem__('e', 2)]),
    'list_and_text': lambda: _history('t', [
        lambda d: d.update({'l': [1, 2], 't': Text()}),
        lambda d: d['t'].insert_at(0, *'abc'),
        lambda d: d['l'].insert(0, 0),
        lambda d: d['t'].delete_at(1)]),
    'several_actors': _two_writers,
    'created_after_first_use': lambda: list(reversed(
        _history('r', [lambda d: d.__setitem__('t', Text()),
                       lambda d: d['t'].insert_at(0, *'xy'),
                       lambda d: d.__setitem__('m', {'k': 1})]))),
    'map_only': lambda: _history('p', [
        lambda d: d.update({f'f{i}': i for i in range(5)}),
        lambda d: d.__setitem__('f1', 'one')]),
}


class TestMixedEndToEnd:
    @pytest.mark.parametrize('name', sorted(MIXED_DOCS))
    def test_mixed_batch_matches_oracle(self, name):
        # each batch mixes the case with a map-only record and a typed
        # text, so columnar and per-op changes share one block
        docs = {name: MIXED_DOCS[name](),
                'record': [_set_record()],
                'typed': MIXED_DOCS['list_and_text']()}
        ds = GeneralDocSet(4)
        ds.apply_changes_batch(docs)
        for doc_id, changes in docs.items():
            assert ds.materialize(doc_id) == _oracle(changes), doc_id

    def test_duplicate_keys_in_one_change(self):
        changes = [_change('a', 1, [_set('k', 1), _set('k', 2)]),
                   _change('a', 2, [_set('j', 1), _set('j', 3),
                                    _set('i', 0)], {'a': 1})]
        ds = GeneralDocSet(1)
        ds.apply_changes_batch({'d': changes})
        assert ds.materialize('d') == _oracle(changes)

    def test_queued_change_resolves_with_the_incoming_creations(self):
        # the edit arrives a batch before the creation it depends on: it
        # waits in the queue and re-encodes with the incoming block's
        # creations (``extra_types``)
        changes = _history('q', [lambda d: d.__setitem__('t', Text()),
                                 lambda d: d['t'].insert_at(0, *'ab'),
                                 lambda d: d.__setitem__('k', 1)])
        ds = GeneralDocSet(1)
        ds.apply_changes_batch({'d': changes[1:]})
        assert ds.materialize('d') == {}
        ds.apply_changes_batch({'d': changes[:1]})
        assert ds.materialize('d') == _oracle(changes)


# -- errors: the same exception and message on either path ------------------

TEXT_OBJ = 'bbbbbbbb-0000-0000-0000-000000000001'


def _text_store():
    store = general.init_store(1)
    store.obj_row(0, TEXT_OBJ, create_type=general._TYPE_TEXT)
    return store


ERRORS = {
    'missing_deps': (
        [{'actor': 'a', 'seq': 1, 'ops': [_set('k', 1)]}],
        ValueError, 'change requires actor, seq and deps'),
    'unknown_action': (
        [_change('a', 1, [_set('k', 1),
                          {'action': 'bogus', 'obj': ROOT_ID, 'key': 'k'}])],
        ValueError, 'Unknown operation type bogus'),
    'seq_bool': ([_change('a', True, [_set('k', 1)])],
                 ValueError, 'change seq True out of range (must fit int32)'),
    'seq_negative': ([_change('a', -1, [_set('k', 1)])],
                     ValueError, 'change seq -1 out of range (must fit int32)'),
    'seq_past_int32': (
        [_change('a', 2 ** 31, [_set('k', 1)])],
        ValueError, 'change seq 2147483648 out of range (must fit int32)'),
    'seq_float': ([_change('a', 1.0, [_set('k', 1)])],
                  ValueError, 'change seq 1.0 out of range (must fit int32)'),
    'dep_seq_bool': (
        [_change('a', 1, [_set('k', 1)], {'b': False})],
        ValueError, 'dep seq False out of range (must fit int32)'),
    'dep_seq_past_int32': (
        [_change('a', 1, [_set('k', 1)], {'b': 2 ** 32})],
        ValueError, 'dep seq 4294967296 out of range (must fit int32)'),
    'malformed_element_id': (
        [_change('a', 1, [_set('a:x', 1, TEXT_OBJ)])],
        ValueError, "malformed element id 'a:x'"),
    'assignment_to_head': (
        [_change('a', 1, [{'action': 'del', 'obj': TEXT_OBJ,
                           'key': '_head'}])],
        ValueError, 'assignment to _head'),
}


class TestErrors:
    @pytest.mark.parametrize('name', sorted(ERRORS))
    def test_error_type_and_message(self, name):
        changes, exc, message = ERRORS[name]
        with pytest.raises(exc) as info:
            _text_store().encode_changes([[_set_record()] + changes])
        assert str(info.value) == message

    def test_first_fault_in_batch_order_wins(self):
        batch = [[_change('a', 1, [_set('k', 1)])],
                 [_change('a', -5, [_set('k', 1)])],
                 [_change('a', 1, [{'action': 'bogus', 'obj': ROOT_ID,
                                    'key': 'k'}])]]
        with pytest.raises(ValueError, match=r'^change seq -5 out'):
            general.init_store(3).encode_changes(batch)

    def test_missing_ops_anywhere_fails_before_other_faults(self):
        batch = [[{'actor': 'a', 'seq': 1, 'ops': [_set('k', 1)]}],
                 [{'actor': 'a', 'seq': 1, 'deps': {}}]]
        with pytest.raises(KeyError, match='ops'):
            general.init_store(2).encode_changes(batch)

    def test_op_fields_read_in_op_order(self):
        ops = [{'action': 'set', 'obj': ROOT_ID, 'value': 1},
               {'action': 'set', 'key': 'k', 'value': 2}]
        with pytest.raises(KeyError, match='key'):
            general.init_store(1).encode_changes([[_change('a', 1, ops)]])


def _set_record():
    return _change('load', 1, [_set(f'field{i}', i) for i in range(3)])


# -- the two path counters -------------------------------------------------

def _path_counts(encode):
    before = (metrics.counters['encode_columnar_changes'],
              metrics.counters['encode_per_op_changes'])
    encode()
    return (metrics.counters['encode_columnar_changes'] - before[0],
            metrics.counters['encode_per_op_changes'] - before[1])


class TestPathCounters:
    def test_map_only_batch_is_all_columnar(self):
        batch = MAP_BATCHES['records_of_one_schema'] + \
            MAP_BATCHES['actors_and_deps']
        store = general.init_store(len(batch))
        assert _path_counts(lambda: store.encode_changes(batch)) == (10, 0)

    def test_insertions_take_the_per_op_path(self):
        changes = _history('t', [lambda d: d.__setitem__('t', Text()),
                                 lambda d: d['t'].insert_at(0, *'ab'),
                                 lambda d: d.__setitem__('k', 1)])
        store = general.init_store(1)
        columnar, per_op = _path_counts(
            lambda: store.encode_changes([changes]))
        assert (columnar, per_op) == (1, 2)

    def test_doc_set_load_and_updates_are_columnar(self):
        ds = GeneralDocSet(8)
        load = {f'r{i}': [_change('load', 1, [_set(f'field{f}', i * f)
                                              for f in range(10)])]
                for i in range(8)}
        assert _path_counts(lambda: ds.apply_changes_batch(load)) == (8, 0)
        update = {f'r{i}': [_change('client', 1, [_set('field3', -i)],
                                    {'load': 1})] for i in range(0, 8, 2)}
        assert _path_counts(lambda: ds.apply_changes_batch(update)) == (4, 0)
        assert ds.materialize('r2')['field3'] == -2
