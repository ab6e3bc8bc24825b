"""Device-resident incremental sequence index: parity + invalidation
suite (ISSUE 15).

The contract under test: for EVERY delivery schedule and EVERY
invalidation path, the incremental batch update
(`general._fused_general_incr` merging one tick's delta into the
persistent 'tp' plane) produces byte-identical documents, diffs and
tree positions to (a) the whole-object `_rga_order` rebuild
(`_INDEX_MODE='rebuild'`) and (b) the pure-Python host oracle. The
edit-stream read path (pallas_view) is pinned against the legacy
host-argsort path the same way, and the Pallas kernel against its lax
fallback in interpret mode (the CPU CI lane for the fused
winner/visible/order kernel).

Runs in both CI lanes: the forced-native parametrization drives the
C++ stager (skipped when the library is unavailable), exactly like the
chaos/materialize suites.
"""

import numpy as np
import pytest

import jax

from automerge_tpu import backend as Backend
from automerge_tpu import frontend as Frontend
from automerge_tpu import native
from automerge_tpu.device import general
from automerge_tpu.device import general_backend as GB
from automerge_tpu.device import profiler
from automerge_tpu.device import pallas_view
from automerge_tpu.text import Text
from automerge_tpu.utils.metrics import metrics


def _materialize(doc):
    def conv(obj):
        name = type(obj).__name__
        if name == 'Text':
            return ''.join(str(c) for c in obj)
        if name == 'AmList':
            return [conv(v) for v in obj]
        if hasattr(obj, '_conflicts'):
            return {k: conv(v) for k, v in obj.items()}
        return obj
    return conv(doc)


def _changes_of(doc, actor):
    return Backend.get_changes_for_actor(
        Frontend.get_backend_state(doc), actor)


def _fork(base_changes, actor, *edits):
    doc = Frontend.init({'backend': Backend})
    doc = Frontend.set_actor_id(doc, actor)
    if base_changes:
        state, patch = Backend.apply_changes(
            Frontend.get_backend_state(doc), base_changes)
        patch['state'] = state
        doc = Frontend.apply_patch(doc, patch)
    for e in edits:
        doc, _ = Frontend.change(doc, e)
    return _changes_of(doc, actor)


def _via_oracle(changes):
    state, _ = Backend.apply_changes(Backend.init(), changes)
    return Frontend.apply_patch(Frontend.init('viewer'),
                                Backend.get_patch(state))


def _via_general(changes, mode, per_change=True, edit_stream=True,
                 force_native=None):
    """Apply through the general engine with the given index mode;
    returns (frontend doc, GeneralBackendState)."""
    prev = (general._INDEX_MODE, general._EDIT_STREAM,
            general._NATIVE_STAGING)
    general._INDEX_MODE = mode
    general._EDIT_STREAM = edit_stream
    if force_native is not None:
        general._NATIVE_STAGING = force_native
    try:
        state = GB.init()
        doc = Frontend.init({'backend': GB})
        batches = [[c] for c in changes] if per_change else [changes]
        for batch in batches:
            state, patch = GB.apply_changes(state, batch)
            patch['state'] = state
            doc = Frontend.apply_patch(doc, patch)
        return doc, state
    finally:
        (general._INDEX_MODE, general._EDIT_STREAM,
         general._NATIVE_STAGING) = prev


def _tp_of(store):
    """Host fetch of the persistent index plane (pos order)."""
    mir = store.pool.mirror
    if mir is None or 'tp' not in mir:
        return None
    return np.asarray(jax.device_get(mir['tp'][:mir['n']]))


def _assert_parity(changes, min_incremental=0, per_change=True):
    """incremental == rebuild == host oracle, diffs and tp included."""
    oracle = _materialize(_via_oracle(changes))
    base = dict(metrics.counters)
    doc_i, st_i = _via_general(changes, mode=None,
                               per_change=per_change)
    incr = metrics.counters.get('device_idx_incremental_applies', 0) \
        - base.get('device_idx_incremental_applies', 0)
    doc_r, st_r = _via_general(changes, mode='rebuild',
                               per_change=per_change)
    assert _materialize(doc_i) == oracle
    assert _materialize(doc_r) == oracle
    assert incr >= min_incremental, \
        f'expected >= {min_incremental} incremental applies, got {incr}'
    st_i.store.pool.sync()
    st_r.store.pool.sync()
    assert np.array_equal(st_i.store.pool.visible,
                          st_r.store.pool.visible)
    assert np.array_equal(st_i.store.pool.vis_index,
                          st_r.store.pool.vis_index)
    tp_i, tp_r = _tp_of(st_i.store), _tp_of(st_r.store)
    if tp_i is not None and tp_r is not None:
        assert np.array_equal(tp_i, tp_r), 'tp plane diverged'
    return doc_i, st_i


def _typing_changes(n=24, deletes=True):
    doc = Frontend.init({'backend': Backend})
    doc = Frontend.set_actor_id(doc, 'typist')

    def init(d):
        d['text'] = Text()
    doc, _ = Frontend.change(doc, init)
    for i in range(n):
        doc, _ = Frontend.change(
            doc, lambda d, i=i: d['text'].insert_at(
                len(d['text']), chr(97 + i % 26)))
        if deletes and i % 7 == 6:
            doc, _ = Frontend.change(
                doc, lambda d: d['text'].delete_at(1))
    return _changes_of(doc, 'typist')


_HAS_NATIVE = native.stage_available()
_NATIVE_PARAMS = [False] + ([True] if _HAS_NATIVE else [])


class TestIncrementalParity:
    @pytest.mark.parametrize('force_native', _NATIVE_PARAMS)
    def test_sequential_typing(self, force_native):
        changes = _typing_changes()
        oracle = _materialize(_via_oracle(changes))
        base = dict(metrics.counters)
        doc_i, _ = _via_general(changes, mode=None,
                                force_native=force_native)
        assert _materialize(doc_i) == oracle
        incr = metrics.counters.get(
            'device_idx_incremental_applies', 0) - base.get(
            'device_idx_incremental_applies', 0)
        assert incr >= 10

    def test_concurrent_appends_and_deletes(self):
        base = _fork([], 'alice',
                     lambda d: d.update({'text': Text()}),
                     lambda d: d['text'].insert_at(0, *'hello'))
        a = _fork(base, 'alice',
                  lambda d: d['text'].insert_at(5, *' world'),
                  lambda d: d['text'].delete_at(0))
        b = _fork(base, 'bob',
                  lambda d: d['text'].insert_at(5, *'!!'),
                  lambda d: d['text'].insert_at(0, '>'))
        _assert_parity(base + a + b, min_incremental=1)

    def test_interleaved_delivery_schedules(self):
        """Shuffled whole-change delivery (causally valid order per
        actor rides the causal queue) — every schedule byte-identical
        to the oracle and to the rebuild arm."""
        base = _fork([], 'a1',
                     lambda d: d.update({'list': [1, 2, 3]}))
        x = _fork(base, 'a2', lambda d: d['list'].insert_at(1, 'x'),
                  lambda d: d['list'].append('y'))
        y = _fork(base, 'a3', lambda d: d['list'].insert_at(3, 'z'),
                  lambda d: d['list'].delete_at(0))
        import random
        rng = random.Random(7)
        for _ in range(3):
            sched = base + x + y
            tail = sched[len(base):]
            rng.shuffle(tail)
            _assert_parity(base + tail)

    def test_insert_after_concurrently_deleted_parent(self):
        """bob inserts after a char alice concurrently deleted: the
        delta root's anchor is a TOMBSTONE — tree positions cover
        hidden nodes, so the incremental merge handles it; both
        delivery orders agree with the oracle."""
        base = _fork([], 'alice',
                     lambda d: d.update({'text': Text()}),
                     lambda d: d['text'].insert_at(0, *'abcdef'))
        a = _fork(base, 'alice', lambda d: d['text'].delete_at(2))
        b = _fork(base, 'bob', lambda d: d['text'].insert_at(3, 'X'))
        _assert_parity(base + a + b)
        _assert_parity(base + b + a)

    def test_mid_insert_falls_back_to_rebuild(self):
        """A late concurrent insert whose elem does not exceed the
        object's max (a non-front insert) must take the rebuild arm —
        and still agree everywhere."""
        base = _fork([], 'alice',
                     lambda d: d.update({'text': Text()}),
                     lambda d: d['text'].insert_at(0, *'abcdef'))
        # bob's concurrent inserts anchor mid-string with SMALLER
        # elems than alice's later ops
        b = _fork(base, 'bob', lambda d: d['text'].insert_at(3, 'X'))
        a2 = _fork(base, 'alice', lambda d: d['text'].insert_at(
            6, *'123456'))
        # deliver alice's extension first, then bob's mid insert: by
        # then max_elem has advanced past bob's elem
        pre = dict(metrics.counters)
        _assert_parity(base + a2 + b)
        rebuilds = metrics.counters.get(
            'device_idx_rebuild_applies', 0) - pre.get(
            'device_idx_rebuild_applies', 0)
        assert rebuilds >= 1

    def test_wide_format_incremental(self):
        """elemc past the packed 15-bit bound puts the mirror on the
        WIDE format; the incremental path must ride it identically."""
        changes = _typing_changes(n=12, deletes=False)
        # a raw change with a huge elem counter forces the wide pick
        big = {'actor': 'typist', 'seq': len(changes) + 1, 'deps': {},
               'ops': [{'action': 'ins',
                        'obj': changes[1]['ops'][0]['obj'],
                        'key': '_head', 'elem': 40000},
                       {'action': 'set',
                        'obj': changes[1]['ops'][0]['obj'],
                        'key': 'typist:40000', 'value': 'W'}]}
        tail = {'actor': 'typist', 'seq': len(changes) + 2,
                'deps': {},
                'ops': [{'action': 'ins',
                         'obj': changes[1]['ops'][0]['obj'],
                         'key': 'typist:40000', 'elem': 40001},
                        {'action': 'set',
                         'obj': changes[1]['ops'][0]['obj'],
                         'key': 'typist:40001', 'value': 'X'}]}
        base = dict(metrics.counters)
        doc_i, st_i = _via_general(changes + [big, tail], mode=None)
        doc_r, st_r = _via_general(changes + [big, tail],
                                   mode='rebuild')
        assert st_i.store.pool.mirror['fmt'] == 'wide'
        assert _materialize(doc_i) == _materialize(doc_r)
        assert np.array_equal(_tp_of(st_i.store), _tp_of(st_r.store))
        incr = metrics.counters.get(
            'device_idx_incremental_applies', 0) - base.get(
            'device_idx_incremental_applies', 0)
        # the boundary-crossing apply converts packed -> wide and the
        # index survives the conversion: the tail append after the
        # crossing still goes incremental
        assert incr >= 1

    def test_cols_fallback_always_rebuilds(self):
        """The cols mirror format (past every packed bound) carries no
        'tp' plane: applies rebuild, index claims drop, and the
        documents still match the oracle."""
        prev_p = general._packed_mirror_guard
        prev_w = general._wide_mirror_guard
        general._packed_mirror_guard = lambda *a, **k: False
        general._wide_mirror_guard = lambda *a, **k: False
        try:
            changes = _typing_changes(n=8, deletes=False)
            base = dict(metrics.counters)
            doc_i, st = _via_general(changes, mode=None)
            assert st.store.pool.mirror['fmt'] == 'cols'
            assert 'tp' not in st.store.pool.mirror
            assert not st.store.pool.idx_ok.any()
            assert metrics.counters.get(
                'device_idx_incremental_applies', 0) == base.get(
                'device_idx_incremental_applies', 0)
            assert metrics.counters.get(
                'device_idx_rebuild_applies', 0) > base.get(
                'device_idx_rebuild_applies', 0)
            assert _materialize(doc_i) == \
                _materialize(_via_oracle(changes))
        finally:
            general._packed_mirror_guard = prev_p
            general._wide_mirror_guard = prev_w

    def test_idx_update_span_emitted(self):
        """The incremental program gets its own observability lane:
        a subscriber sees a 'device.idx_update' span per incremental
        apply (dump_chrome_trace maps each device.* name to a
        dedicated Perfetto track)."""
        changes = _typing_changes(n=6, deletes=False)
        events = []
        metrics.subscribe(events.append)
        try:
            _via_general(changes, mode=None)
        finally:
            metrics.unsubscribe(events.append)
        idx_spans = [e for e in events
                     if e.get('name') == 'device.idx_update']
        assert idx_spans, 'no device.idx_update spans emitted'
        assert all('dur_ms' in e for e in idx_spans)

    def test_index_mode_require_raises_on_first_sight(self):
        general._INDEX_MODE = 'require'
        try:
            state = GB.init()
            with pytest.raises(RuntimeError, match='incremental'):
                GB.apply_changes(state, _typing_changes(n=2)[:2])
        finally:
            general._INDEX_MODE = None
        # the rollback left the store usable
        state2, _ = GB.apply_changes(GB.init(), _typing_changes(n=2))

    def test_require_holds_on_warm_appends(self):
        """Steady-state appends NEVER silently fall back: after the
        first-sight rebuild, 'require' mode must not raise."""
        changes = _typing_changes(n=8, deletes=False)
        state = GB.init()
        doc = Frontend.init({'backend': GB})
        state, patch = GB.apply_changes(state, changes[:2])
        patch['state'] = state
        doc = Frontend.apply_patch(doc, patch)
        general._INDEX_MODE = 'require'
        try:
            for c in changes[2:]:
                state, patch = GB.apply_changes(state, [c])
                patch['state'] = state
                doc = Frontend.apply_patch(doc, patch)
        finally:
            general._INDEX_MODE = None
        assert _materialize(doc) == \
            _materialize(_via_oracle(changes))


class TestInvalidationPaths:
    def test_snapshot_resume_skips_rebuild(self):
        changes = _typing_changes(n=10, deletes=False)
        _, st = _via_general(changes, mode=None)
        data = st.store.save_snapshot()
        resumed = general.GeneralStore.load_snapshot(data)
        assert resumed.pool.idx_ok.any()
        assert 'tp' in resumed.pool.mirror
        # the next append on the resumed store goes straight to the
        # incremental path (no rebuild)
        obj = changes[1]['ops'][0]['obj']
        last = max(c['seq'] for c in changes)
        nxt = {'actor': 'typist', 'seq': last + 1, 'deps': {},
               'ops': [{'action': 'ins', 'obj': obj,
                        'key': 'typist:10', 'elem': 11000},
                       {'action': 'set', 'obj': obj,
                        'key': 'typist:11000', 'value': 'Z'}]}
        base = dict(metrics.counters)
        block = resumed.encode_changes([[nxt]])
        p = general.apply_general_block(resumed, block)
        p.to_patches()
        assert metrics.counters.get(
            'device_idx_incremental_applies', 0) - base.get(
            'device_idx_incremental_applies', 0) == 1
        # parity against a rebuild-mode continuation of a second
        # resumed copy
        resumed2 = general.GeneralStore.load_snapshot(data)
        general._INDEX_MODE = 'rebuild'
        try:
            p2 = general.apply_general_block(
                resumed2, resumed2.encode_changes([[nxt]]))
            p2.to_patches()
        finally:
            general._INDEX_MODE = None
        resumed.pool.sync()
        resumed2.pool.sync()
        assert np.array_equal(resumed.pool.vis_index,
                              resumed2.pool.vis_index)
        assert np.array_equal(_tp_of(resumed), _tp_of(resumed2))

    def test_pre_index_resume_rebuilds_then_goes_incremental(self):
        changes = _typing_changes(n=6, deletes=False)
        _, st = _via_general(changes, mode=None)
        st.store.pool.idx_ok[:] = False      # simulate a pre-index
        data = st.store.save_snapshot()      # snapshot's claims
        resumed = general.GeneralStore.load_snapshot(data)
        assert not resumed.pool.idx_ok.any()
        obj = changes[1]['ops'][0]['obj']
        last = max(c['seq'] for c in changes)
        for k in range(2):
            nxt = {'actor': 'typist', 'seq': last + 1 + k, 'deps': {},
                   'ops': [{'action': 'ins', 'obj': obj,
                            'key': f'typist:{9000 + k - 1}'
                            if k else 'typist:6',
                            'elem': 9000 + k},
                           {'action': 'set', 'obj': obj,
                            'key': f'typist:{9000 + k}',
                            'value': 'q'}]}
            base = dict(metrics.counters)
            p = general.apply_general_block(
                resumed, resumed.encode_changes([[nxt]]))
            p.to_patches()
            key = ('device_idx_rebuild_applies' if k == 0
                   else 'device_idx_incremental_applies')
            assert metrics.counters.get(key, 0) - base.get(key, 0) \
                == 1

    def test_eviction_rebuild_revalidates(self):
        """drop_doc_state re-applies surviving docs into a fresh
        store: the index re-derives through the rebuild path and the
        NEXT tick is incremental again."""
        from automerge_tpu.sync.general_doc_set import GeneralDocSet
        import automerge_tpu as am
        ds = GeneralDocSet(4)
        fdocs = {}
        for i in range(3):
            doc = am.change(am.init(f'actor-{i:03d}'),
                            lambda d: d.update({'text': Text()}))
            doc = am.change(doc,
                            lambda d: d['text'].insert_at(0, *'abcd'))
            fdocs[f'doc-{i}'] = doc
            ds.set_doc(f'doc-{i}', doc)
        before = ds.materialize_all()
        ds.extract_doc_state(['doc-1'])
        ds.drop_doc_state(['doc-1'])
        assert ds.materialize('doc-0') == before['doc-0']
        assert ds.materialize('doc-2') == before['doc-2']
        # a fresh append on a survivor: first touch after the rebuild
        # already finds a valid index (the chunked re-apply went
        # through the rebuild arm and revalidated)
        base = dict(metrics.counters)
        d0b = am.change(fdocs['doc-0'],
                        lambda d: d['text'].insert_at(4, '!'))
        ds.set_doc('doc-0', d0b)
        assert ds.materialize('doc-0')['text'] == 'abcd!'
        assert metrics.counters.get(
            'device_idx_incremental_applies', 0) - base.get(
            'device_idx_incremental_applies', 0) >= 1

    def test_state_absorb_carries_index(self):
        from automerge_tpu import compaction
        changes = _typing_changes(n=8, deletes=False)
        _, st = _via_general(changes, mode=None)
        states = compaction.extract_doc_states(st.store, [0])
        payload = states[0]['state']
        decoded = compaction.decode_state_snapshot(payload)
        assert decoded['idx']
        assert len(decoded['nd_tpos']) == len(decoded['nd_obj'])
        fresh = general.init_store(1)
        compaction.absorb_doc_states(fresh, [(0, payload, decoded)])
        assert fresh.pool.idx_ok.any()
        assert 'tp' in fresh.pool.mirror
        # the absorbed store's visibility matches the original
        st.store.pool.sync()
        fresh.pool.sync()
        assert np.array_equal(np.sort(st.store.pool.vis_index),
                              np.sort(fresh.pool.vis_index))
        # next append is incremental immediately — the restore
        # skipped the rebuild
        obj = changes[1]['ops'][0]['obj']
        last = max(c['seq'] for c in changes)
        nxt = {'actor': 'typist', 'seq': last + 1, 'deps': {},
               'ops': [{'action': 'ins', 'obj': obj,
                        'key': 'typist:8', 'elem': 7000},
                       {'action': 'set', 'obj': obj,
                        'key': 'typist:7000', 'value': '!'}]}
        base = dict(metrics.counters)
        p = general.apply_general_block(
            fresh, fresh.encode_changes([[nxt]]))
        p.to_patches()
        assert metrics.counters.get(
            'device_idx_incremental_applies', 0) - base.get(
            'device_idx_incremental_applies', 0) == 1

    def test_old_state_snapshot_decodes_without_index(self):
        """Backward compat: a v1 payload (no nd_tpos column) decodes
        and absorbs with no index claim."""
        from automerge_tpu import compaction
        changes = _typing_changes(n=4, deletes=False)
        _, st = _via_general(changes, mode=None)
        states = compaction.extract_doc_states(st.store, [0])
        decoded = compaction.decode_state_snapshot(
            states[0]['state'])
        # re-encode through the v1 manifest
        st1 = {k: v for k, v in decoded.items()
               if k not in ('nd_tpos', 'idx')}
        import json
        import struct
        import zlib
        from automerge_tpu.durability import pack_snapshot
        header = {'format': compaction.STATE_FORMAT,
                  'clock': st1['clock'], 'digest': st1['digest'],
                  'actors': st1['actors'], 'keys': st1['keys'],
                  'values': st1['values'], 'objs': st1['objs'],
                  'inbound': st1['inbound'],
                  'lens': [int(len(st1[name]))
                           for name, _ in compaction._ARRAYS]}
        head = json.dumps(header, separators=(',', ':')).encode()
        body = b''.join(
            [struct.Struct('>I').pack(len(head)), head] +
            [np.ascontiguousarray(st1[name].astype(dtype)).tobytes()
             for name, dtype in compaction._ARRAYS])
        v1 = pack_snapshot(compaction._STATE_MAGIC
                           + zlib.compress(body, 6))
        dec = compaction.decode_state_snapshot(v1)
        assert not dec['idx']
        fresh = general.init_store(1)
        compaction.absorb_doc_states(fresh, [(0, v1, dec)])
        assert not fresh.pool.idx_ok.any()


class TestEditStream:
    def test_edit_stream_matches_legacy(self):
        changes = _typing_changes(n=16)
        doc_a, _ = _via_general(changes, mode=None, edit_stream=True)
        doc_b, _ = _via_general(changes, mode=None, edit_stream=False)
        assert _materialize(doc_a) == _materialize(doc_b)

    def test_edit_stream_matches_legacy_rebuild_arm(self):
        changes = _typing_changes(n=10)
        doc_a, _ = _via_general(changes, mode='rebuild',
                                edit_stream=True)
        doc_b, _ = _via_general(changes, mode='rebuild',
                                edit_stream=False)
        assert _materialize(doc_a) == _materialize(doc_b)

    def _random_planes(self, rng, K=5, m=64):
        pv = rng.random((K, m)) < 0.5
        nv = rng.random((K, m)) < 0.5
        touched = (rng.random((K, m)) < 0.4) | (nv & ~pv) | (pv & ~nv)
        # dense unique prior/new ranks per row for visible nodes
        pi = np.full((K, m), -1, np.int64)
        ni = np.full((K, m), -1, np.int64)
        for j in range(K):
            vis_p = np.flatnonzero(pv[j])
            pi[j, vis_p] = rng.permutation(len(vis_p))
            vis_n = np.flatnonzero(nv[j])
            ni[j, vis_n] = rng.permutation(len(vis_n))
        tb = np.packbits(touched, axis=1)
        return pv, nv, pi, ni, tb

    @pytest.mark.parametrize('K,m', [
        (5, 64),      # job count not a multiple of 8
        (8, 128),     # one lane tile
        (3, 300),     # multi-tile node axis, ragged tail
    ])
    def test_pallas_interpret_parity(self, K, m):
        """The hand-fused Pallas winner/visible/order kernel is
        bit-identical to the lax fallback — interpret mode on CPU (the
        v5e compile is pinned in tests/test_tpu_compile.py)."""
        rng = np.random.default_rng(42 + K)
        for e_pad in (8, 24):
            pv, nv, pi, ni, tb = self._random_planes(rng, K=K, m=m)
            lax_out = jax.device_get(pallas_view.edit_stream(
                pv, nv, pi, ni, tb, e_pad=e_pad))
            pl_out = jax.device_get(pallas_view.edit_stream_pallas(
                pv, nv, pi, ni, tb, e_pad=e_pad, interpret=True))
            for a, b in zip(lax_out, pl_out):
                assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_packed_wide_wrappers_match_cols(self):
        rng = np.random.default_rng(3)
        pv, nv, pi, ni, tb = self._random_planes(rng, K=3, m=32)
        packed = (pv.astype(np.int32) << 31) | \
            (nv.astype(np.int32) << 30) | \
            (((pi + 1) << 15) | (ni + 1)).astype(np.int32)
        wp = (pv.astype(np.int32) << 22) | (pi + 1).astype(np.int32)
        wn = (nv.astype(np.int32) << 22) | (ni + 1).astype(np.int32)
        ref = jax.device_get(pallas_view.edit_stream(
            pv, nv, pi, ni, tb, e_pad=16))
        got_p = jax.device_get(pallas_view.edit_stream_packed(
            packed, tb, e_pad=16))
        got_w = jax.device_get(pallas_view.edit_stream_wide(
            wp, wn, tb, e_pad=16))
        for a, b, c in zip(ref, got_p, got_w):
            assert np.array_equal(np.asarray(a), np.asarray(b))
            assert np.array_equal(np.asarray(a), np.asarray(c))

    def test_force_switch_raises_instead_of_falling_back(self):
        if jax.default_backend() == 'tpu':
            pytest.skip('force switch only raises off-TPU')
        prev_v, prev_i = pallas_view._FUSED_VIEW, \
            pallas_view._INTERPRET
        pallas_view._FUSED_VIEW = True
        pallas_view._INTERPRET = False
        try:
            with pytest.raises(RuntimeError, match='Pallas'):
                pallas_view.dispatch_edit_stream(
                    'packed',
                    jax.numpy.zeros((1, 8), jax.numpy.int32),
                    np.zeros((1, 1), np.uint8), 8)
        finally:
            pallas_view._FUSED_VIEW = prev_v
            pallas_view._INTERPRET = prev_i


class TestJobBucketing:
    def test_drifting_dirty_sets_do_not_retrace(self):
        """Satellite (ISSUE 15): the job axis buckets like every other
        padded axis — steady-state ticks whose dirty-set size drifts
        inside one bucket mint NO new jit signatures. Before the fix,
        every distinct dirty count was a fresh signature on the fused
        programs (K rode the `sizes` static) and
        `device_retraces_total` climbed without bound. Every OTHER
        axis is pinned via fixed pads so the job axis is the only
        variable."""
        from automerge_tpu.common import ROOT_ID
        from automerge_tpu.config import Options
        opts = Options(op_pad=64, seg_pad=64, node_pad=256,
                       actor_pad=8)
        store = general.init_store(8)
        per_doc = []
        for d in range(6):
            ops = [{'action': 'makeList', 'obj': f'L{d}'},
                   {'action': 'link', 'obj': ROOT_ID, 'key': 'list',
                    'value': f'L{d}'}]
            prev = '_head'
            for i in range(3):
                ops.append({'action': 'ins', 'obj': f'L{d}',
                            'key': prev, 'elem': i + 1})
                ops.append({'action': 'set', 'obj': f'L{d}',
                            'key': f'a{d}:{i + 1}', 'value': i})
                prev = f'a{d}:{i + 1}'
            per_doc.append([{'actor': f'a{d}', 'seq': 1, 'deps': {},
                             'ops': ops}])
        per_doc += [[], []]
        blocks = [per_doc]
        general.apply_general_block(
            store, store.encode_changes(per_doc),
            options=opts).to_patches()
        seqs = [2] * 6
        elems = [3] * 6

        def tick(n):
            pd = [[] for _ in range(8)]
            for d in range(n):
                pd[d] = [{'actor': f'a{d}', 'seq': seqs[d],
                          'deps': {}, 'ops': [
                              {'action': 'ins', 'obj': f'L{d}',
                               'key': f'a{d}:{elems[d]}',
                               'elem': elems[d] + 1},
                              {'action': 'set', 'obj': f'L{d}',
                               'key': f'a{d}:{elems[d] + 1}',
                               'value': 0}]}]
                seqs[d] += 1
                elems[d] += 1
            blocks.append(pd)
            general.apply_general_block(
                store, store.encode_changes(pd),
                options=opts).to_patches()
        # warm every job-bucket class 1..6 dirty docs can hit
        # ({1, 2, 4, 8}), then drift freely within them
        for n in (1, 2, 3, 5):
            tick(n)
        before = dict(metrics.counters)
        for n in (4, 6, 1, 5, 2, 6, 3, 4, 1, 6):
            tick(n)
        after = metrics.counters.get('device_retraces_total', 0)
        assert after - before.get('device_retraces_total', 0) == 0, \
            'retraces from dirty-set drift'
        # the drift ticks were MULTI-JOB incremental applies — assert
        # they took the incremental path and agree with a rebuild-mode
        # twin fed the identical blocks
        assert metrics.counters.get(
            'device_idx_incremental_applies', 0) - before.get(
            'device_idx_incremental_applies', 0) >= 10
        twin = general.init_store(8)
        general._INDEX_MODE = 'rebuild'
        try:
            for pd in blocks:
                general.apply_general_block(
                    twin, twin.encode_changes(pd),
                    options=opts).to_patches()
        finally:
            general._INDEX_MODE = None
        store.pool.sync()
        twin.pool.sync()
        assert np.array_equal(store.pool.visible, twin.pool.visible)
        assert np.array_equal(store.pool.vis_index,
                              twin.pool.vis_index)
        assert np.array_equal(_tp_of(store), _tp_of(twin))
