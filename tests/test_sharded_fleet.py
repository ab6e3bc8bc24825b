"""Sharded fleet suite: doc-axis placement, live migration, rollups.

Everything here gates the ISSUE 17 invariants: a sharded fleet is
byte-identical to one GeneralDocSet (the single-shard compat oracle),
migration preserves digests and re-routes — never drops — in-flight
changes behind the fence, the psum rollup equals the numpy sum, and
the controller's placement knob drains a hot shard while guaranteeing
to do nothing on a balanced fleet. Chaos lanes run duplicated /
reordered / partition-delayed delivery with migrations firing
mid-stream and still demand byte-identity with a clean oracle and
zero quarantines, on both the numpy and forced-native staging lanes.
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip('jax')

from jax.sharding import Mesh

from automerge_tpu import native
from automerge_tpu.common import ROOT_ID
from automerge_tpu.device import general
from automerge_tpu.parallel.general_shard import (
    fleet_rollup, sharded_fleet_order, sharded_rga_jobs)
from automerge_tpu.sync import GeneralDocSet
from automerge_tpu.sync.chaos import canonical, doc_set_view
from automerge_tpu.sync.control import FleetController
from automerge_tpu.sync.sharded import (
    PlacementMap, ShardedGeneralDocSet, decode_migration_unit,
    encode_migration_unit)
from automerge_tpu.utils.metrics import metrics


def _mesh(n=8):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f'needs {n} virtual devices')
    return Mesh(np.array(devs[:n]), ('docs',))


def rich_changes(d, n_items=3):
    """One doc's worth of changes: a list with causal inserts + sets
    and a second actor depending on the first — enough structure that
    a mis-sliced wire block or a lossy migration shows up in the
    materialized view, not just the clock."""
    obj = f'00000000-0000-4000-8000-{d:012x}'
    ops = [
        {'action': 'makeList', 'obj': obj},
        {'action': 'link', 'obj': ROOT_ID, 'key': 'items',
         'value': obj},
        {'action': 'ins', 'obj': obj, 'key': '_head', 'elem': 1},
        {'action': 'set', 'obj': obj, 'key': f'w0-{d}:1',
         'value': d * 10}]
    for i in range(2, n_items + 1):
        ops += [
            {'action': 'ins', 'obj': obj, 'key': f'w0-{d}:{i - 1}',
             'elem': i},
            {'action': 'set', 'obj': obj, 'key': f'w0-{d}:{i}',
             'value': d * 10 + i}]
    return [
        {'actor': f'w0-{d}', 'seq': 1, 'deps': {}, 'ops': ops},
        {'actor': f'w1-{d}', 'seq': 1, 'deps': {f'w0-{d}': 1},
         'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'meta',
                  'value': d}]}]


def seeded_pair(n_docs=12, n_shards=4, capacity=32):
    """(sharded, plain-oracle) both fed the identical seed batch."""
    sharded = ShardedGeneralDocSet(capacity, n_shards=n_shards)
    oracle = GeneralDocSet(capacity)
    batch = {f'doc{d}': rich_changes(d) for d in range(n_docs)}
    sharded.apply_changes_batch(batch)
    oracle.apply_changes_batch(batch)
    return sharded, oracle


def assert_views_equal(a, b):
    assert canonical(doc_set_view(a)) == canonical(doc_set_view(b))


class TestPlacementMap:
    def test_deterministic_and_stable(self):
        a = PlacementMap(8)
        b = PlacementMap(8)
        docs = [f'doc{i}' for i in range(200)]
        assert [a.shard_of(d) for d in docs] == \
            [b.shard_of(d) for d in docs]
        # every shard owns something under the default ring
        assert set(a.shard_of(d) for d in docs) == set(range(8))

    def test_pin_overrides_ring_and_unpin_restores(self):
        p = PlacementMap(4)
        ring = p.shard_of('doc0')
        p.pin('doc0', (ring + 1) % 4)
        assert p.shard_of('doc0') == (ring + 1) % 4
        p.unpin('doc0')
        assert p.shard_of('doc0') == ring

    def test_snapshot_round_trip(self):
        p = PlacementMap(4, replicas=16)
        p.pin('doc3', 2)
        q = PlacementMap.restore(p.snapshot())
        assert q.n_shards == 4
        for d in (f'doc{i}' for i in range(50)):
            assert q.shard_of(d) == p.shard_of(d)


class TestMigrationUnit:
    def test_round_trip(self):
        rec = {'doc_id': 'doc0', 'clock': {'w0-0': 1},
               'changes': rich_changes(0), 'queued': []}
        assert decode_migration_unit(
            encode_migration_unit(rec)) == rec

    def test_checksum_rejects_flipped_byte(self):
        unit = bytearray(encode_migration_unit(
            {'doc_id': 'doc0', 'changes': []}))
        unit[len(unit) // 2] ^= 0xFF
        with pytest.raises(ValueError):
            decode_migration_unit(bytes(unit))


class TestSingleShardCompat:
    """n_shards=1 (a 1-device mesh) must be digest- and
    byte-identical to the plain GeneralDocSet path."""

    def test_views_and_digests_identical(self):
        sharded, oracle = seeded_pair(n_shards=1)
        assert_views_equal(sharded, oracle)
        for d in oracle.doc_ids:
            assert int(sharded.digest_of_id(d)) == \
                int(oracle.digest_of_id(d))

    def test_multi_shard_views_identical_too(self):
        sharded, oracle = seeded_pair(n_shards=4)
        assert_views_equal(sharded, oracle)
        for d in oracle.doc_ids:
            assert int(sharded.digest_of_id(d)) == \
                int(oracle.digest_of_id(d))


class TestMeshPlacement:
    def test_conftest_forces_eight_devices(self):
        # the multi-device CI lane asserts the mesh it pays for
        assert len(jax.devices()) == 8

    def test_default_shards_cover_mesh_devices(self):
        sharded = ShardedGeneralDocSet(32)
        assert sharded.n_shards == 8
        assert len({str(d) for d in sharded.devices}) == 8

    @staticmethod
    def _assert_planes_home(sharded):
        for i, shard in enumerate(sharded.shards):
            planes = [v for v in shard.store.pool.mirror.values()
                      if isinstance(v, jax.Array)]
            assert planes, f'shard {i} holds no device planes'
            for v in planes:
                assert v.committed
                assert v.devices() == {sharded.devices[i]}

    @pytest.mark.parametrize('resume', [False, True],
                             ids=['applied', 'snapshot_resumed'])
    def test_shard_planes_committed_to_own_device(self, resume):
        """No device context on routine calls: each shard's store
        commits its mirror to its device, and every later apply,
        migration or resume keeps it there."""
        sharded, _ = seeded_pair(n_docs=24)
        assert all(len(s.doc_ids) for s in sharded.shards)
        doc = 'doc0'
        assert sharded.migrate_doc(doc, (sharded.shard_of(doc) + 1) % 4)
        if resume:
            sharded = ShardedGeneralDocSet.load_snapshot(
                sharded.save_snapshot(), mesh=_mesh(4))
        sharded.apply_changes(doc, [
            {'actor': 'late', 'seq': 1,
             'deps': sharded.clock_of_id(doc),
             'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'late',
                      'value': 1}]}])
        self._assert_planes_home(sharded)


class TestMigration:
    def test_parity_after_migration(self):
        sharded, oracle = seeded_pair()
        doc = 'doc0'
        src = sharded.shard_of(doc)
        dst = (src + 1) % sharded.n_shards
        before = int(sharded.digest_of_id(doc))
        assert sharded.migrate_doc(doc, dst)
        assert sharded.shard_of(doc) == dst
        assert int(sharded.digest_of_id(doc)) == before
        assert_views_equal(sharded, oracle)
        status = sharded.fleet_status()
        assert status['docs'][doc]['shard'] == dst
        assert status['placement']['migrations'] >= 1
        # the source dropped its copy (ghost id may remain; the live
        # registry and placement both answer dst)
        assert sharded._doc_shard[doc] == dst
        assert sharded.placement.shard_of(doc) == dst

    def test_plan_spreads_across_destinations(self):
        sharded, oracle = seeded_pair()
        docs = sharded.doc_ids[:3]
        plan = {d: (sharded.shard_of(d) + 1 + i) % sharded.n_shards
                for i, d in enumerate(docs)}
        plan = {d: s for d, s in plan.items()
                if s != sharded.shard_of(d)}
        moved = sharded.migrate_docs(plan)
        assert moved == len(plan)
        for d, s in plan.items():
            assert sharded.shard_of(d) == s
        assert_views_equal(sharded, oracle)

    def test_migrated_doc_keeps_accepting_writes(self):
        sharded, oracle = seeded_pair()
        doc = 'doc1'
        dst = (sharded.shard_of(doc) + 2) % sharded.n_shards
        sharded.migrate_doc(doc, dst)
        extra = [{'actor': f'w2-{doc}', 'seq': 1,
                  'deps': {'w0-1': 1},
                  'ops': [{'action': 'set', 'obj': ROOT_ID,
                           'key': 'post', 'value': 'moved'}]}]
        sharded.apply_changes(doc, extra)
        oracle.apply_changes(doc, extra)
        assert_views_equal(sharded, oracle)

    def test_fence_reroutes_concurrent_applies(self):
        """Changes arriving WHILE a doc migrates buffer behind the
        fence and land on the destination after the flip — never
        dropped, never applied to the dropped source."""
        sharded, oracle = seeded_pair()
        doc = 'doc2'
        src = sharded.shard_of(doc)
        dst = (src + 1) % sharded.n_shards
        late = [{'actor': f'w9-{doc}', 'seq': 1, 'deps': {},
                 'ops': [{'action': 'set', 'obj': ROOT_ID,
                          'key': 'late', 'value': 'fenced'}]}]
        real_extract = sharded.shards[src].extract_doc_state
        fenced_seen = {}

        def extract_and_race(ids):
            rec = real_extract(ids)
            # the fence is already up: this apply must buffer
            sharded.apply_changes_batch({doc: late})
            fenced_seen['buffered'] = doc in sharded._fences and \
                bool(sharded._fences[doc])
            return rec

        sharded.shards[src].extract_doc_state = extract_and_race
        try:
            assert sharded.migrate_doc(doc, dst)
        finally:
            sharded.shards[src].extract_doc_state = real_extract
        assert fenced_seen['buffered']
        assert doc not in sharded._fences
        oracle.apply_changes(doc, late)
        assert sharded.shard_of(doc) == dst
        assert_views_equal(sharded, oracle)
        assert metrics.counters.get('placement_fenced_changes', 0) > 0

    def test_absorb_fault_rolls_back_and_source_serves(self):
        sharded, oracle = seeded_pair()
        doc = 'doc3'
        src = sharded.shard_of(doc)
        dst = (src + 1) % sharded.n_shards
        real = sharded.shards[dst].apply_states

        def boom(payloads):
            raise RuntimeError('absorb fault')

        sharded.shards[dst].apply_states = boom
        sharded.shards[dst].apply_changes_batch_orig = None
        real_batch = sharded.shards[dst].apply_changes_batch
        sharded.shards[dst].apply_changes_batch = boom
        try:
            with pytest.raises(RuntimeError):
                sharded.migrate_doc(doc, dst)
        finally:
            sharded.shards[dst].apply_states = real
            sharded.shards[dst].apply_changes_batch = real_batch
        assert sharded.shard_of(doc) == src
        assert doc not in sharded._fences
        assert not sharded.quarantined
        assert_views_equal(sharded, oracle)

    def test_quarantined_docs_refuse_to_travel(self):
        sharded, _ = seeded_pair()
        doc = 'doc4'
        src = sharded.shard_of(doc)
        sharded.shards[src].quarantined[doc] = {
            'error': 'poisoned', 'changes': []}
        try:
            assert sharded.migrate_docs(
                [doc], (src + 1) % sharded.n_shards) == 0
            assert sharded.shard_of(doc) == src
        finally:
            sharded.shards[src].quarantined.pop(doc, None)


class TestWireAdmission:
    def test_columnar_block_slices_per_shard(self):
        """ONE AMW2 container spanning docs on different shards: the
        sharded slice-and-remap apply must land the identical state
        as the plain single-store apply of the same container."""
        wire_mod = pytest.importorskip('automerge_tpu.wire')
        per_doc = [rich_changes(d) for d in range(6)]
        doc_ids = [f'doc{d}' for d in range(6)]
        scratch = GeneralDocSet(8)
        block = scratch.store.encode_changes(per_doc)
        rows = list(range(block.n_changes))
        entries = wire_mod.encode_change_rows_columnar(block, rows)
        spans, tab = wire_mod.assemble_columnar_spans(entries)
        spans_per_doc = [[] for _ in range(block.n_docs)]
        for c, span in zip(rows, spans):
            spans_per_doc[block.doc[c]].append((0, span))
        data = wire_mod.build_columnar_container([tab], spans_per_doc)

        sharded = ShardedGeneralDocSet(32, n_shards=4)
        oracle = GeneralDocSet(32)
        handles = sharded.apply_wire(data, doc_ids=doc_ids)
        oracle.apply_wire(data, doc_ids=doc_ids)
        assert all(h is not None for h in handles)
        assert {sharded.shard_of(d) for d in doc_ids} != {0}
        assert_views_equal(sharded, oracle)
        for d in doc_ids:
            assert int(sharded.digest_of_id(d)) == \
                int(oracle.digest_of_id(d))

    def test_json_wire_routes_through_change_path(self):
        sharded = ShardedGeneralDocSet(16, n_shards=2)
        oracle = GeneralDocSet(16)
        per_doc = [rich_changes(d) for d in range(3)]
        ids = [f'doc{d}' for d in range(3)]
        data = json.dumps(per_doc).encode()
        sharded.apply_wire(data, doc_ids=ids)
        oracle.apply_wire(data, doc_ids=ids)
        assert_views_equal(sharded, oracle)


class TestRollups:
    def test_fleet_rollup_psum_equals_numpy(self):
        mesh = _mesh()
        per_shard = np.arange(8 * 5, dtype=np.int64).reshape(8, 5) * 3
        got = fleet_rollup(mesh, per_shard)
        np.testing.assert_array_equal(
            np.asarray(got, np.int64), per_shard.sum(axis=0))

    def test_fleet_rollup_big_values_stay_exact(self):
        # values past the int32 device lane fall back to numpy
        mesh = _mesh()
        per_shard = np.full((8, 2), 2**40, np.int64)
        got = fleet_rollup(mesh, per_shard)
        np.testing.assert_array_equal(
            np.asarray(got, np.int64), per_shard.sum(axis=0))

    def test_sharded_fleet_order_matches_per_shard(self):
        """The packed one-dispatch fleet ordering slices back to
        exactly what each shard's own dispatch would produce."""
        mesh = _mesh()
        rng = np.random.default_rng(7)
        shard_jobs = []
        for s in range(3):
            k, m = 2 + s, 6
            parent = np.zeros((k, m), np.int32)
            elem = np.zeros((k, m), np.int32)
            actor = np.zeros((k, m), np.int32)
            visible = np.ones((k, m), bool)
            valid = np.ones((k, m), bool)
            for j in range(k):
                for i in range(1, m):
                    parent[j, i] = rng.integers(0, i)
                    elem[j, i] = i
                    actor[j, i] = rng.integers(0, 4)
            shard_jobs.append((parent, elem, actor, visible, valid))
        per_shard, stats = sharded_fleet_order(mesh, shard_jobs)
        for planes, got in zip(shard_jobs, per_shard):
            ref, _ = sharded_rga_jobs(mesh, *planes)
            for name in ref:
                np.testing.assert_array_equal(
                    np.asarray(got[name]), np.asarray(ref[name]),
                    err_msg=name)
        assert stats['jobs'] >= sum(p[0].shape[0]
                                    for p in shard_jobs)


class TestPlacementKnob:
    def _loaded_fleet(self, pin_shard=0, n_docs=12):
        sharded = ShardedGeneralDocSet(32, n_shards=4)
        for d in range(n_docs):
            sharded.placement.pin(f'doc{d}', pin_shard)
        sharded.apply_changes_batch(
            {f'doc{d}': rich_changes(d) for d in range(n_docs)})
        return sharded

    def test_drains_hot_shard(self):
        sharded = self._loaded_fleet(n_docs=10)
        FleetController(sharded, hold=2, cooldown=2,
                        placement_min_ops=8, placement_ratio=1.5,
                        migrate_batch=2)
        before = metrics.counters.get('control_migrations', 0)
        rng = np.random.default_rng(3)
        for t in range(9):
            writes = {}
            for _ in range(16):
                d = min(int(rng.zipf(1.2)) - 1, 9)
                doc = f'doc{d}'
                writes.setdefault(doc, []).append(
                    {'actor': f'h{t}-{d}', 'seq': 1,
                     'deps': {f'w0-{d}': 1},
                     'ops': [{'action': 'set', 'obj': ROOT_ID,
                              'key': f'k{t}', 'value': t}]})
            sharded.apply_changes_batch(writes)
            sharded.tick()
        assert metrics.counters.get('control_migrations', 0) > before
        load = sharded.shard_load()
        assert sum(1 for n in load['docs'] if n > 0) > 1
        assert not sharded.quarantined

    def test_do_nothing_on_balanced_fleet(self):
        sharded = ShardedGeneralDocSet(32, n_shards=4)
        FleetController(sharded, hold=2, cooldown=2,
                        placement_min_ops=8, placement_ratio=1.5)
        docs = [f'doc{d}' for d in range(8)]
        for i, d in enumerate(docs):
            sharded.placement.pin(d, i % 4)
        sharded.apply_changes_batch(
            {d: rich_changes(i) for i, d in enumerate(docs)})
        before_m = metrics.counters.get('control_migrations', 0)
        placement_before = {d: sharded.shard_of(d) for d in docs}
        for t in range(5):
            sharded.apply_changes_batch(
                {d: [{'actor': f'b{t}-{i}', 'seq': 1,
                      'deps': {f'w0-{i}': 1},
                      'ops': [{'action': 'set', 'obj': ROOT_ID,
                               'key': f'k{t}', 'value': t}]}]
                 for i, d in enumerate(docs)})
            sharded.tick()
        assert metrics.counters.get(
            'control_migrations', 0) == before_m
        assert {d: sharded.shard_of(d)
                for d in docs} == placement_before


class TestSnapshot:
    def test_round_trip_preserves_views_and_placement(self):
        sharded, oracle = seeded_pair()
        doc = 'doc0'
        dst = (sharded.shard_of(doc) + 1) % sharded.n_shards
        sharded.migrate_doc(doc, dst)
        blob = sharded.save_snapshot()
        restored = ShardedGeneralDocSet.load_snapshot(blob)
        assert restored.shard_of(doc) == dst
        assert_views_equal(restored, sharded)
        assert_views_equal(restored, oracle)


def _chaos_run(seed, migrate_every=3):
    """Adversarial delivery into a sharded fleet with migrations
    firing mid-stream: each tick's wire batch may duplicate, arrive
    reordered, or sit out a partition and arrive late — every batch
    is delivered at least once. The clean oracle gets each batch
    exactly once, in order, on one plain GeneralDocSet."""
    rng = np.random.default_rng(seed)
    n_docs = 6
    sharded = ShardedGeneralDocSet(32, n_shards=4)
    oracle = GeneralDocSet(32)
    seed_batch = {f'doc{d}': rich_changes(d) for d in range(n_docs)}
    sharded.apply_changes_batch(seed_batch)
    oracle.apply_changes_batch(seed_batch)
    delayed = []                       # partitioned batches, land late
    for t in range(8):
        batch = {}
        for d in range(n_docs):
            if rng.random() < 0.6:
                batch[f'doc{d}'] = [
                    {'actor': f'c{t}-{d}', 'seq': 1,
                     'deps': {f'w0-{d}': 1},
                     'ops': [{'action': 'set', 'obj': ROOT_ID,
                              'key': f'k{t}', 'value': t * 100 + d}]}]
        oracle.apply_changes_batch(batch)
        r = rng.random()
        if r < 0.2:                    # partition: delivery delayed
            delayed.append(batch)
        elif r < 0.45:                 # duplicate delivery
            sharded.apply_changes_batch(batch)
            sharded.apply_changes_batch(batch)
        elif r < 0.7 and len(batch) > 1:   # reordered split delivery
            items = list(batch.items())
            order = rng.permutation(len(items))
            for i in order:
                sharded.apply_changes_batch(dict([items[i]]))
        else:
            sharded.apply_changes_batch(batch)
        if t % migrate_every == migrate_every - 1:
            doc = f'doc{int(rng.integers(n_docs))}'
            dst = int(rng.integers(sharded.n_shards))
            if dst != sharded.shard_of(doc):
                sharded.migrate_doc(doc, dst)
        sharded.tick()
    for batch in delayed:              # partitions heal, twice over
        sharded.apply_changes_batch(batch)
        sharded.apply_changes_batch(batch)
    return sharded, oracle


class TestChaosWithMigration:
    def test_converges_byte_identical_to_oracle(self):
        sharded, oracle = _chaos_run(seed=11)
        assert not sharded.quarantined
        assert not sharded.diverged
        assert_views_equal(sharded, oracle)
        for d in oracle.doc_ids:
            assert int(sharded.digest_of_id(d)) == \
                int(oracle.digest_of_id(d))

    @pytest.mark.skipif(not native.stage_available(),
                        reason='native stager unavailable')
    def test_forced_native_lane_matches(self):
        prev = general._NATIVE_STAGING
        views = {}
        try:
            for lane, force in (('numpy', False), ('native', True)):
                general._NATIVE_STAGING = force
                sharded, oracle = _chaos_run(seed=13)
                assert not sharded.quarantined
                assert_views_equal(sharded, oracle)
                views[lane] = canonical(doc_set_view(sharded))
        finally:
            general._NATIVE_STAGING = prev
        assert views['numpy'] == views['native']
