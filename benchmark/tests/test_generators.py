"""The YCSB generators follow YCSB's own code and make workload A's
shapes, and the reference is last-writer-wins in causal order."""

import json
import os

import numpy as np
import pytest

import reference
from conftest import BENCH
from gen import ycsb


def _config():
    with open(os.path.join(BENCH, 'configs', 'ycsb_a.json')) as f:
        return json.load(f)


def _fnv_plain(v):
    """FNV-1a over eight bytes, in Python integers, as Java computes it."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (v & 0xFF)) * 1099511628211) % 2 ** 64
        v >>= 8
    return abs(h - 2 ** 64 if h >= 2 ** 63 else h)


@pytest.mark.parametrize('v', [0, 1, 2, 255, 256, 102_399, 123_456_789,
                               9_999_999_999])
def test_fnvhash64_is_ycsbs(v):
    assert ycsb.fnvhash64([v]).tolist() == [_fnv_plain(v)]


def test_zetan_is_the_zeta_of_ycsbs_item_count():
    # sum of i ** -0.99 for i up to 10 ** 10: 10 ** 7 terms exactly and
    # the rest by Euler-Maclaurin
    s, m, n = 0.99, 10 ** 7, ycsb.ITEM_COUNT
    head = np.sum(np.arange(1, m + 1, dtype=np.float64) ** -s)
    tail = ((n ** (1 - s) - m ** (1 - s)) / (1 - s)
            + (n ** -s - m ** -s) / 2 + s * (m ** (-s - 1) - n ** (-s - 1)) / 12)
    assert head + tail == pytest.approx(ycsb.ZETAN, rel=1e-9)


def test_load_is_the_configured_table():
    cfg = _config()
    ids, history = ycsb.load_changes(cfg['recordcount'], cfg['fieldcount'],
                                     cfg['fieldlength'], seed=2 ** 31 + 5)
    assert len(set(ids)) == len(history) == 102_400
    assert sum(len(c['ops']) for cs in history for c in cs) == \
        cfg['ops_total'] == 1_024_000
    doc = reference.replay(history[7]).plain()
    assert sorted(doc) == [f'field{i}' for i in range(10)]
    assert {len(v) for v in doc.values()} == {100}
    assert all(32 <= ord(ch) < 128 for v in doc.values() for ch in v)


def test_zipfian_keys_are_ycsbs_skew():
    rng = np.random.default_rng(9)
    keys = ycsb.scrambled_zipfian(rng, 400_000, 102_400, 0.99)
    assert keys.min() >= 0 and keys.max() < 102_400
    top = np.sort(np.bincount(keys))[::-1] / keys.size
    # the hottest item of the Zipfian draws 1 / zetan; the second
    # 2 ** -0.99 / zetan (a hashed neighbour may share its record)
    assert top[0] == pytest.approx(1 / ycsb.ZETAN, rel=0.05)
    assert top[1] == pytest.approx(2 ** -0.99 / ycsb.ZETAN, rel=0.1)


def test_run_ticks_are_workload_a_and_causal():
    cfg = dict(_config(), recordcount=2048)
    ids, history = ycsb.load_changes(2048, 10, 100, seed=4)
    ticks = ycsb.run_ticks(cfg, 40, 256, seed=4)
    docs = {d: reference.replay(h) for d, h in zip(ids, history)}
    n_updates = n_reads = 0
    for updates, reads, n_ops in ticks:
        assert n_ops == sum(len(c['ops']) for cs in updates.values()
                            for c in cs)
        assert len(set(reads)) == len(reads)
        n_reads += len(reads)
        for doc_id, cs in updates.items():
            for c in cs:                 # every change applies in order
                (op,) = c['ops']
                assert op['key'].startswith('field')
                assert len(op['value']) == 100
                docs[doc_id].apply(c)
            n_updates += len(cs)
    assert n_updates == pytest.approx(0.5 * 40 * 256, rel=0.05)
    assert n_reads > 0


def test_reference_is_last_writer_wins_in_causal_order():
    root = reference.ROOT_ID
    doc = reference.RefDoc()
    doc.apply({'actor': 'a', 'seq': 1, 'deps': {}, 'ops': [
        {'action': 'set', 'obj': root, 'key': 'x', 'value': 1},
        {'action': 'makeMap', 'obj': 'm'},
        {'action': 'link', 'obj': root, 'key': 'm', 'value': 'm'},
        {'action': 'set', 'obj': 'm', 'key': 'y', 'value': 'v'}]})
    doc.apply({'actor': 'b', 'seq': 1, 'deps': {'a': 1}, 'ops': [
        {'action': 'set', 'obj': root, 'key': 'x', 'value': 2},
        {'action': 'del', 'obj': 'm', 'key': 'y'}]})
    assert doc.plain() == {'x': 2, 'm': {}}
    with pytest.raises(ValueError, match='out of order'):
        doc.apply({'actor': 'b', 'seq': 3, 'deps': {}, 'ops': []})
    with pytest.raises(ValueError, match='concurrent'):
        doc.apply({'actor': 'c', 'seq': 1, 'deps': {'a': 1}, 'ops': []})
    with pytest.raises(ValueError, match='outside'):
        doc.apply({'actor': 'b', 'seq': 2, 'deps': {'a': 1}, 'ops': [
            {'action': 'makeList', 'obj': 'l'}]})
