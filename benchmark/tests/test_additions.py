"""A configuration, a traffic mix, a request kind, a per-layer reader and
a cell of four chips, added as new files and new entries in
``BENCHMARK.json`` alone, run through the unchanged harness and the
unchanged tiny cut on the CPU's four devices."""

import functools
import json
import os
import shutil

import pytest

from conftest import ROOT, make_tiny_root, program_metrics, run_cell

CELL = 'ycsb_a4.split_ticks'

# op_ticks served by one GeneralDocSet per device it is handed: doc i of
# the load goes to shard i % n, and each shard's apply bumps the counter
# bench_split_applies
KIND = '''
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402

op_ticks = harness._load_module(os.path.join(HERE, 'op_ticks.py'),
                                'split_ticks_op_ticks')


class SplitPath:
    def __init__(self, devices):
        self.devices = devices

    def new(self, n_docs):
        from automerge_tpu.sync.general_doc_set import GeneralDocSet
        per = -(-n_docs // len(self.devices))
        return SimpleNamespace(home={}, shards=[
            GeneralDocSet(per, device=d) for d in self.devices])

    def apply(self, fleet, batch):
        from automerge_tpu.utils.metrics import metrics
        parts = [{} for _ in fleet.shards]
        for doc_id, changes in batch.items():
            s = fleet.home.setdefault(doc_id,
                                      len(fleet.home) % len(fleet.shards))
            parts[s][doc_id] = changes
        for shard, part in zip(fleet.shards, parts):
            if part:
                shard.apply_changes_batch(part)
                metrics.bump('bench_split_applies')

    def read(self, fleet, doc_ids):
        views = {}
        for s, shard in enumerate(fleet.shards):
            ids = [d for d in doc_ids if fleet.home[d] == s]
            if ids:
                views.update(zip(ids, shard.materialize_many(ids)))
        return [views[d] for d in doc_ids]


def build(config, mix, seed, seconds, rec, path=None, devices=None):
    assert len(devices) == config['shards']
    return op_ticks.OpTicks(config, mix, seed, seconds, rec,
                            path or SplitPath(devices))
'''

READER = '''
def read(ctx):
    return ctx.counter('bench_split_applies')
'''


def _write_json(path, data):
    with open(path, 'w') as f:
        json.dump(data, f)


def extend(dst):
    """A full-size copy of the benchmark with the cell's files added and
    its entries appended to ``BENCHMARK.json``."""
    shutil.copytree(os.path.join(ROOT, 'benchmark'),
                    os.path.join(dst, 'benchmark'),
                    ignore=shutil.ignore_patterns('tests', '__pycache__'))
    bench = os.path.join(dst, 'benchmark')
    with open(os.path.join(bench, 'configs', 'ycsb_a.json')) as f:
        config = json.load(f)
    # the rule cuts recordcount; the file's own tiny object cuts the
    # key the rule does not know, and cannot override one it does
    config.update(name='ycsb_a4s', shards=4,
                  tiny={'fieldlength': 10, 'recordcount': 4096})
    _write_json(os.path.join(bench, 'configs', 'ycsb_a4s.json'), config)
    _write_json(os.path.join(bench, 'traffic', 'split_ticks.json'),
                {'loop': 'fixed_rate', 'requests': 'split_ticks',
                 'rate_per_s': 8.4, 'ops_per_tick': 1024})
    with open(os.path.join(bench, 'kinds', 'split_ticks.py'), 'w') as f:
        f.write(KIND)
    with open(os.path.join(bench, 'layers', 'shard_applies.split.py'),
              'w') as f:
        f.write(READER)
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    spec['configs'].append({'name': 'ycsb_a4s', 'source': 'test',
                            'file': 'benchmark/configs/ycsb_a4s.json',
                            'reduced': ['recordcount'], 'why': 'test'})
    spec['workloads'].append({'name': CELL, 'config': 'ycsb_a4s',
                              'traffic': 'split_ticks', 'chips': 4,
                              'why': 'test'})
    for m in spec['end_to_end']:
        if m['name'] in ('merge_ops_per_s', 'tick_p50_ms'):
            m['workloads'].append(CELL)
    spec['per_layer'].append({'name': 'shard_applies.split',
                              'unit': 'applies', 'better': 'lower',
                              'source': 'program_counter',
                              'layer': 'shards', 'moves': 'tick_p50_ms',
                              'workloads': [CELL]})
    _write_json(os.path.join(dst, 'BENCHMARK.json'), spec)
    return str(dst)


@pytest.fixture(scope='module')
def extended(tmp_path_factory):
    full = extend(tmp_path_factory.mktemp('full'))
    return make_tiny_root(tmp_path_factory.mktemp('tiny'), src=full)


def _read(root, *parts):
    with open(os.path.join(root, 'benchmark', *parts)) as f:
        return json.load(f)


def test_tiny_cut_takes_new_files(extended):
    config = _read(extended, 'configs', 'ycsb_a4s.json')
    assert (config['recordcount'], config['fieldlength'],
            config['shards']) == (64, 10, 4)
    mix = _read(extended, 'traffic', 'split_ticks.json')
    assert (mix['rate_per_s'], mix['ops_per_tick']) == (10, 16)
    assert _read(extended, 'configs', 'ycsb_a.json')['recordcount'] == 64


def _capture(monkeypatch):
    import harness
    built = []
    build = harness.build_system

    def keep(*args, **kwargs):
        built.append(build(*args, **kwargs))
        return built[-1]
    monkeypatch.setattr(harness, 'build_system', keep)
    return built


def _applies_per_tick(system):
    """Shard applies per tick, counted from the ticks: one for each
    shard that holds a doc the tick updates."""
    index = {doc_id: i for i, doc_id in enumerate(system.ids)}
    shards = [{index[d] % 4 for d in updates}
              for updates, _, _ in system.ticks]
    return sum(map(len, shards)) / len(shards)


def test_four_chip_cell_runs_on_four_devices(extended, monkeypatch):
    import jax
    built = _capture(monkeypatch)
    out = run_cell(extended, CELL)
    assert out['correct'], out['checks']
    assert out['attempted'] == 10 and out['failed'] == 0
    assert out['window']['compiles'] == 0
    assert out['device']['count'] == 4
    assert set(out['metrics']) == {'merge_ops_per_s', 'tick_p50_ms',
                                   'hbm_peak_mb', 'setup_s'}
    devices = built[0].path.devices
    assert len(set(devices)) == 4
    assert set(devices) <= set(jax.devices())


def test_counter_reader_reads_the_kinds_count(extended, monkeypatch):
    built = _capture(monkeypatch)
    out = run_cell(extended, CELL, seed=4_000_000_037, trace=True)
    assert out['correct'], out['checks']
    with open(os.path.join(extended, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    assert set(out['metrics']) == program_metrics(spec, CELL) == \
        {'shard_applies.split'}
    # the 1 s window lies inside the traced seconds: every tick is traced
    assert out['metrics']['shard_applies.split']['value'] == \
        pytest.approx(_applies_per_tick(built[0]))


def test_four_chip_cell_on_one_device_fails(extended, monkeypatch, capsys):
    import harness
    import jax
    import run
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, 'devices', lambda *args, **kwargs: one)
    with pytest.raises(harness.Failure, match='needs 4 chips'):
        run_cell(extended, CELL)
    monkeypatch.setattr(harness, 'run', functools.partial(
        harness.run, root=extended, require_tpu=False))
    with pytest.raises(SystemExit) as stop:
        run.main(['--workload', CELL, '--seed', '1', '--seconds', '1'])
    assert 'needs 4 chips' in str(stop.value.code)
    assert capsys.readouterr().out == ''
