"""The harness finds a cell from data files alone, and refuses to run
without a TPU."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT, make_tiny_root, run_cell


def test_cell_added_as_data_files_runs(tmp_path):
    """A new deployment (YCSB workload B's read-mostly mix) and a new
    traffic mix, added as two JSON files and one entry each in
    BENCHMARK.json, run through the unchanged harness."""
    root = make_tiny_root(tmp_path)
    bench = os.path.join(root, 'benchmark')
    with open(os.path.join(bench, 'configs', 'ycsb_a.json')) as f:
        config = json.load(f)
    config.update(name='ycsb_b_small', recordcount=32, readproportion=0.95,
                  updateproportion=0.05)
    with open(os.path.join(bench, 'configs', 'ycsb_b_small.json'),
              'w') as f:
        json.dump(config, f)
    mix = {'loop': 'fixed_rate', 'requests': 'op_ticks', 'rate_per_s': 8,
           'ops_per_tick': 40}
    with open(os.path.join(bench, 'traffic', 'small_ticks.json'), 'w') as f:
        json.dump(mix, f)
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        spec = json.load(f)
    spec['configs'].append({'name': 'ycsb_b_small', 'source': 'test',
                            'file': 'benchmark/configs/ycsb_b_small.json',
                            'reduced': [], 'why': 'test'})
    spec['workloads'].append({'name': 'ycsb_b.small_ticks',
                              'config': 'ycsb_b_small',
                              'traffic': 'small_ticks', 'chips': 1,
                              'why': 'test'})
    for m in spec['end_to_end'] + spec['per_layer']:
        if 'ycsb_a.target_ticks' in m.get('workloads', []):
            m['workloads'].append('ycsb_b.small_ticks')
    with open(path, 'w') as f:
        json.dump(spec, f)
    out = run_cell(root, 'ycsb_b.small_ticks')
    assert out['correct'], out['checks']
    assert out['attempted'] == 8
    assert {'tick_p50_ms', 'merge_ops_per_s', 'hbm_peak_mb',
            'setup_s'} == set(out['metrics'])


def test_counter_is_per_unit_and_silent_where_nothing_moved():
    import harness
    rec = harness.Recorder()
    rec.units = 3
    rec.counters = {'a': 6, 'b': 3}
    ctx = harness.LayerContext(rec, None)
    assert ctx.counter('a', 'b', 'c') == 3.0
    assert ctx.counter('c') is None


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload',
         'ycsb_a.bulk_load', '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''
    assert 'needs a TPU' in proc.stderr
