"""Shared fixtures: a tiny copy of the benchmark that runs on the CPU.

Run from the checkout's root:  python3 -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import sys
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

TINY_CONFIG = {'ycsb_a': {'recordcount': 64}}
TINY_TRAFFIC = {'target_ticks': {'rate_per_s': 10, 'ops_per_tick': 16}}


def _update(path, changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, 'w') as f:
        json.dump(data, f)


def make_tiny_root(dst):
    """A checkout-shaped directory holding a copy of ``benchmark/`` and
    ``BENCHMARK.json`` with every configuration and mix cut to a size
    the CPU runs in seconds."""
    shutil.copytree(BENCH, os.path.join(dst, 'benchmark'),
                    ignore=shutil.ignore_patterns('tests', '__pycache__'))
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), dst)
    with open(os.path.join(dst, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    for c in spec['configs']:
        _update(os.path.join(dst, c['file']), TINY_CONFIG[c['name']])
    for mix, changes in TINY_TRAFFIC.items():
        _update(os.path.join(dst, 'benchmark', 'traffic', mix + '.json'),
                changes)
    return str(dst)


@pytest.fixture(scope='session')
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp('tiny'))


def run_cell(root, workload, seed=3_000_000_017, seconds=1.0, path=None,
             trace=False):
    import harness
    return harness.run(workload, seed, seconds, trace, time.perf_counter(),
                       root=root, require_tpu=False, path=path)


CELLS = ['ycsb_a.bulk_load', 'ycsb_a.target_ticks']
TICK_CELLS = ['ycsb_a.target_ticks']
