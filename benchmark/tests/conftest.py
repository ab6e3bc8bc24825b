"""Shared fixtures: a tiny copy of the benchmark that runs on the CPU.

Run from the checkout's root:  python3 -m pytest benchmark/tests -q

The CPU backend is given four devices, so that a cell of four chips runs
here too.
"""

import json
import os
import shutil
import sys
import time

flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=4').strip()
os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

# The cut to a size the CPU runs in seconds, by parameter: every
# configuration and mix that has the key takes the value. A file's own
# ``"tiny"`` object gives the tiny values of keys this rule does not know.
TINY = {'recordcount': 64, 'rate_per_s': 10, 'ops_per_tick': 16}


def _cut(path):
    with open(path) as f:
        data = json.load(f)
    data.update({k: v for k, v in TINY.items() if k in data})
    data.update({k: v for k, v in data.get('tiny', {}).items()
                 if k not in TINY})
    with open(path, 'w') as f:
        json.dump(data, f)


def _load_spec(root):
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        return json.load(f)


def make_tiny_root(dst, src=ROOT):
    """A checkout-shaped directory holding a copy of ``src``'s
    ``benchmark/`` and ``BENCHMARK.json``, with every configuration and
    mix cut to a size the CPU runs in seconds."""
    shutil.copytree(os.path.join(src, 'benchmark'),
                    os.path.join(dst, 'benchmark'),
                    ignore=shutil.ignore_patterns('tests', '__pycache__'))
    shutil.copy(os.path.join(src, 'BENCHMARK.json'), dst)
    spec = _load_spec(dst)
    for c in spec['configs']:
        _cut(os.path.join(dst, c['file']))
    for mix in {w['traffic'] for w in spec['workloads']}:
        _cut(os.path.join(dst, 'benchmark', 'traffic', mix + '.json'))
    return str(dst)


@pytest.fixture(scope='session')
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp('tiny'))


def run_cell(root, workload, seed=3_000_000_017, seconds=1.0, path=None,
             trace=False):
    import harness
    return harness.run(workload, seed, seconds, trace, time.perf_counter(),
                       root=root, require_tpu=False, path=path)


def fixed_rate_cells(spec, root):
    """The cells whose mix is an open loop at a fixed rate."""
    out = []
    for w in spec['workloads']:
        with open(os.path.join(root, 'benchmark', 'traffic',
                               w['traffic'] + '.json')) as f:
            if json.load(f)['loop'] == 'fixed_rate':
                out.append(w['name'])
    return out


def program_metrics(spec, cell):
    """The per-layer metrics of ``cell`` read from the program's spans
    and counters, which a traced run on the CPU reports; those read from
    the device trace stay silent without a TPU."""
    return {m['name'] for m in spec['per_layer']
            if cell in m.get('workloads', [cell])
            and m['source'] != 'device_trace'}


SPEC = _load_spec(ROOT)
CELLS = [w['name'] for w in SPEC['workloads']]
TICK_CELLS = fixed_rate_cells(SPEC, ROOT)
