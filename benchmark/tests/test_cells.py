"""Each traffic mix runs through the harness at a tiny size on the CPU,
untraced and traced, and its answers equal the reference."""

import os

import pytest

from conftest import BENCH, CELLS, TICK_CELLS, run_cell


@pytest.mark.parametrize('cell', CELLS)
def test_cell_runs_correct(tiny_root, cell):
    out = run_cell(tiny_root, cell)
    assert out['correct'], out['checks']
    assert out['attempted'] > 0 and out['failed'] == 0
    assert out['window']['compiles'] == 0
    metrics = out['metrics']
    assert metrics['setup_s']['value'] > 0
    assert metrics['merge_ops_per_s']['value'] > 0
    assert 'hbm_peak_mb' in metrics
    tick = cell in TICK_CELLS
    assert ('tick_p50_ms' in metrics) == tick
    assert list(out)[-1] == 'checks'


@pytest.mark.parametrize('cell', CELLS)
def test_traced_run_reads_program_spans(tiny_root, cell):
    out = run_cell(tiny_root, cell, trace=True)
    assert out['correct'], out['checks']
    suffix = '.tick' if cell in TICK_CELLS else '.bulk'
    names = set(out['metrics'])
    # the CPU has no device plane: the trace-based readers stay silent
    assert names == {'stage_ms' + suffix, 'dispatch_ms' + suffix,
                     'read_ms' + suffix}
    assert all(m['value'] > 0 for m in out['metrics'].values())


def test_warmup_renumbers_the_ticks_it_picks():
    """Warm-up serves a subset of the window's ticks on its scratch set:
    renumbered, each applies in order under the reference, and ticks
    alike in their padded counts share a shape."""
    import harness
    import reference
    from gen import ycsb
    kind = harness._load_module(os.path.join(BENCH, 'kinds', 'op_ticks.py'),
                                'test_op_ticks')
    config = {'recordcount': 256, 'fieldcount': 10, 'fieldlength': 100,
              'readproportion': 0.5, 'updateproportion': 0.5,
              'requestdistribution': 'zipfian', 'zipfianconstant': 0.99}
    ids, history = ycsb.load_changes(256, 10, 100, seed=6)
    docs = {d: reference.replay(h) for d, h in zip(ids, history)}
    ticks = ycsb.run_ticks(config, 30, 64, seed=6)
    seqs = {}
    for updates, _, _ in ticks[::3]:
        for doc_id, cs in kind._renumber(updates, seqs).items():
            for c in cs:
                docs[doc_id].apply(c)
    assert [kind._bucket(n) for n in (1, 8, 90, 96, 97, 128, 129, 255)] == \
        [1, 8, 96, 96, 128, 128, 192, 256]
    shapes = {kind._shape(u, r) for u, r, _ in ticks}
    assert 1 <= len(shapes) < len(ticks)
