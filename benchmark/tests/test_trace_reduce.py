"""The trace reduction on a small trace recorded on a v5e
(``make_trace_fixture.py``): three runs of one jitted program, one per
``bench.apply``, with ``bench.wait`` spans of about 20 ms around them.

The expected numbers were read off the trace's raw events by hand:
``bench.window`` spans 49,233,966-134,382,540 ns; the three programs run
35,504 + 35,503 + 35,506 ns, and the union of their ops (a copy-start, a
copy-done and a fusion each) 35,498 + 35,497 + 35,499 ns. The device's
clock reads about 1.2 ms ahead of the host's in this trace, so each
program falls in the ``bench.wait`` before its ``bench.apply``.
"""

import os
from types import SimpleNamespace as NS

import pytest

import harness
import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), 'fixtures',
                       'v5e_three_applies.xplane.pb')


@pytest.fixture(scope='module')
def reduced():
    return trace_reduce.reduce(trace_reduce.load(FIXTURE))


def test_window_and_busy(reduced):
    assert reduced['devices'] == 1
    assert reduced['window_s'] == pytest.approx(85_148_574e-9, abs=1e-12)
    assert reduced['busy_s'] == pytest.approx(106_494e-9, abs=1e-12)
    assert reduced['busy_s_by_device'] == [reduced['busy_s']]
    assert reduced['idle_share'] == pytest.approx(
        1 - 106_494 / 85_148_574, abs=1e-12)


def test_programs_and_ops(reduced):
    assert list(reduced['program_s']) == [
        'jit__lambda(17575888338393728085)']
    assert reduced['program_s']['jit__lambda(17575888338393728085)'] == \
        pytest.approx(106_513e-9, abs=1e-12)
    top = trace_reduce.top_items(reduced['op_s'], top=1)
    assert top[0][0].startswith('%fusion = f32[]')
    assert top[0][1] == pytest.approx(
        (35_482 + 35_481 + 35_483) * 1e-9, abs=1e-12)


def test_idle_gaps(reduced):
    gaps = reduced['gaps']
    assert [g[0] for g in gaps[:4]] == ['bench.wait'] * 4
    assert [g[1] for g in gaps[:4]] == pytest.approx(
        [22_772_148e-9, 21_558_051e-9, 21_551_437e-9, 19_160_436e-9],
        abs=1e-12)
    assert sum(g[1] for g in gaps[:4]) < reduced['window_s']


def test_no_device_plane_reduces_to_none(tmp_path):
    class Empty:
        planes = []
    assert trace_reduce.reduce(Empty()) is None


def _plane(name, *lines):
    return NS(name=name, lines=[
        NS(name=line, events=[NS(name=n, start_ns=s, duration_ns=d)
                              for n, s, d in events])
        for line, events in lines])


def test_busy_by_device_tells_planes_apart():
    """Two chips taking turns: one busy 150 ns early in a 1,000 ns
    window (two overlapping ops), the other 100 ns late."""
    profile = NS(planes=[
        _plane('/host:CPU', ('python', [('bench.window', 0, 1000)])),
        _plane('/device:TPU:0', ('XLA Ops', [('a', 0, 100),
                                             ('b', 50, 100)])),
        _plane('/device:TPU:1', ('XLA Ops', [('c', 600, 100)])),
    ])
    reduced = trace_reduce.reduce(profile)
    assert reduced['devices'] == 2
    assert reduced['busy_s_by_device'] == pytest.approx([150e-9, 100e-9],
                                                        abs=1e-18)
    assert sum(reduced['busy_s_by_device']) / 2 == pytest.approx(
        reduced['busy_s'], abs=1e-18)
    assert reduced['idle_share'] == pytest.approx(1 - 125 / 1000)
    ctx = harness.LayerContext(harness.Recorder(), reduced)
    assert ctx.device_busy_shares() == pytest.approx([0.15, 0.10])
    assert harness.LayerContext(harness.Recorder(),
                                None).device_busy_shares() is None
