"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

One traced window gives, averaged over the device planes: the seconds in
which some operation ran (the union of the op intervals), the idle share,
device seconds per operation and per program, and the idle gaps, each
named by the benchmark's own host annotation (``bench.*``) that covers
the gap's midpoint.

The window is the span of the host annotation ``bench.window`` where the
trace holds one, else the first to the last device event.

Host and device events share the trace's clock only to about a
millisecond: on a v5e the device's programs show up about 1.2 ms before
the host span that ran them (``tests/test_trace_reduce.py``). Busy time
over seconds and gaps of tens of milliseconds are named reliably; a
shorter gap may be named by the span next to it.
"""

import re

from jax.profiler import ProfileData

DEVICE_PLANE = re.compile(r'^/device:(TPU|GPU):\d+$')
OPS_LINE = 'XLA Ops'
PROGRAMS_LINE = 'XLA Modules'
WINDOW = 'bench.window'
UNNAMED = 'outside any bench span'


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def load(path):
    return ProfileData.from_file(path)


def reduce(profile, top=10):
    """``{'window_s', 'busy_s', 'idle_share', 'devices', 'op_s',
    'program_s', 'gaps'}`` of one trace; ``op_s`` and ``program_s`` map
    a name to its device seconds summed over the device planes and
    divided by their number; ``gaps`` lists the ``top`` longest idle
    gaps as ``[name, seconds]``. Returns None where the trace holds no
    device plane or no device event."""
    host, devices = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                host += [ev for ev in _events(line)
                         if ev[0].startswith('bench.')]
    if not devices:
        return None
    lines = []
    for plane in devices:
        by_name = {line.name: line for line in plane.lines}
        ops = by_name.get(OPS_LINE)
        ops = _events(ops) if ops is not None else [
            ev for line in plane.lines for ev in _events(line)]
        progs = by_name.get(PROGRAMS_LINE)
        lines.append((ops, _events(progs) if progs is not None else []))
    windows = [ev for ev in host if ev[0] == WINDOW]
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:
        starts = [s for ops, _ in lines for _, s, _ in ops]
        ends = [e for ops, _ in lines for _, _, e in ops]
        if not starts:
            return None
        lo, hi = min(starts), max(ends)
    spans = sorted((s, e, n) for n, s, e in host if n != WINDOW)
    n_dev = len(devices)
    busy_ns = 0
    op_s, program_s, gaps = {}, {}, []
    for ops, progs in lines:
        ops = _clip(ops, lo, hi)
        merged = _union([(s, e) for _, s, e in ops])
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in ops:
            op_s[name] = op_s.get(name, 0.0) + (e - s) * 1e-9 / n_dev
        for name, s, e in _clip(progs, lo, hi):
            program_s[name] = (program_s.get(name, 0.0)
                               + (e - s) * 1e-9 / n_dev)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append([_name_at(spans, (s + e) / 2),
                             (e - s) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    window_s = (hi - lo) * 1e-9
    busy_s = busy_ns * 1e-9 / n_dev
    return {'window_s': window_s, 'busy_s': busy_s,
            'idle_share': 1.0 - busy_s / window_s if window_s else None,
            'devices': n_dev, 'op_s': op_s, 'program_s': program_s,
            'gaps': gaps[:top]}


def _name_at(spans, t):
    """The innermost ``bench.*`` span covering ``t``."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else UNNAMED


def top_items(seconds_by_name, top=10):
    return [[n, s] for n, s in sorted(seconds_by_name.items(),
                                      key=lambda kv: -kv[1])[:top]]
