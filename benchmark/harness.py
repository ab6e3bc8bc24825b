"""The benchmark harness: one run of one cell.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name under this directory:

* ``BENCHMARK.json`` (at the checkout's root) names the cell's
  configuration and traffic mix, and the per-layer metrics of each cell;
* ``configs/<config>.json`` is the deployment;
* ``traffic/<mix>.json`` holds the mix's parameters; its ``loop`` is
  ``bulk`` (closed loop: whole iterations back to back) or ``fixed_rate``
  (open loop: requests due at ``rate_per_s``, timed from when each was
  due), and its ``requests`` names the request kind;
* ``kinds/<kind>.py`` builds a request kind's data from the seed, warms
  up, serves one iteration or request, and checks the answers;
* ``layers/<metric>.py`` reads one per-layer metric; its ``read(ctx)``
  returns a number, or None where the run gave it nothing to read.

A run: look for the chips, point JAX's compile cache into the checkout,
build the cell's data from the seed and warm up its shapes (set-up),
measure for ``seconds``, read the device's memory peak, free the
program's state, compare the answers with the plain reference, and print
one JSON line. With ``trace`` the same run also records the program's
spans and a profiler trace over the first seconds of the window, and
prints the per-layer metrics in place of the end-to-end ones.
"""

import contextlib
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 5.0


class Failure(Exception):
    """The run cannot measure this cell (no chip, missing library,
    traffic that does not fit the window)."""


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise Failure(f'no module at {path}')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload, root=ROOT):
    """``(spec, cell, config, mix)`` of the named cell, from
    ``BENCHMARK.json`` and the files it names under ``root``."""
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        spec = json.load(f)
    cells = {w['name']: w for w in spec['workloads']}
    if workload not in cells:
        raise Failure(f'no workload {workload!r} in BENCHMARK.json')
    cell = cells[workload]
    configs = {c['name']: c for c in spec['configs']}
    with open(os.path.join(root, configs[cell['config']]['file'])) as f:
        config = json.load(f)
    with open(os.path.join(root, 'benchmark', 'traffic',
                           cell['traffic'] + '.json')) as f:
        mix = json.load(f)
    return spec, cell, config, mix


def build_system(config, mix, seed, seconds, rec, root=ROOT, path=None):
    kind = mix['requests']
    mod = _load_module(os.path.join(root, 'benchmark', 'kinds',
                                    kind + '.py'), 'bench_kind_' + kind)
    return mod.build(config, mix, seed, seconds, rec, path=path)


# -- preflight ------------------------------------------------------------------

def preflight(chips):
    """The devices this cell runs on. Raises Failure where JAX finds no
    TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != 'tpu':
        raise Failure(f'needs a TPU, but JAX found platform '
                      f'{devs[0].platform!r}; nothing ran')
    if len(devs) < chips:
        raise Failure(f'needs {chips} TPU chips, JAX found {len(devs)}')
    return devs[:chips]


def use_cache(root=ROOT):
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache`` (a fixed path: the path is
    part of the cache key). Every compile is written."""
    import jax
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir',
                          os.path.join(root, '.jax_cache'))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    return jax.config.jax_compilation_cache_dir


def require_native():
    """The program's native stager and view gather: without them it
    falls back to slower host paths, which is not the system served."""
    sys.path.insert(0, ROOT)
    from automerge_tpu import native
    probes = {'native.available': native.available,
              'native.stage_available': native.stage_available,
              'native.view_available': native.view_available}
    missing = [n for n, probe in probes.items() if not probe()]
    if missing:
        raise Failure(f'native libraries not built or loaded: {missing}')


class CompileWatch:
    """Counts JAX compiles and sums their seconds, from JAX's own
    monitoring events."""

    EVENT = '/jax/core/compile/backend_compile_duration'

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs


# -- the benchmark's own spans ---------------------------------------------------

class Recorder:
    """The benchmark's spans around each call into a layer. Off, a span
    is a no-op. On (while the profiler runs), it is a
    ``jax.profiler.TraceAnnotation`` (``bench.<name>``) that names the
    device's idle gaps; the program's own span events are collected from
    its metrics bus at the same time."""

    def __init__(self):
        self.on = False
        self.events = []
        self.units = 0

    @contextlib.contextmanager
    def span(self, name):
        if not self.on:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation('bench.' + name):
            yield

    def _collect(self, event):
        if event.get('event') == 'span':
            self.events.append((event['name'], event['dur_ms']))


class Tracer:
    """Turns the recorder and the profiler on over the first
    ``seconds`` of the window, a whole unit at a time."""

    def __init__(self, rec, seconds=TRACE_SECONDS):
        self.rec = rec
        self.seconds = seconds
        self.dir = None
        self.window = None
        self.t0 = None
        self.done = False

    def start(self):
        import jax
        from automerge_tpu.utils.metrics import metrics
        self.dir = tempfile.mkdtemp(prefix='bench_trace_')
        # the program's own annotations and the device's ops, and no
        # Python function tracing: that slows the host several times
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.window = jax.profiler.TraceAnnotation('bench.window')
        self.window.__enter__()
        metrics.subscribe(self.rec._collect)
        self.rec.on = True
        self.t0 = time.perf_counter()

    def after_unit(self):
        if self.done:
            return
        self.rec.units += 1
        if time.perf_counter() - self.t0 >= self.seconds:
            self.stop()

    def stop(self):
        import jax
        from automerge_tpu.utils.metrics import metrics
        if self.done:
            return
        self.done = True
        self.rec.on = False
        metrics.unsubscribe(self.rec._collect)
        self.window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        import trace_reduce
        try:
            for base, _, files in os.walk(self.dir):
                for f in files:
                    if f.endswith('.xplane.pb'):
                        return trace_reduce.reduce(trace_reduce.load(
                            os.path.join(base, f)))
            return None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class _NoTracer:
    def start(self):
        pass

    def after_unit(self):
        pass

    def stop(self):
        pass


# -- loops -----------------------------------------------------------------------

class Failures:
    """Requests that raised: counted against those attempted, and the
    first traceback printed to standard error."""

    def __init__(self):
        self.count = 0

    def call(self, fn, *args):
        try:
            return fn(*args)
        except Exception:
            if not self.count:
                traceback.print_exc()
            self.count += 1
            return 0


def bulk_loop(system, seconds, tracer):
    """Closed loop: whole iterations back to back until ``seconds`` have
    passed; the rate is all the work over all the time."""
    ops = iters = 0
    failures = Failures()
    t0 = time.perf_counter()
    tracer.start()
    while True:
        ops += failures.call(system.iteration)
        iters += 1
        tracer.after_unit()
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    tracer.stop()
    return {'attempted': iters, 'failed': failures.count, 'ops': ops,
            'elapsed': elapsed, 'latencies': None}


def fixed_rate_loop(system, rate, tracer, rec):
    """Open loop: request ``k`` is due ``k / rate`` seconds into the
    window and is timed from then until its answer is readable, so a
    stall counts against the requests queued behind it."""
    n = system.n_requests
    lat = np.empty(n)
    late = np.empty(n)
    ops = 0
    failures = Failures()
    t0 = time.perf_counter()
    tracer.start()
    for k in range(n):
        due = t0 + k / rate
        now = time.perf_counter()
        if now < due:
            with rec.span('wait'):
                time.sleep(due - now)
        late[k] = time.perf_counter() - due
        ops += failures.call(system.serve, k)
        lat[k] = time.perf_counter() - due
        tracer.after_unit()
    elapsed = time.perf_counter() - t0
    tracer.stop()
    return {'attempted': n, 'failed': failures.count, 'ops': ops,
            'elapsed': elapsed, 'latencies': lat, 'late': late}


# -- one run ------------------------------------------------------------------------

def device_info(devs):
    peak = max((d.memory_stats() or {}).get('peak_bytes_in_use', 0)
               for d in devs)
    return {'platform': devs[0].platform, 'kind': devs[0].device_kind,
            'count': len(devs), 'memory_peak_bytes': int(peak)}


def end_to_end(spec, cell, run, dev, setup_s):
    """The cell's end-to-end metrics, as BENCHMARK.json lists them."""
    values = {'setup_s': (setup_s, 's'),
              'hbm_peak_mb': (dev['memory_peak_bytes'] / 1e6, 'MB'),
              'merge_ops_per_s': (run['ops'] / run['elapsed'], 'ops/s')}
    if run['latencies'] is not None:
        ms = run['latencies'] * 1e3
        values['tick_p50_ms'] = (float(np.percentile(ms, 50)), 'ms')
        values['tick_p95_ms'] = (float(np.percentile(ms, 95)), 'ms')
    out = {}
    for m in spec['end_to_end']:
        if cell['name'] in m.get('workloads', [cell['name']]):
            value, unit = values[m['name']]
            out[m['name']] = {'value': value, 'unit': unit}
    return out


class LayerContext:
    """What a per-layer reader sees: the traced units, the program's
    span totals and the reduced device trace (None where there is
    none)."""

    def __init__(self, rec, trace):
        self.units = rec.units
        self.trace = trace
        self._span_ms = {}
        for name, ms in rec.events:
            self._span_ms[name] = self._span_ms.get(name, 0.0) + ms

    def span_ms(self, *names):
        """Program span milliseconds per traced unit, summed over
        ``names``; None where no such span was recorded."""
        got = [self._span_ms[n] for n in names if n in self._span_ms]
        if not got or not self.units:
            return None
        return sum(got) / self.units

    def program_ms(self, pattern):
        """Device milliseconds per traced unit of the programs whose
        name matches ``pattern``; None where none ran."""
        if self.trace is None or not self.units:
            return None
        hit = [s for n, s in self.trace['program_s'].items()
               if re.search(pattern, n)]
        if not hit:
            return None
        return sum(hit) * 1e3 / self.units


def per_layer(spec, cell, ctx, root=ROOT):
    out = {}
    for m in spec['per_layer']:
        if cell['name'] not in m.get('workloads', [cell['name']]):
            continue
        mod = _load_module(os.path.join(root, 'benchmark', 'layers',
                                        m['name'] + '.py'),
                           'bench_layer_' + m['name'].replace('.', '_'))
        value = mod.read(ctx)
        if value is not None:
            out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out


def run(workload, seed, seconds, trace, t_start, root=ROOT,
        require_tpu=True, path=None):
    """One run of one cell; returns the result line's object. ``path``
    replaces the served path (the control and the fault tests)."""
    spec, cell, config, mix = load_cell(workload, root)
    if require_tpu:
        devs = preflight(cell['chips'])
    else:
        import jax
        devs = jax.devices()[:cell['chips']]
    use_cache(root)
    require_native()
    watch = CompileWatch()
    rec = Recorder()
    system = build_system(config, mix, seed, seconds, rec, root, path)
    system.prepare(watch)
    # the generated traffic and the preloaded state stay alive all
    # window: keep them out of the collector's scans
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    compiles0 = watch.count
    tracer = Tracer(rec) if trace else _NoTracer()
    if mix['loop'] == 'bulk':
        result = bulk_loop(system, seconds, tracer)
    elif mix['loop'] == 'fixed_rate':
        result = fixed_rate_loop(system, mix['rate_per_s'], tracer, rec)
    else:
        raise Failure(f"unknown loop {mix['loop']!r}")
    window_compiles = watch.count - compiles0
    dev = device_info(devs)
    system.release()
    gc.collect()
    checks = system.check()
    correct = not result['failed'] and all(
        value <= limit for value, limit in checks.values())
    out = {'correct': correct, 'attempted': result['attempted'],
           'failed': result['failed']}
    if trace:
        reduced = tracer.reduce()
        out['metrics'] = per_layer(spec, cell, LayerContext(rec, reduced),
                                   root)
        if reduced is not None:
            dev['busy_s'] = reduced['busy_s']
            dev['window_s'] = reduced['window_s']
            import trace_reduce
            out['breakdown'] = {
                'device_ops': trace_reduce.top_items(reduced['program_s']
                                                     or reduced['op_s']),
                'idle_gaps': reduced['gaps']}
    else:
        out['metrics'] = end_to_end(spec, cell, result, dev, setup_s)
    out['device'] = dev
    out['setup'] = dict(system.setup_split, total_s=setup_s,
                        compiles=compiles0,
                        compile_s=watch.seconds)
    out['window'] = {'seconds': result['elapsed'], 'ops': result['ops'],
                     'compiles': window_compiles}
    if result.get('late') is not None:
        out['window']['late_p95_ms'] = float(
            np.percentile(result['late'], 95) * 1e3)
    out['checks'] = {name: {'value': value, 'limit': limit}
                     for name, (value, limit) in checks.items()}
    return out
