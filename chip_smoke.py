"""On-chip smoke run of the served general-engine path.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips, the sharded fleet only

One chip runs four phases, the last three twice (the first pass compiles, the
second runs warm) and each checked against an independent reference:

* config 5, the 10k-doc collab server: a 10,240-doc fleet of ~1M
  mixed ops (lists, links, causal chains) loaded into a
  ``GeneralDocSet``, replicated to a second one over a
  ``WireConnection`` pair at the default wire version, materialized,
  and a seeded sample of docs compared with the host oracle
  (``automerge_tpu.backend``);
* live typing: one change per tick, minted by the frontend, into a
  ~32k-char text and into a short text, served through the general
  backend protocol; after every tick the frontend's text equals the
  host-built string, warm ticks take the incremental index, and the
  short text's patch reads run the Pallas edit-stream kernel;
* kernel parity: the Pallas edit-stream and RGA kernels against their
  XLA twins on random inputs at the widths they serve;
* the dense headline: the 1M-op ``gen_block_workload`` block through
  ``DenseMapStore``, with a doc sample compared with the host oracle,
  and the resolve kernel's auto pick (Pallas at this shape) compared
  with ``kernel='xla'``.

``--four-chips`` applies the config-5 fleet to a 4-shard
``ShardedGeneralDocSet`` over four devices and to a plain
``GeneralDocSet`` on device 0, and checks that views and digests are
equal, that docs sit on all four shards, that each shard's store
planes are committed to its own device (routine calls run under no
device context), and that a live migration keeps digest parity.

Every phase runs in this one process (a chip belongs to one process),
and no failure is caught: any error exits non-zero. Without a TPU the
script exits non-zero before running anything. Lines before the last
report set-up observations (compile and warm seconds, compile counts,
peak device memory), not benchmark results. The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, compiled programs are kept
there; otherwise in ``<checkout>/.jax_cache``.
"""

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# config 5 (BASELINE.json): 10,240 docs x ~100 ops. gen_mixed_docs
# gives 2 * list_ops + 3 ops per doc.
FLEET_DOCS = 10240
FLEET_LIST_OPS = 48
ORACLE_SAMPLE = 64
LONG_TEXT_CHARS = 32768
SHORT_TEXT_CHARS = 300
TYPING_TICKS = 16


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def report(msg):
    print(msg, flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def run_twice(name, fn):
    """Run a phase cold (compiles) and warm; report both as set-up."""
    _, cold = _timed(fn)
    out, warm = _timed(fn)
    report(f'setup[{name}]: cold pass {cold:.3f} s (includes '
           f'compiles), warm pass {warm:.3f} s')
    return out


# -- references ---------------------------------------------------------------

def plain_tree(doc):
    """A frontend document as plain JSON (Text joined to a string)."""
    name = type(doc).__name__
    if name == 'Text':
        return ''.join(str(c) for c in doc)
    if name == 'AmList':
        return [plain_tree(v) for v in doc]
    if hasattr(doc, '_conflicts'):
        return {k: plain_tree(v) for k, v in doc.items()}
    return doc


def oracle_doc(changes):
    """The host oracle's view of ``changes``: reference backend
    ``apply_changes`` -> ``get_patch`` -> frontend patch applier."""
    from automerge_tpu import backend as Backend
    from automerge_tpu import frontend as Frontend
    state, _ = Backend.apply_changes(Backend.init(), changes)
    return Frontend.apply_patch(Frontend.init('viewer'),
                                Backend.get_patch(state))


def doc_sample(n_docs, k, seed):
    """``k`` seeded doc indexes, the last doc always among them."""
    rng = np.random.default_rng(seed)
    rest = rng.choice(n_docs - 1, min(k, n_docs) - 1, replace=False)
    return sorted(int(d) for d in rest) + [n_docs - 1]


def plane_devices(doc_set):
    """The devices holding a general doc set's device arrays (the
    store's own and its sequence mirror planes); ``'uncommitted'``
    stands for an array that is not committed to its device."""
    store = doc_set.store
    found = set()
    for v in (list(vars(store).values())
              + list((store.pool.mirror or {}).values())):
        if isinstance(v, jax.Array):
            found |= set(v.devices()) if v.committed else {'uncommitted'}
    return found


# -- preflight ----------------------------------------------------------------

def preflight(four_chips):
    devs = jax.devices()
    platform = devs[0].platform
    if platform != 'tpu':
        sys.exit(f'chip_smoke: needs a TPU, but JAX found platform '
                 f'{platform!r} ({len(devs)} device(s)); nothing ran')
    want = 4 if four_chips else 1
    if len(devs) < want:
        sys.exit(f'chip_smoke: needs {want} TPU devices, found '
                 f'{len(devs)}')
    sys.path.insert(0, HERE)
    import automerge_tpu
    pkg = os.path.dirname(os.path.abspath(automerge_tpu.__file__))
    check(pkg == os.path.join(HERE, 'automerge_tpu'),
          f'automerge_tpu imported from {pkg}, not from this checkout')
    from automerge_tpu.config import use_compile_cache
    cache = use_compile_cache()
    from automerge_tpu import native, wire
    libs = {'native.available': native.available,
            'native.stage_available': native.stage_available,
            'native.view_available': native.view_available,
            'native.emit_available': native.emit_available,
            'native.columnar_available': native.columnar_available,
            'native.columnar_v3_available': native.columnar_v3_available,
            'wire.available': wire.available}
    missing = [name for name, probe in libs.items() if not probe()]
    check(not missing, f'native codec/stager not built or loaded: '
          f'{missing} (needs g++; see automerge_tpu/native.py)')
    report(f'setup[device]: {devs[0].device_kind} x {len(devs)} '
           f'({platform}); compile cache {cache}')
    return devs


# -- phase: config 5 ----------------------------------------------------------

def pump(ca, cb, msgs_a, msgs_b, max_rounds=1000):
    """Flush/deliver both ends of a connection pair until quiet.
    Returns the wire versions of the data messages a sent."""
    versions = set()
    for _ in range(max_rounds):
        ca.flush()
        if not (msgs_a or msgs_b):
            return versions
        batch, msgs_a[:] = msgs_a[:], []
        for m in batch:
            if m.get('blob'):
                versions.add(m.get('wire', 1))
            cb.receive_msg(m)
        cb.flush()
        batch, msgs_b[:] = msgs_b[:], []
        for m in batch:
            ca.receive_msg(m)
    raise AssertionError(f'connection pair not quiet after {max_rounds} '
                         f'rounds')


def phase_fleet(n_docs=FLEET_DOCS, list_ops=FLEET_LIST_OPS,
                sample=ORACLE_SAMPLE, seed=0):
    """Config 5: load, replicate over the wire, materialize, check the
    destination against the source and a doc sample against the
    oracle."""
    from automerge_tpu.device.workloads import gen_mixed_docs
    from automerge_tpu.sync.connection import WireConnection
    from automerge_tpu.sync.general_doc_set import GeneralDocSet

    per_doc = gen_mixed_docs(n_docs, list_ops)
    n_ops = sum(len(c['ops']) for doc in per_doc for c in doc)
    batch = {f'doc{d}': per_doc[d] for d in range(n_docs)}
    picks = doc_sample(n_docs, sample, seed)
    want = {d: plain_tree(oracle_doc(per_doc[d])) for d in picks}

    def one_pass():
        src = GeneralDocSet(n_docs)
        src.apply_changes_batch(batch)
        dst = GeneralDocSet(n_docs)
        msgs_a, msgs_b = [], []
        ca = WireConnection(src, msgs_a.append)
        cb = WireConnection(dst, msgs_b.append)
        ca.open()
        cb.open()
        versions = pump(ca, cb, msgs_a, msgs_b)
        ca.close()
        cb.close()
        view = dst.materialize_all()
        check(len(view) == n_docs,
              f'destination holds {len(view)} of {n_docs} docs')
        check(view == src.materialize_all(),
              'destination view differs from the source')
        for d in picks:
            check(view[f'doc{d}'] == want[d],
                  f'doc{d} differs from the host oracle')
        return versions

    versions = run_twice('config5', one_pass)
    report(f'setup[config5]: {n_docs} docs, {n_ops} ops replicated '
           f'over WireConnection (data at wire version(s) '
           f'{sorted(versions)}); {len(picks)} sampled docs equal the '
           f'host oracle')


# -- phase: live typing -------------------------------------------------------

def _typing_script(n_chars, ticks, cursor, seed):
    """Frontend-minted typing: the initial text as one change, then
    ``ticks`` one-char changes at ``cursor`` (None = end of text).
    Returns (history, tick changes, host strings after each tick)."""
    from automerge_tpu import Text
    from automerge_tpu import backend as Backend
    from automerge_tpu import frontend as Frontend

    rng = np.random.default_rng(seed)
    alphabet = 'abcdefghijklmnopqrstuvwxyz '
    chars = [alphabet[i] for i in rng.integers(0, len(alphabet),
                                               n_chars + ticks)]
    actor = f'typist-{seed}'
    doc = Frontend.set_actor_id(Frontend.init({'backend': Backend}),
                                actor)

    def init(d):
        d['text'] = Text()
        d['text'].insert_at(0, *chars[:n_chars])

    doc, _ = Frontend.change(doc, init)
    state = Frontend.get_backend_state(doc)
    history = Backend.get_changes_for_actor(state, actor)
    host = ''.join(chars[:n_chars])
    tick_changes, strings = [], []
    for i in range(ticks):
        c = chars[n_chars + i]
        at = len(host) if cursor is None else cursor + i
        doc, _ = Frontend.change(
            doc, lambda d, at=at, c=c: d['text'].insert_at(at, c))
        host = host[:at] + c + host[at:]
        state = Frontend.get_backend_state(doc)
        tick_changes.append(
            Backend.get_changes_for_actor(state, actor)[-1:])
        strings.append(host)
    check(str(plain_tree(doc['text'])) == host,
          'frontend text disagrees with the host-built string')
    return history, tick_changes, strings


def _serve_typing(history, tick_changes, strings):
    """Apply the history, then each tick, through the general backend
    protocol; a viewer frontend applies every patch."""
    from automerge_tpu import frontend as Frontend
    from automerge_tpu.device import general_backend as GB

    state = GB.init()
    viewer = Frontend.init({'backend': GB})
    state, patch = GB.apply_changes(state, history)
    patch['state'] = state
    viewer = Frontend.apply_patch(viewer, patch)
    for i, (changes, want) in enumerate(zip(tick_changes, strings)):
        state, patch = GB.apply_changes(state, changes)
        patch['state'] = state
        viewer = Frontend.apply_patch(viewer, patch)
        check(plain_tree(viewer['text']) == want,
              f'tick {i}: served text differs from the host string')


def phase_typing(long_chars=LONG_TEXT_CHARS, short_chars=SHORT_TEXT_CHARS,
                 ticks=TYPING_TICKS, seed=0):
    """Live typing: end-of-text typing into a long text (warm ticks
    take the incremental index and its suffix window) and mid-text
    typing into a short one (every tick reads its patch through the
    edit-stream kernel, Pallas at this width)."""
    from automerge_tpu.utils.metrics import metrics

    long_script = _typing_script(long_chars, ticks, None, seed)
    short_script = _typing_script(short_chars, ticks, short_chars // 2,
                                  seed + 1)

    def counter(name):
        return metrics.counters.get(name, 0)

    def one_pass():
        incr0 = counter('device_idx_incremental_applies')
        _serve_typing(*long_script)
        incr = counter('device_idx_incremental_applies') - incr0
        check(incr >= ticks - 1,
              f'{incr} incremental applies over {ticks} warm ticks '
              f'of the long text')
        _serve_typing(*short_script)
        return incr

    from automerge_tpu.device import pallas_view, profiler
    lax0 = profiler.signatures('view.edit_stream')
    incr = run_twice('typing', one_pass)
    small = pallas_view._PALLAS_MAX_M
    pallas = [sig for sig in profiler.signatures('view.edit_stream_pallas')
              if sig[2] <= small]
    check(pallas, 'the Pallas edit-stream kernel never served a patch '
          'read')
    lax_small = [sig for sig in profiler.signatures('view.edit_stream')
                 - lax0 if sig[2] <= small]
    check(not lax_small, f'lax edit stream served planes of m <= '
          f'{small}: {lax_small}')
    compiles = metrics.counters.get(
        'jit/view.edit_stream_pallas/device_compiles', 0)
    report(f'setup[typing]: {long_chars}-char and {short_chars}-char '
           f'texts, {ticks} ticks each, served text equals the host '
           f'string every tick; {incr} incremental applies on the long '
           f'text; jit/view.edit_stream_pallas/device_compiles='
           f'{compiles}, signatures (fmt, K, m, e_pad) {sorted(pallas)}')


# -- phase: dense headline ----------------------------------------------------

def phase_dense(seed=0, sample=ORACLE_SAMPLE, resolve_docs=1024):
    """The 1M-op block through DenseMapStore, a doc sample against the
    oracle; the resolve kernel's auto pick against kernel='xla'."""
    from automerge_tpu import frontend as Frontend
    from automerge_tpu.device import profiler
    from automerge_tpu.device.dense_store import DenseMapStore
    from automerge_tpu.device.engine import batch_merge_docs
    from automerge_tpu.device.workloads import gen_block_workload

    block = gen_block_workload(seed=seed)
    picks = doc_sample(block.n_docs, sample, seed)
    changes_of = {}
    for c in range(block.n_changes):
        changes_of.setdefault(int(block.doc[c]), []).append(c)
    want = {}
    for d in picks:
        doc = oracle_doc([block.change_dict(c) for c in changes_of[d]])
        want[d] = (plain_tree(doc), doc._conflicts)
    store = DenseMapStore(block.n_docs, key_capacity=64,
                          actor_capacity=16)

    def one_pass():
        store.reset()
        jax.block_until_ready(store.eseq)
        patch = store.apply_block(block)
        patch.block_until_ready()
        return patch

    patch = run_twice('dense', one_pass)
    for d in picks:
        doc = Frontend.apply_patch(
            Frontend.init('viewer'),
            {'clock': {}, 'deps': {}, 'canUndo': False,
             'canRedo': False, 'diffs': patch.diffs(d)})
        check((plain_tree(doc), doc._conflicts) == want[d],
              f'dense doc {d} differs from the host oracle')

    docs = [[block.change_dict(c) for c in changes_of[d]]
            for d in range(resolve_docs)]
    auto, t_auto = _timed(lambda: batch_merge_docs(docs))
    xla, t_xla = _timed(lambda: batch_merge_docs(docs, kernel='xla'))
    check(auto == xla, 'resolve kernel: auto pick differs from xla')
    picked = {sig[0] for sig in profiler.signatures('engine.resolve')}
    check('resolve_assignments_batch_pallas' in picked,
          f'the auto resolve kernel did not pick Pallas: {picked}')
    report(f'setup[dense]: {block.n_ops} ops / {block.n_docs} docs; '
           f'{len(picks)} sampled docs equal the host oracle; resolve '
           f'kernel on {resolve_docs} docs: auto (Pallas) equals xla '
           f'({t_auto:.3f} s / {t_xla:.3f} s incl. compile)')


# -- phase: kernel parity ----------------------------------------------------

def _edit_planes(rng, K, m):
    """Random edit-stream planes: prior/new visibility, dense unique
    per-row ranks for visible nodes, a packed touched plane."""
    pv = rng.random((K, m)) < 0.5
    nv = rng.random((K, m)) < 0.5
    touched = (rng.random((K, m)) < 0.4) | (pv != nv)
    pi = np.full((K, m), -1, np.int32)
    ni = np.full((K, m), -1, np.int32)
    for j in range(K):
        for vis, idx in ((pv, pi), (nv, ni)):
            cols = np.flatnonzero(vis[j])
            idx[j, cols] = rng.permutation(len(cols))
    return pv, nv, pi, ni, np.packbits(touched, axis=1)


def _rga_trees(rng, K, m, n_real):
    """Random insertion trees with concurrent siblings."""
    parent = np.zeros((K, m), np.int32)
    for i in range(1, n_real):
        parent[:, i] = rng.integers(0, i, K)
    elem = np.tile(np.arange(m, dtype=np.int32), (K, 1))
    actor = rng.integers(0, 8, (K, m)).astype(np.int32)
    visible = rng.random((K, m)) < 0.9
    valid = np.zeros((K, m), bool)
    valid[:, :n_real] = True
    return parent, elem, actor, visible, valid


def phase_kernels(seed=0, edit_shapes=((64, 128, 8), (256, 512, 64),
                                       (5, 120, 8)),
                  rga_shape=(2048, 128, 66), interpret=False):
    """The Pallas kernels against their XLA twins on random inputs at
    the widths they serve: the edit stream up to its auto-dispatch
    bound, the RGA kernel at the bench A/B shape."""
    from automerge_tpu.device import pallas_view
    from automerge_tpu.device.pallas_sequence import rga_order_batch_pallas
    from automerge_tpu.device.sequence import _rga_order

    rng = np.random.default_rng(seed)
    for K, m, e_pad in edit_shapes:
        planes = _edit_planes(rng, K, m)
        want = jax.device_get(pallas_view.edit_stream(*planes,
                                                      e_pad=e_pad))
        got = jax.device_get(pallas_view.edit_stream_pallas(
            *planes, e_pad=e_pad, interpret=interpret))
        check(all(np.array_equal(a, b) for a, b in zip(want, got)),
              f'edit_stream_pallas differs from edit_stream at '
              f'K={K} m={m} e_pad={e_pad}')
    trees = _rga_trees(rng, *rga_shape)
    want = jax.device_get(jax.jit(jax.vmap(_rga_order))(*trees))
    got = jax.device_get(rga_order_batch_pallas(*trees,
                                                interpret=interpret))
    for key in ('vis_index', 'length'):
        check(np.array_equal(want[key], got[key]),
              f'rga_order_batch_pallas {key} differs from the gather '
              f'variant at {rga_shape[:2]}')
    report(f'setup[kernels]: edit_stream_pallas equals edit_stream at '
           f'(K, m, e_pad) {list(edit_shapes)}; rga_order_batch_pallas '
           f'equals the gather variant at {rga_shape[:2]}')


# -- phase: four chips --------------------------------------------------------

def phase_four_chips(n_docs=FLEET_DOCS, list_ops=FLEET_LIST_OPS, seed=0,
                     n_shards=4):
    """The config-5 fleet on a 4-shard ShardedGeneralDocSet against a
    plain GeneralDocSet on device 0."""
    from automerge_tpu.common import ROOT_ID
    from automerge_tpu.device.workloads import gen_mixed_docs
    from automerge_tpu.parallel.mesh import make_mesh
    from automerge_tpu.sync.chaos import canonical, doc_set_view
    from automerge_tpu.sync.general_doc_set import GeneralDocSet
    from automerge_tpu.sync.sharded import ShardedGeneralDocSet

    per_doc = gen_mixed_docs(n_docs, list_ops)
    batch = {f'doc{d}': per_doc[d] for d in range(n_docs)}
    ids = list(batch)
    devs = jax.devices()[:n_shards]

    def one_pass():
        plain = GeneralDocSet(n_docs, device=devs[0])
        fleet = ShardedGeneralDocSet(n_docs, n_shards=n_shards,
                                     mesh=make_mesh(n_shards))
        fleet.apply_changes_batch(batch)
        plain.apply_changes_batch(batch)
        check(canonical(doc_set_view(fleet)) ==
              canonical(doc_set_view(plain)),
              'sharded view differs from the one-chip store')
        check(all(fleet.digest_of_id(d) == plain.digest_of_id(d)
                  for d in ids), 'sharded digests differ')
        return fleet, plain

    fleet, plain = run_twice('four_chips', one_pass)
    per_shard = [len(s.doc_ids) for s in fleet.shards]
    check(all(per_shard), f'docs per shard: {per_shard}')
    homes = []
    for i, shard in enumerate(fleet.shards):
        on = plane_devices(shard)
        check(on == {fleet.devices[i]},
              f'shard {i} planes on {on}, want {fleet.devices[i]}')
        homes.append(fleet.devices[i])
    check(len(set(homes)) == n_shards,
          f'shards share devices: {homes}')
    check(plane_devices(plain) == {devs[0]},
          'one-chip store left device 0')

    doc_id = f'doc{doc_sample(n_docs, 1, seed)[0]}'
    src = fleet.shard_of(doc_id)
    dst = (src + 1) % n_shards
    check(fleet.migrate_doc(doc_id, dst), f'{doc_id} did not migrate')
    check(fleet.shard_of(doc_id) == dst, 'placement did not flip')
    check(fleet.digest_of_id(doc_id) == plain.digest_of_id(doc_id),
          'digest parity lost in migration')
    late = [{'actor': f'late-{seed}', 'seq': 1,
             'deps': fleet.clock_of_id(doc_id),
             'ops': [{'action': 'set', 'obj': ROOT_ID, 'key': 'late',
                      'value': seed}]}]
    fleet.apply_changes(doc_id, late)
    plain.apply_changes(doc_id, late)
    check(fleet.materialize(doc_id) == plain.materialize(doc_id),
          'post-migration write diverged')
    check(fleet.digest_of_id(doc_id) == plain.digest_of_id(doc_id),
          'post-migration digests differ')
    for i, shard in enumerate(fleet.shards):
        check(plane_devices(shard) == {fleet.devices[i]},
              f'shard {i} planes moved after migration')
    report(f'setup[four_chips]: {n_docs} docs over {n_shards} shards '
           f'{per_shard} on {[str(d) for d in homes]}; views and '
           f'digests equal the one-chip store; {doc_id} migrated '
           f'{src}->{dst} with digest parity')


# -- main ---------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--four-chips', action='store_true',
                    help='run only the four-chip sharded-fleet phase')
    ap.add_argument('--seed', type=int, default=0,
                    help='seed of the sampled docs and typed text')
    args = ap.parse_args(argv)

    devs = preflight(args.four_chips)
    from automerge_tpu.utils.metrics import metrics
    if args.four_chips:
        phase_four_chips(seed=args.seed)
    else:
        phase_kernels(seed=args.seed)
        phase_fleet(seed=args.seed)
        phase_typing(seed=args.seed)
        phase_dense(seed=args.seed)
    report(f'setup[compiles]: device_compiles_total='
           f'{metrics.counters.get("device_compiles_total", 0)} '
           f'device_retraces_total='
           f'{metrics.counters.get("device_retraces_total", 0)}')
    for d in devs:
        stats = d.memory_stats() or {}
        report(f'setup[memory]: {d} peak_bytes_in_use='
               f'{stats.get("peak_bytes_in_use", "not reported")}')
    print(json.dumps({'ok': True, 'device': {
        'platform': devs[0].platform, 'kind': devs[0].device_kind,
        'count': len(devs)}}), flush=True)


if __name__ == '__main__':
    main()
