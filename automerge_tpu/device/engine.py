"""Batched document-store engine: many documents, one device program.

This is the TPU realization of the north star: `applyChanges` vmap'd
across every document in a DocSet. The host packs change batches into
dense arrays (:mod:`.packing`), one jitted program resolves every field of
every document (:mod:`.merge`), and the winners map back to JSON values.

For workloads the oracle backend walks op-by-op (O(total ops) of Python/JS
dict churn), this path does two segment reductions and a couple of gathers
over the whole batch — the per-op cost is a few HBM-bandwidth-bound array
lanes, which is what makes million-op merges per chip feasible.
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..config import DEFAULT as DEFAULT_OPTIONS
from ..utils.metrics import metrics
from . import merge as merge_kernel
from . import packing


def as_options(options=None, kernel=None):
    """Normalize (options, legacy kernel kwarg) into one Options."""
    if options is None:
        options = DEFAULT_OPTIONS
    if kernel is not None and kernel != options.kernel:
        options = options.with_(kernel=kernel)
    return options


def _pallas_wins(n_docs, n_ops, n_actors):
    """Whether the Pallas kernel should run for this shape.

    Feasibility: the kernel keeps one DOC_BLOCK of every plane resident
    — 5 int32 inputs + 3 outputs + 1 scratch + the [.., n_actors]
    clock, i.e. DOC_BLOCK * n_pad * (9 + n_actors) * 4 bytes — and
    unrolls ~3 * n_tiles^2 tile-pair bodies; past those bounds Mosaic
    either fails allocation or compiles pathologically.

    Profitability (measured on v5e, amortized per-dispatch, r3):
    pallas wins on LARGE doc batches with few op tiles — 2.26x at
    [10240 x 128 x 8] (46.5 vs 105.2 ms, 28M vs 12M ops/s), 1.5x at
    [1024 x 128 x 8] — and loses ~0.85x once the tile-pair unroll grows
    ([256 x 512 x 16], [8 x 1024 x 8]) or the doc grid is too small to
    fill the chip. Hence: tiles <= 2 AND docs >= 256.
    """
    from . import pallas_merge as pm
    n_pad = pm._round_up(max(n_ops, pm.OPS_TILE), pm.OPS_TILE)
    vmem_bytes = pm.DOC_BLOCK * n_pad * (9 + n_actors) * 4
    n_tiles = n_pad // pm.OPS_TILE
    return (vmem_bytes <= 8 * 1024 * 1024 and n_tiles <= 2
            and n_docs >= 256)


def pick_resolve_kernel(kernel, shape):
    """Select the field-resolution kernel for a batch of ``shape`` =
    (docs, ops, actors).

    'xla'    — segment-reduction path (merge.py), runs everywhere.
    'pallas' — hand-scheduled VMEM-resident kernel (pallas_merge.py);
               requires a TPU backend (Mosaic).
    'auto'   — on TPU, pallas for the shapes where the measured A/B
               says it wins (large doc batches, few op tiles — see
               `_pallas_wins`), xla otherwise and on non-TPU backends.
    """
    if kernel == 'auto':
        kernel = 'pallas' if (jax.default_backend() == 'tpu'
                              and _pallas_wins(*shape)) else 'xla'
    if kernel == 'pallas':
        from . import pallas_merge
        return pallas_merge.resolve_assignments_batch_pallas
    return merge_kernel.resolve_assignments_batch


class DocStore:
    """A batch of documents resolved on device.

    Round-1 scope: flat map documents (the DocSet batch-merge workload,
    BASELINE config 5). Nested object graphs and sequences run through the
    oracle backend or the sequence kernel respectively.
    """

    def __init__(self):
        self.resolved = []    # per doc: {(obj, key): {'value','action','conflicts'}}

    @classmethod
    def from_changes(cls, docs_changes):
        store = cls()
        store.resolved = batch_merge_docs(docs_changes)
        return store

    def materialize(self, doc_index, obj_id):
        """Plain {key: value} for one (flat) object of one document."""
        return {key: field['value']
                for (obj, key), field in self.resolved[doc_index].items()
                if obj == obj_id and field['action'] == 'set'}


def unpack_resolved(packed, surviving_row, winner_row):
    """Turn one document's kernel outputs back into JSON field state.

    Shared by the single-chip and sharded engines so the two can never
    diverge. O(N + S) per document: survivors are grouped by segment in one
    pass instead of rescanning the op array per field.
    """
    n_real = len(packed.op_meta)
    by_seg = {}
    for j in np.flatnonzero(surviving_row[:n_real]):
        by_seg.setdefault(int(packed.seg_id[j]), []).append(j)

    doc_fields = {}
    for s, field in enumerate(packed.segments):
        w = winner_row[s]
        if w < 0 or not surviving_row[w]:
            doc_fields[field] = {'action': 'remove', 'value': None,
                                 'conflicts': None}
            continue
        action, value = packed.op_meta[w]
        conflicts = None
        survivors = by_seg.get(s, [])
        if len(survivors) > 1:
            losers = sorted((j for j in survivors if j != w),
                            key=lambda j: packed.actor_names[packed.actor[j]],
                            reverse=True)
            conflicts = {packed.actor_names[packed.actor[j]]: packed.op_meta[j][1]
                         for j in losers}
        doc_fields[field] = {'action': 'set', 'value': value,
                             'conflicts': conflicts, 'link': action == 'link'}
    return doc_fields


def batch_merge_docs(docs_changes, return_timing=False, kernel=None,
                     options=None):
    """Merge a batch of change lists, one per document, on device.

    Args:
      docs_changes: list over documents; each entry is a list of changes
        (causally self-contained per document).
      return_timing: also return a dict of phase timings.
      options: :class:`~automerge_tpu.config.Options` (kernel choice and
        padding policy); `kernel` remains as a shorthand override.

    Returns:
      per-doc dict {(obj, key): {'action': 'set'|'remove', 'value', 'conflicts'}}
      matching exactly what the oracle's field state would be.
    """
    import time
    opts = as_options(options, kernel)
    t0 = time.perf_counter()
    packed = [packing.pack_assignments(changes) for changes in docs_changes]
    seg_id, actor, seq, clock, is_del, valid, n_pad = packing.pad_and_stack(
        packed, n_ops=opts.op_pad, n_actors=opts.actor_pad,
        index_dtype=opts.index_dtype, clock_dtype=opts.clock_dtype)
    n_segs = opts.pad_segments(max((p.n_segments for p in packed), default=1))
    t1 = time.perf_counter()

    resolve = pick_resolve_kernel(
        opts.kernel, (seg_id.shape[0], seg_id.shape[1], clock.shape[2]))
    from . import profiler as _profiler
    _profiler.note_dispatch(
        'engine.resolve',
        (resolve.__name__, seg_id.shape, clock.shape, str(seg_id.dtype),
         str(clock.dtype), n_segs),
        rows=seg_id.shape[0])
    out = resolve(
        jnp.asarray(seg_id), jnp.asarray(actor), jnp.asarray(seq),
        jnp.asarray(clock), jnp.asarray(is_del), jnp.asarray(valid),
        num_segments=n_segs)
    surviving = np.asarray(out['surviving'])
    winner = np.asarray(out['winner'])
    t2 = time.perf_counter()

    results = [unpack_resolved(p, surviving[i], winner[i])
               for i, p in enumerate(packed)]
    t3 = time.perf_counter()

    real_ops = int(valid.sum())
    metrics.bump('device_batches')
    metrics.bump('device_ops', real_ops)
    metrics.set_gauge('device_batch_occupancy',
                      real_ops / max(valid.size, 1))
    if metrics.active:
        metrics.emit('device_batch', docs=len(packed), ops=real_ops,
                     padded_ops=int(valid.size), pack_s=t1 - t0,
                     device_s=t2 - t1, unpack_s=t3 - t2)

    if return_timing:
        return results, {'pack': t1 - t0, 'device': t2 - t1, 'unpack': t3 - t2}
    return results
