"""Fused patch-read kernel: winner/visibility diff -> pre-ordered,
delta-sized edit buffers, in ONE device dispatch.

The read side of the tick used to undo the apply side's batching:
``GeneralPatch._ensure`` fetched the FULL [K, m] visibility/order
planes (O(doc) bytes per tick for a 1-op edit) and then re-derived the
edit order on host — per dirty object, an argsort over the prior
indexes for removes and over the new indexes for inserts/sets. This
module is the device twin of the host read trio (the winner-gated edit
classification of ``winner_select``, the visible-order walk of
``visible_walk``, and the order gather): one dispatch classifies every
node of every dirty object as remove/insert/set, ranks each class in
document order with a prefix sum (no sort — vis indexes are already
dense ranks), and compacts the results into ``[K, e_pad]`` buffers
where ``e_pad`` is bounded by the tick's RESOLVED ROW COUNT, never the
tree size. ``GeneralPatch._ensure`` then reads one pre-ordered,
delta-sized buffer: a 1-op append to a 100k-element text fetches a few
hundred bytes instead of half a megabyte, and the host argsorts
disappear.

Two implementations, byte-identical by construction and pinned against
each other in CI:

* :func:`edit_stream` — the ``jax.lax`` fallback (scatter + cumsum +
  gather), the production path on CPU and for large planes;
* :func:`edit_stream_pallas` — the hand-fused Pallas variant next to
  :mod:`.pallas_sequence`: each job's planes stay resident in VMEM and
  every scatter/gather (and the prefix sum, as a triangular matrix)
  rides an MXU matmul. Like the RGA MXU variant, the one-hot build is
  O(m^2) VPU compares, so the auto-dispatch regime is m <= 512 (the
  one-hot crossover documented in pallas_sequence's module docstring;
  the v5e compile at m = 128 and m = 512 is pinned in
  tests/test_tpu_compile.py). Each job is one grid step over a
  ``(1, m)`` block of a ``[K, 1, m]`` array, so any K is legal. The
  CPU CI lane runs it in interpret mode.

The ``_FUSED_VIEW`` switch mirrors the native-path conventions:
``None`` = auto (Pallas on a real TPU backend inside the small-plane
regime, lax otherwise), ``False`` = lax always, ``True`` = REQUIRE the
Pallas kernel — raising instead of silently falling back (tests assert
this; ``_INTERPRET = True`` lets the forced path run on CPU).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# force switch: None = auto, False = lax only, True = require Pallas
# (raise instead of silently falling back)
_FUSED_VIEW = None
# run the Pallas kernel in interpret mode (CPU CI lane)
_INTERPRET = False
# auto-dispatch bound: the one-hot MXU schedule is only profitable for
# small planes (see pallas_sequence's one-hot crossover)
_PALLAS_MAX_M = 512

_W2_ELEM = 0x7FFF
_W2_VIS_SHIFT = 30
_W2_IDX_SHIFT = 15
_WIDE_IDX_MASK = (1 << 22) - 1
_WIDE_VIS_SHIFT = 22


def _unpack_touch(touched_u8, m):
    """MSB-first bit unpack of the host-built touched plane (one bit
    per (job, node) slot; np.packbits layout along the node axis)."""
    i = jnp.arange(m)
    return ((touched_u8[:, i >> 3] >> (7 - (i & 7))) & 1).astype(bool)


def _edit_core(pv, nv, pi, ni, touched, e_pad):
    """The lax edit-stream pipeline over [K, m] planes. Returns
    (rm_idx, ins_node, ins_idx, set_node, set_idx, cnts[K, 3]); the
    [K, e_pad] buffers are -1 padded, each class compacted in document
    order (removes ascending by PRIOR index — the host reads them
    reversed for the descending emit; inserts/sets ascending by NEW
    index)."""
    K, m = pv.shape
    rowi = jnp.arange(K, dtype=jnp.int32)[:, None]
    iota_l = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None, :],
                              (K, m))
    removed = pv & ~nv
    inserted = nv & ~pv
    setd = nv & pv & touched

    def compact(mask, key, vals):
        # ranks via presence-grid prefix sum: `key` is a dense
        # per-object rank (a vis index), unique among mask rows — no
        # sort needed, one scatter + cumsum + gather
        key_c = jnp.minimum(jnp.maximum(key, 0), m - 1)
        grid = jnp.zeros((K, m), bool).at[
            rowi, jnp.where(mask, key_c, 0)].max(mask, mode='drop')
        rank_g = (jnp.cumsum(grid, axis=1) - grid).astype(jnp.int32)
        rank = jnp.take_along_axis(rank_g, key_c, axis=1)
        tgt = jnp.where(mask, rank, e_pad)
        outs = tuple(
            jnp.full((K, e_pad), -1, jnp.int32).at[rowi, tgt].set(
                v.astype(jnp.int32), mode='drop') for v in vals)
        return outs, jnp.sum(mask, axis=1, dtype=jnp.int32)

    (rm_idx,), rm_cnt = compact(removed, pi, (pi,))
    (ins_node, ins_idx), ins_cnt = compact(inserted, ni, (iota_l, ni))
    (set_node, set_idx), set_cnt = compact(setd, ni, (iota_l, ni))
    cnts = jnp.stack([rm_cnt, ins_cnt, set_cnt], axis=1)
    return rm_idx, ins_node, ins_idx, set_node, set_idx, cnts


@partial(jax.jit, static_argnames=('e_pad',))
def edit_stream(pv, nv, pi, ni, touched_u8, *, e_pad):
    """lax edit stream over unpacked planes (cols vis format)."""
    m = pv.shape[1]
    return _edit_core(pv.astype(bool), nv.astype(bool),
                      pi.astype(jnp.int32), ni.astype(jnp.int32),
                      _unpack_touch(touched_u8, m), e_pad)


@partial(jax.jit, static_argnames=('e_pad',))
def edit_stream_packed(vis_packed, touched_u8, *, e_pad):
    """lax edit stream over the packed apply's vis word plane
    (prior_vis<<31 | visible<<30 | (prior_idx+1)<<15 | (new_idx+1))."""
    v = vis_packed
    m = v.shape[1]
    pv = ((v >> 31) & 1).astype(bool)
    nv = ((v >> _W2_VIS_SHIFT) & 1).astype(bool)
    pi = ((v >> _W2_IDX_SHIFT) & _W2_ELEM) - 1
    ni = (v & _W2_ELEM) - 1
    return _edit_core(pv, nv, pi, ni, _unpack_touch(touched_u8, m),
                      e_pad)


@partial(jax.jit, static_argnames=('e_pad',))
def edit_stream_wide(vis_prior, vis_new, touched_u8, *, e_pad):
    """lax edit stream over the wide apply's two vis word planes
    (``visible << 22 | (idx + 1)`` each)."""
    m = vis_prior.shape[1]
    pv = ((vis_prior >> _WIDE_VIS_SHIFT) & 1).astype(bool)
    nv = ((vis_new >> _WIDE_VIS_SHIFT) & 1).astype(bool)
    pi = (vis_prior & _WIDE_IDX_MASK) - 1
    ni = (vis_new & _WIDE_IDX_MASK) - 1
    return _edit_core(pv, nv, pi, ni, _unpack_touch(touched_u8, m),
                      e_pad)


# -- hand-fused Pallas variant ------------------------------------------------

def _make_edit_kernel(m, e_pad):
    from jax.experimental import pallas as pl  # noqa: F401
    f32 = jnp.float32
    i32 = jnp.int32

    def dot(a, b):
        return jax.lax.dot_general(
            a, b, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32)

    def kernel(pv_ref, nv_ref, pi_ref, ni_ref, tch_ref,
               rm_ref, insn_ref, insi_ref, setn_ref, seti_ref,
               cnt_ref):
        iota_m = jax.lax.broadcasted_iota(i32, (m, m), 1)      # [m, m]
        # strictly-lower-triangular ones: `before @ x` is the exclusive
        # prefix sum of x on the MXU (lane-shifted concatenates past the
        # first 128-lane tile do not lower in Mosaic)
        before = (iota_m < jax.lax.broadcasted_iota(i32, (m, m), 0)) \
            .astype(f32)
        iota_e = jax.lax.broadcasted_iota(i32, (m, e_pad), 1)  # [m, e]
        pv = pv_ref[0, :] != 0
        nv = nv_ref[0, :] != 0
        pi = pi_ref[0, :]
        ni = ni_ref[0, :]
        touched = tch_ref[0, :] != 0
        removed = pv & ~nv
        inserted = nv & ~pv
        setd = nv & pv & touched
        iota_l = jax.lax.broadcasted_iota(i32, (m, 1), 0)[:, 0]

        def compact(mask, key, vals, out_refs):
            # presence grid via one-hot matmul (keys unique per mask
            # row, so the sum IS the presence bit), prefix sum as a
            # triangular matmul, rank gather + e-space scatter as two
            # more one-hot dots — the whole class pipeline stays on
            # the MXU/VPU, no sort anywhere
            key_c = jnp.minimum(jnp.maximum(key, 0), m - 1)
            G = (key_c[:, None] == iota_m).astype(f32) \
                * mask.astype(f32)[:, None]                 # [l, p]
            grid = dot(G.T, jnp.ones((m, 1), f32))           # [p, 1]
            rank_g = dot(before, grid)              # exclusive scan
            rank = dot(G, rank_g)[:, 0].astype(i32)
            E = (rank[:, None] == iota_e).astype(f32) \
                * mask.astype(f32)[:, None]                 # [l, e]
            present = dot(E.T, jnp.ones((m, 1), f32))[:, 0] > 0
            for v, ref in zip(vals, out_refs):
                got = dot(E.T, v.astype(f32)[:, None])[:, 0] \
                    .astype(i32)
                ref[0, :] = jnp.where(present, got, -1)
            return jnp.sum(mask.astype(i32))

        n_rm = compact(removed, pi, (pi,), (rm_ref,))
        n_in = compact(inserted, ni, (iota_l, ni),
                       (insn_ref, insi_ref))
        n_st = compact(setd, ni, (iota_l, ni), (setn_ref, seti_ref))
        # scalar element sets hit Mosaic limits: lay the three counts
        # out with iota selects instead
        iota_c = jax.lax.broadcasted_iota(i32, (e_pad, 1), 0)[:, 0]
        cnt_ref[0, :] = jnp.where(
            iota_c == 0, n_rm,
            jnp.where(iota_c == 1, n_in,
                      jnp.where(iota_c == 2, n_st, 0)))

    return kernel


@partial(jax.jit, static_argnames=('e_pad', 'interpret'))
def _edit_stream_pallas_core(pv, nv, pi, ni, touched, *, e_pad,
                             interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .pallas_merge import _round_up
    K, m = pv.shape
    m_pad = _round_up(max(m, 2), 128)
    e_out = _round_up(max(e_pad, 8), 128)

    def pad(a, fill):
        out = jnp.full((K, 1, m_pad), fill, jnp.int32)
        return out.at[:, 0, :m].set(a.astype(jnp.int32))

    spec_in = pl.BlockSpec((None, 1, m_pad), lambda d: (d, 0, 0),
                           memory_space=pltpu.VMEM)
    spec_out = pl.BlockSpec((None, 1, e_out), lambda d: (d, 0, 0),
                            memory_space=pltpu.VMEM)
    outs = pl.pallas_call(
        _make_edit_kernel(m_pad, e_out),
        grid=(K,),
        in_specs=[spec_in] * 5,
        out_specs=[spec_out] * 6,
        out_shape=[jax.ShapeDtypeStruct((K, 1, e_out), jnp.int32)] * 6,
        interpret=interpret,
    )(pad(pv, 0), pad(nv, 0), pad(pi, -1), pad(ni, -1),
      pad(touched, 0))
    rm, insn, insi, setn, seti, cnt = (o[:, 0] for o in outs)
    return (rm[:, :e_pad], insn[:, :e_pad], insi[:, :e_pad],
            setn[:, :e_pad], seti[:, :e_pad], cnt[:, :3])


@partial(jax.jit, static_argnames=('e_pad', 'interpret'))
def edit_stream_pallas(pv, nv, pi, ni, touched_u8, *, e_pad,
                       interpret=False):
    """Hand-fused Pallas edit stream — bit-identical to
    :func:`edit_stream` (differentially tested in the interpret-mode
    CI lane)."""
    m = pv.shape[1]
    return _edit_stream_pallas_core(
        pv.astype(jnp.int32), nv.astype(jnp.int32),
        pi.astype(jnp.int32), ni.astype(jnp.int32),
        _unpack_touch(touched_u8, m).astype(jnp.int32),
        e_pad=e_pad, interpret=interpret)


def _use_pallas(m):
    if _FUSED_VIEW is False:
        return False
    if _FUSED_VIEW is True:
        if not _INTERPRET and jax.default_backend() != 'tpu':
            raise RuntimeError(
                'Pallas fused view required (_FUSED_VIEW=True) but no '
                'TPU backend is available (set _INTERPRET=True for '
                'the CPU interpret lane)')
        return True
    return (jax.default_backend() == 'tpu' and m <= _PALLAS_MAX_M)


def dispatch_edit_stream(vis_fmt, planes, touched_u8, e_pad):
    """Dispatch the edit-stream kernel over one apply's vis planes
    (device-resident outputs of the fused apply) — the entry point
    ``GeneralPatch._ensure`` calls. Returns the device output tuple
    (fetch with one ``jax.device_get``). Each dispatch lands in the
    profiler's registry as ``view.edit_stream_pallas`` or
    ``view.edit_stream`` (the lax programs), so a run can tell which
    implementation served its patch reads."""
    from . import profiler as _profiler
    t_u8 = jnp.asarray(touched_u8)
    first = planes if vis_fmt == 'packed' else planes[0]
    K, m = (int(n) for n in np.shape(first))
    use_pallas = _use_pallas(m)
    _profiler.note_dispatch(
        'view.edit_stream_pallas' if use_pallas else 'view.edit_stream',
        (vis_fmt, K, m, e_pad), rows=K)
    if vis_fmt == 'packed':
        v = planes
        if use_pallas:
            pv = ((v >> 31) & 1)
            nv = ((v >> _W2_VIS_SHIFT) & 1)
            pi = ((v >> _W2_IDX_SHIFT) & _W2_ELEM) - 1
            ni = (v & _W2_ELEM) - 1
            return edit_stream_pallas(pv, nv, pi, ni, t_u8,
                                      e_pad=e_pad,
                                      interpret=_INTERPRET)
        return edit_stream_packed(v, t_u8, e_pad=e_pad)
    if vis_fmt == 'wide':
        vp, vn = planes
        if use_pallas:
            pv = (vp >> _WIDE_VIS_SHIFT) & 1
            nv = (vn >> _WIDE_VIS_SHIFT) & 1
            pi = (vp & _WIDE_IDX_MASK) - 1
            ni = (vn & _WIDE_IDX_MASK) - 1
            return edit_stream_pallas(pv, nv, pi, ni, t_u8,
                                      e_pad=e_pad,
                                      interpret=_INTERPRET)
        return edit_stream_wide(vp, vn, t_u8, e_pad=e_pad)
    pv, nv, pi, ni = planes
    if use_pallas:
        return edit_stream_pallas(pv, nv, pi, ni, t_u8, e_pad=e_pad,
                                  interpret=_INTERPRET)
    return edit_stream(pv, nv, pi, ni, t_u8, e_pad=e_pad)
