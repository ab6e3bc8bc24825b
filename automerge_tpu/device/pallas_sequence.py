"""Pallas TPU kernel for RGA sequence ordering (the flagship kernel).

Hand-scheduled counterpart of :mod:`.sequence` — the skip-list
replacement of SURVEY §5, scheduled for the MXU the way
:mod:`.pallas_merge` schedules field resolution.

The XLA variants pay their pointer-doubling rounds in HBM: the gather
path issues ~2·log2(m) dependent cross-lane gathers per batch, and the
one-hot MXU path (`sequence._rga_order_mxu`) materializes a [K, m, m]
one-hot plane in HBM EVERY round. This kernel keeps a block of jobs'
node planes resident in VMEM and runs the whole pipeline — tree
threading, ancestor climb, list ranking, visibility scan — as
straight-line Mosaic code:

* every gather/scatter is a **one-hot matmul on the MXU** over [m, m]
  f32 tiles (exact: all values < 2^24). The two doubled quantities of
  the ranking loop ride one [m, 2] right-hand side, so each doubling
  round is ONE dot;
* the child-priority sort stays in XLA (measured free — sorting 128-lane
  segments is nothing next to the doubling rounds); the kernel takes the
  sorted permutation `order` and sorted parents `p_sorted` as inputs;
* the visibility prefix-sum is log2(m) shifted adds on the VPU.

Chain ends terminate with SELF-LOOPS instead of the XLA path's (n+1)-slot
terminator, which changes nothing for valid on-chain nodes (tree_pos is
anchored to the head's distance) — vis_index/length are bit-identical to
`vmap(_rga_order)`; tree_pos of PADDING rows differs and is not emitted.

Layout: node axis padded to one 128-lane tile; jobs ride the grid in
blocks of 8, walked by a ``fori_loop`` inside the kernel (unrolled, the
8-job body took 15-25 s to compile for v5e; looped, ~5 s). On a TPU
the kernel is bounded to m <= 128 (``MAX_M``): its log-shifted
concatenates do not lower past the first lane tile (Mosaic: "Input
offsets outside of the first tile" at m = 512), and a variant with the
shifts as matmuls did not finish compiling at m = 256 within 300 s.
Wider trees ride the XLA variants. Interpret mode (CPU tests) takes
any m.

Why the one-hot schedules STOP at m ~= 512 (measured, r5): every
doubling round must materialize a [*, m, m] one-hot — O(m^2) VPU
compares — before the MXU sees it. On a v5e, ONE such build at
[64, 4096] costs ~45 ms while the ENTIRE gather-variant pipeline
(26 rounds, all phases) runs in ~123 ms; at [8, 16384] one build is
~31 ms vs ~66 ms for the whole gather pipeline. Any one-hot schedule
— Pallas-tiled or XLA — is therefore >= ~10x WORSE than the gather
variant for m >= ~2048, and the crossover sits near the MXU
variant's m <= 512 bound. Scalar in-kernel pointer chasing is no
rescue either: ~m * 2log2(m) dependent scalar loads put a 180k-node
tree at best near the gather variant's time, with none of its
batching. The 3-way dispatch in `sequence._rga_order_batched` (and
the A/B the bench captures) encodes exactly this measured boundary;
large single trees ride the gather variant by design, not omission.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_merge import _round_up
from .sequence import _ceil_log2

JOB_BLOCK = 8
NODE_TILE = 128
# widest node axis the kernel compiles at for a TPU (module docstring)
MAX_M = NODE_TILE


def _make_kernel(m, rounds):
    f32 = jnp.float32
    i32 = jnp.int32

    def dot(a, b):
        return jax.lax.dot_general(
            a, b, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32)

    def kernel(order_ref, psort_ref, parent_ref, vis_ref, valid_ref,
               visidx_ref, len_ref):
        iota_r = jax.lax.broadcasted_iota(i32, (m, 1), 0)[:, 0]   # [m]
        iota_col = jax.lax.broadcasted_iota(i32, (m, m), 1)       # [m, m]
        def job(j, carry):
            row = pl.ds(j, 1)
            order = order_ref[row, :][0]
            p_sorted = psort_ref[row, :][0]
            parent = parent_ref[row, :][0]
            visible = vis_ref[row, :][0] != 0
            valid = valid_ref[row, :][0] != 0

            # ---- thread the tree from the sorted order ----------------
            # (1-D bool concats hit Mosaic vreg-cast limits: shift in i32)
            seg_start = jnp.concatenate(
                [jnp.ones((1,), i32),
                 (p_sorted[1:] != p_sorted[:-1]).astype(i32)])
            # first_child[p] = order at the first sorted slot under p
            A = (p_sorted[None, :] == iota_col.T).astype(f32) \
                * seg_start.astype(f32)[None, :]            # [p, s]
            fc_val = dot(A, order.astype(f32)[:, None])[:, 0]
            fc_has = dot(A, jnp.ones((m, 1), f32))[:, 0] > 0
            first_child = jnp.where(fc_has, fc_val.astype(i32), -1)
            # next_sibling via the inverse permutation
            same_next = jnp.concatenate(
                [(p_sorted[1:] == p_sorted[:-1]).astype(i32),
                 jnp.zeros((1,), i32)])
            ns_sorted = jnp.where(
                same_next != 0,
                jnp.concatenate([order[1:], -jnp.ones((1,), i32)]), -1)
            O = (order[:, None] == iota_col).astype(f32)    # [s, node]
            next_sibling = dot(O.T, ns_sorted.astype(f32)[:, None])[:, 0] \
                .astype(i32)
            next_sibling = jnp.where(iota_r == 0, -1, next_sibling)
            has_sib = next_sibling >= 0

            # ---- climb to the nearest ancestor with a sibling ---------
            climb = jnp.where(has_sib | (iota_r == 0), iota_r, parent) \
                .astype(f32)
            for _ in range(rounds):
                G = (climb.astype(i32)[:, None] == iota_col).astype(f32)
                climb = dot(G, climb[:, None])[:, 0]
            G = (climb.astype(i32)[:, None] == iota_col).astype(f32)
            pair = jnp.stack([next_sibling.astype(f32),
                              has_sib.astype(f32)], axis=1)   # [m, 2]
            up2 = dot(G, pair)
            up = jnp.where(up2[:, 1] > 0, up2[:, 0].astype(i32), -1)
            succ = jnp.where(first_child >= 0, first_child, up)
            succ = jnp.where(valid, succ, -1)

            # ---- list-rank the successor chain (self-loop ends) -------
            nxt = jnp.where(succ >= 0, succ, iota_r)
            dist = (succ >= 0).astype(f32)
            nxt_f = nxt.astype(f32)
            for _ in range(rounds):
                G = (nxt_f.astype(i32)[:, None] == iota_col).astype(f32)
                g2 = dot(G, jnp.stack([dist, nxt_f], axis=1))
                dist = dist + g2[:, 0]
                nxt_f = g2[:, 1]
            tree_pos = (dist[0] - dist).astype(i32)

            # ---- visibility scan --------------------------------------
            on_chain = valid & (tree_pos > 0)
            # bool minor-dim inserts are unsupported in Mosaic: build the
            # mask product in f32
            N = (tree_pos[:, None] == iota_col).astype(f32) \
                * on_chain.astype(f32)[:, None]             # [node, pos]
            vis_ordered = dot(N.T, (visible & on_chain)
                              .astype(f32)[:, None])[:, 0]
            run = vis_ordered
            for k in range(rounds):                     # inclusive scan
                s = 1 << k
                if s >= m:
                    break
                run = run + jnp.concatenate(
                    [jnp.zeros((s,), f32), run[:m - s]])
            vis_rank = run - vis_ordered                 # exclusive
            vis_index = dot(N, vis_rank[:, None])[:, 0].astype(i32)
            vis_index = jnp.where(visible & on_chain, vis_index, -1)
            visidx_ref[row, :] = vis_index[None, :]
            len_ref[row, :] = jnp.broadcast_to(
                jnp.sum((visible & on_chain).astype(i32)), (1, m))
            return carry

        jax.lax.fori_loop(0, JOB_BLOCK, job, 0)

    return kernel


def _rga_pallas_padded(order, p_sorted, parent, visible, valid,
                       interpret=False):
    """Core pallas_call on pre-padded [K(=k*8), m(=t*128)] inputs."""
    K, m = order.shape
    rounds = _ceil_log2(m) + 1
    spec = pl.BlockSpec((JOB_BLOCK, m), lambda d: (d, 0),
                        memory_space=pltpu.VMEM)
    visidx, length = pl.pallas_call(
        _make_kernel(m, rounds),
        grid=(K // JOB_BLOCK,),
        in_specs=[spec] * 5,
        out_specs=[spec] * 2,
        out_shape=[jax.ShapeDtypeStruct((K, m), jnp.int32)] * 2,
        interpret=interpret,
    )(order, p_sorted, parent, visible, valid)
    return visidx, length[:, 0]


@partial(jax.jit, static_argnames=('interpret',))
def rga_order_batch_pallas(parent, elem, actor, visible, valid,
                           interpret=False):
    """Batched RGA ordering with the doubling pipeline in one Pallas
    kernel. Returns {'vis_index', 'length'} — bit-identical to the XLA
    variants for valid nodes (differentially tested)."""
    K, m = parent.shape
    K_pad = _round_up(max(K, 1), JOB_BLOCK)
    m_pad = _round_up(max(m, 2), NODE_TILE)
    if m_pad > MAX_M and not interpret:
        raise ValueError(f'rga_order_batch_pallas compiles for m <= '
                         f'{MAX_M} on a TPU, got m={m}')

    def pad(a, fill):
        out = jnp.full((K_pad, m_pad), fill, jnp.int32)
        return out.at[:K, :m].set(a.astype(jnp.int32))

    visible = visible.astype(bool)
    valid = valid.astype(bool)
    idx = jnp.arange(m, dtype=jnp.int32)[None, :]
    # child-priority sort in XLA (free next to the doubling rounds);
    # head and padding bucket together at parent m_pad
    parent_adj = jnp.where(valid & (idx != 0), parent, m_pad)
    parent_adj = jnp.concatenate(
        [parent_adj,
         jnp.full((K, m_pad - m), m_pad, jnp.int32)], axis=1)
    parent_adj = jnp.concatenate(
        [parent_adj, jnp.full((K_pad - K, m_pad), m_pad, jnp.int32)])
    order = jax.vmap(lambda a, e, p: jnp.lexsort((-a, -e, p)))(
        pad(actor, 0), pad(elem, 0), parent_adj)
    p_sorted = jnp.take_along_axis(parent_adj, order, axis=1)
    out_vi, out_len = _rga_pallas_padded(
        order.astype(jnp.int32), p_sorted.astype(jnp.int32),
        pad(parent, 0), pad(visible, 0), pad(valid, 0),
        interpret=interpret)
    return {'vis_index': out_vi[:K, :m], 'length': out_len[:K]}
