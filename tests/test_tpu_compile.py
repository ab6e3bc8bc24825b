"""Ahead-of-time compiles of the Pallas kernels for a described v5e.

The TPU compiler is installed even where no chip is attached: it
compiles for a topology that is only described, and refuses what the
chip would refuse (block shapes off the (8, 128) tiling, lowering
limits, VMEM overruns) — what interpret-mode tests cannot see. Nothing
runs; each test asserts the kernel made it into the program as a
``tpu_custom_call``.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every worker
of a parallel run imports this file. The persistent compile cache is
off around these compiles (a described chip cannot read entries back).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', prev)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *specs):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize('K,m,e_pad', [
    (64, 128, 8),      # one lane tile
    (256, 512, 64),    # the auto-dispatch bound (_PALLAS_MAX_M)
    (5, 120, 8),       # ragged job count and node axis
])
def test_edit_stream_pallas_compiles(one_chip, K, m, e_pad):
    from automerge_tpu.device import pallas_view
    assert m <= pallas_view._PALLAS_MAX_M
    plane = ((K, m), jnp.int32)
    text = _compile_text(
        lambda pv, nv, pi, ni, t: pallas_view.edit_stream_pallas(
            pv, nv, pi, ni, t, e_pad=e_pad),
        one_chip, plane, plane, plane, plane,
        ((K, (m + 7) // 8), jnp.uint8))
    assert 'tpu_custom_call' in text


def test_resolve_pallas_compiles_at_config5(one_chip):
    from automerge_tpu.device.pallas_merge import (
        resolve_assignments_batch_pallas)
    D, N, A = 10240, 128, 8
    i32 = ((D, N), jnp.int32)
    flag = ((D, N), jnp.bool_)
    text = _compile_text(
        lambda *a: resolve_assignments_batch_pallas(*a, num_segments=N),
        one_chip, i32, i32, i32, ((D, N, A), jnp.int32), flag, flag)
    assert 'tpu_custom_call' in text


def test_rga_pallas_compiles_at_its_bound(one_chip):
    """The shape the bench A/B runs it at (2048 jobs x 128 nodes), the
    widest node axis the kernel compiles at for a TPU."""
    from automerge_tpu.device import pallas_sequence
    K, m = 2048, pallas_sequence.MAX_M
    i32 = ((K, m), jnp.int32)
    flag = ((K, m), jnp.bool_)
    text = _compile_text(pallas_sequence.rga_order_batch_pallas,
                         one_chip, i32, i32, i32, flag, flag)
    assert 'tpu_custom_call' in text


def test_rga_pallas_refuses_wider_trees_on_tpu():
    from automerge_tpu.device import pallas_sequence
    m = pallas_sequence.MAX_M + 1
    z = jnp.zeros((1, m), jnp.int32)
    with pytest.raises(ValueError, match='compiles for m'):
        pallas_sequence.rga_order_batch_pallas(z, z, z, z > 0, z > 0)
