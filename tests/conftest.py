"""Test configuration.

Unit tests run JAX on the CPU with 8 virtual devices, so the
multi-device paths run without chips. The suite never takes a TPU: a
chip belongs to one process at a time, and ``python chip_smoke.py``
is the on-chip check. ``tests/test_tpu_compile.py`` compiles the
Pallas kernels for a described v5e without one.
"""
import os
import sys

flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (flags + ' --xla_force_host_platform_device_count=8').strip()
os.environ['JAX_PLATFORMS'] = 'cpu'

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from automerge_tpu.config import use_compile_cache  # noqa: E402

assert jax.devices()[0].platform == 'cpu'

# the suite is compile-dominated on the CPU: the persistent cache lets
# every process skip the compiles an earlier run already paid for
use_compile_cache()
