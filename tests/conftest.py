"""Test configuration.

Unit tests run JAX on the CPU with 8 virtual devices, so the
multi-device paths run without chips. The suite never takes a TPU: a
chip belongs to one process at a time, and ``python chip_smoke.py``
is the on-chip check. ``tests/test_tpu_compile.py`` compiles the
Pallas kernels for a described v5e without one.

Every test runs under a wall-clock limit (:data:`TEST_TIME_LIMIT_S`):
a test that hangs fails with its stack, and the run goes on.
"""
import contextlib
import os
import signal
import sys

import pytest

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

# one limit for every lane (the full run loads this file too): over 3x
# the slowest tier-1 test and well above the slowest slow-marked one.
# pyproject's faulthandler_timeout sits just below it, so a hang also
# dumps every thread's stack.
TEST_TIME_LIMIT_S = 300


class TimeLimitExceeded(SystemExit):
    """A test outlived its limit. A ``SystemExit``: no ``except
    Exception`` swallows it, and it is one of the two exceptions an
    asyncio callback or task passes on, so a test hung in a running
    event loop fails too. pytest reports it as the test's failure."""


@contextlib.contextmanager
def time_limit(seconds):
    """Raise :class:`TimeLimitExceeded` in the main thread once
    ``seconds`` have passed, and again every second after that (an
    event loop callback that catches the first one cannot hold the
    test). Disarmed on exit, the previous SIGALRM handler restored."""
    def expire(signum, frame):
        raise TimeLimitExceeded(f'exceeded the {seconds} s time limit')

    handler = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, seconds, 1.0)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, handler)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    # xdist workers run tests in their main thread, where signals land
    with time_limit(TEST_TIME_LIMIT_S):
        yield
