"""The per-test time limit of ``tests/conftest.py``: a hang fails its
own test, and the alarm does not outlive the block that armed it."""

import signal
import time

import pytest

from conftest import TimeLimitExceeded, time_limit


def test_time_limit_fails_a_hung_call_and_disarms():
    handler = signal.getsignal(signal.SIGALRM)
    start = time.monotonic()
    with pytest.raises(TimeLimitExceeded):
        with time_limit(1):
            time.sleep(5)
    elapsed = time.monotonic() - start
    assert 0.9 <= elapsed < 2, elapsed
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is handler
