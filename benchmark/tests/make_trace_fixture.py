"""Record the small trace that ``test_trace_reduce.py`` checks.

    python3 benchmark/tests/make_trace_fixture.py OUT.xplane.pb

on a machine with a TPU: a ``bench.window`` of three ``bench.apply``
spans, each running one jitted program, each after 20 ms of
``bench.wait``, and 20 ms more of ``bench.wait`` at the end.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out):
    step = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation('bench.window'):
            for _ in range(3):
                with jax.profiler.TraceAnnotation('bench.wait'):
                    time.sleep(0.02)
                with jax.profiler.TraceAnnotation('bench.apply'):
                    step(x).block_until_ready()
            with jax.profiler.TraceAnnotation('bench.wait'):
                time.sleep(0.02)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tmp, '**', '*.xplane.pb'),
                          recursive=True)
        shutil.copy(found[0], out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == '__main__':
    main(sys.argv[1])
