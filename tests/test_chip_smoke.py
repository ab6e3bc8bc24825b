"""``chip_smoke.py`` without a chip: it refuses to run on the CPU, and
its phases pass against their references at toy sizes (Pallas in
interpret mode) — so an API drift breaks here, before a chip run."""

import os
import subprocess
import sys

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'chip_smoke.py')],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_fleet_phase():
    chip_smoke.phase_fleet(n_docs=64, list_ops=8, sample=8)


def test_typing_phase(monkeypatch):
    """The patch-read checks, with the TPU's auto pick emulated."""
    from automerge_tpu.device import general, pallas_view, profiler
    monkeypatch.setattr(general, '_EDIT_STREAM', True)
    monkeypatch.setattr(pallas_view, '_INTERPRET', True)
    monkeypatch.setattr(pallas_view, '_use_pallas',
                        lambda m: m <= pallas_view._PALLAS_MAX_M)
    profiler.reset()
    chip_smoke.phase_typing(long_chars=1000, short_chars=100, ticks=4)


def test_four_chip_phase():
    chip_smoke.phase_four_chips(n_docs=64, list_ops=8)


def test_kernel_phase():
    chip_smoke.phase_kernels(edit_shapes=((5, 64, 8), (3, 200, 16)),
                             rga_shape=(8, 128, 66), interpret=True)
