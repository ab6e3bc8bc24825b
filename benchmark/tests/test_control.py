"""The control (the reference with a stale read in the program's place)
comes out as not correct in every cell, and the stale read is the only
thing that fails it: the reference with the read made fresh passes."""

import pytest

import control
from conftest import CELLS


@pytest.mark.parametrize('cell', CELLS)
def test_control_is_not_correct(tiny_root, cell):
    out = control.run(cell, 5_000_000_029, 1.0, 0.0, root=tiny_root,
                      require_tpu=False)
    assert not out['correct'], out['checks']
    wrong = [c['value'] for name, c in out['checks'].items()
             if name.endswith('_wrong')]
    assert wrong and wrong[0] > 0


def test_fresh_reference_is_correct(tiny_root, monkeypatch):
    def fresh(doc, changes):
        for c in changes:
            doc.apply(c)
        return doc.plain()
    monkeypatch.setattr(control, '_stale_apply', fresh)
    for cell in CELLS:
        out = control.run(cell, 5_000_000_029, 1.0, 0.0, root=tiny_root,
                          require_tpu=False)
        assert out['correct'], (cell, out['checks'])
