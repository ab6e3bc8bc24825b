"""The comparison that decides ``correct`` fails a broken timed path.

Each fault is planted in the served path once set-up has finished, under
a tiny run of the harness (the look for a chip skipped): a step that leaves the state
unchanged, half of a request's batch left out, and an answer altered
where it is produced. One chip holds each cell, so no exchange between
chips can be left out.
"""

import pytest

from conftest import CELLS, run_cell


def _half(items):
    return items[:len(items) - len(items) // 2]


def faulty_path(fault):
    import served

    class Broken(served.DocSetPath):
        def apply(self, doc_set, batch):
            if fault == 'unchanged':
                return
            if fault == 'half':
                batch = dict(_half(list(batch.items())))
            super().apply(doc_set, batch)

        def read(self, doc_set, doc_ids):
            views = super().read(doc_set, doc_ids)
            if fault == 'altered' and views:
                views = list(views)
                views[0] = dict(views[0], field0='altered')
            return views
    return Broken()


FAULTS = [(cell, fault) for cell in CELLS
          for fault in ('unchanged', 'half', 'altered')]


@pytest.mark.parametrize('cell,fault', FAULTS)
def test_fault_is_not_correct(tiny_root, cell, fault, monkeypatch):
    import harness
    build = harness.build_system

    def build_then_break(*args, **kwargs):
        system = build(*args, **kwargs)
        prepare = system.prepare

        def prepare_then_break(watch):
            prepare(watch)
            system.path = faulty_path(fault)
        system.prepare = prepare_then_break
        return system

    monkeypatch.setattr(harness, 'build_system', build_then_break)
    out = run_cell(tiny_root, cell)
    assert not out['correct'], (out['checks'], out['failed'])
