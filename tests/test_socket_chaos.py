"""Socket chaos lane: PR 13's seeded scenario schedules replayed
over REAL loopback sockets through the fault-injecting proxy, judged
byte-identical against the clean in-process oracle.

This is the transport's end-to-end trust argument: the same write
schedule, once through a clean in-process fabric and once through TCP
with latency, drop, duplication, mid-frame cuts and byte corruption —
the per-doc canonical views must match EXACTLY, with zero quarantines
and zero divergence. Delivery ORDER differs (TCP + asyncio schedule
it), but CRDT convergence makes the final state order-independent,
which is precisely the property under test.
"""

from automerge_tpu.fleetsim import build_schedule, run_oracle
from automerge_tpu.sync.chaos import (ChaosProxy,
                                      replay_schedule_over_sockets)
from automerge_tpu.utils.metrics import metrics

CHAOS = {'drop': 0.05, 'dup': 0.05, 'cut': 0.01, 'corrupt': 0.01}


def _assert_matches_oracle(scenario, seed):
    sched = build_schedule(scenario, seed=seed, scale='smoke')
    oracle = run_oracle(sched)
    out = replay_schedule_over_sockets(sched, chaos=CHAOS)
    assert out['quarantined'] == 0, 'sockets quarantined docs'
    assert out['diverged'] == 0, 'sockets recorded divergence'
    assert out['views'] == oracle, (
        f'{scenario}: socket replay is not byte-identical to the '
        f'in-process oracle')


class TestScheduleReplayOverSockets:
    def test_flash_crowd_matches_oracle(self):
        _assert_matches_oracle('flash_crowd', seed=5)

    def test_reconnect_storm_matches_oracle(self):
        """Partitions + heals from the schedule map to severing and
        restarting the loopback proxies: re-dials see ECONNREFUSED,
        back off, and recover through the transparent-reconnect
        path."""
        _assert_matches_oracle('reconnect_storm', seed=5)

    def test_flash_crowd_heavy_faults(self):
        """Crank the fault knobs well past the default lane: the
        stream resets and re-dials must still land byte-identical."""
        sched = build_schedule('flash_crowd', seed=9, scale='smoke')
        oracle = run_oracle(sched)
        out = replay_schedule_over_sockets(
            sched, chaos={'drop': 0.12, 'dup': 0.12, 'cut': 0.04,
                          'corrupt': 0.05}, max_ticks=8000)
        assert out['quarantined'] == 0 and out['diverged'] == 0
        assert out['views'] == oracle


class TestChaosProxyFaults:
    def test_corrupt_fault_exercises_crc_reject(self):
        """The byte-flip fault must actually land: frame errors are
        COUNTED, streams reset, re-dials recover, and the fleet still
        converges with zero quarantines. (Whole-chunk drop/dup mostly
        stay frame-aligned on loopback — corruption is the fault that
        proves the CRC path.)"""
        from automerge_tpu.common import ROOT_ID
        from automerge_tpu.sync import GeneralDocSet
        from automerge_tpu.sync.chaos import (SocketChaosFleet,
                                              canonical, doc_set_view)
        sets = [GeneralDocSet(64) for _ in range(2)]
        fleet = SocketChaosFleet(sets, seed=7, drop=0.1, dup=0.1,
                                 cut=0.03, corrupt=0.08)
        try:
            for t in range(30):
                sets[t % 2].apply_changes_batch({f'doc{t % 8}': [
                    {'actor': f'w{t}', 'seq': 1, 'deps': {}, 'ops': [
                        {'action': 'set', 'obj': ROOT_ID,
                         'key': f'k{t}', 'value': t}]}]})
                fleet.tick()
            fleet.run(max_ticks=3000)
            assert canonical(doc_set_view(sets[0])) == \
                canonical(doc_set_view(sets[1]))
            errs = sum(v for k, v in metrics.counters.items()
                       if k.endswith('transport_frame_errors'))
            redials = sum(v for k, v in metrics.counters.items()
                          if k.endswith('transport_reconnects'))
            assert errs > 0, 'corruption never hit the CRC path'
            assert redials > 0, 'no stream reset / re-dial happened'
            assert not sets[0].quarantined and not sets[1].quarantined
        finally:
            fleet.close()
