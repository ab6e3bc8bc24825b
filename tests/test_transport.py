"""Socket transport suite: framing, fuzz, mux, membership.

The frame codec is the trust boundary between a hostile byte stream
and the envelope protocol: every fuzz case below must either decode
the original frames, reset the stream through a COUNTED FrameError,
or account a torn tail — never a hang, never a quarantine. On top:
the delta-clock elision (satellite 1), the membership retransmit park
(satellite 2) and the endpoint's kill/restart acceptance path —
failure detection within the heartbeat deadline, a peer_down
incident, and a session resume that serves only the divergence
window.
"""

import asyncio
import json
import random
import zlib

import pytest

from automerge_tpu.common import ROOT_ID
from automerge_tpu.durability import load_incident
from automerge_tpu.sync import (FrameDecoder, FrameError,
                                GeneralDocSet, ResilientConnection,
                                ServingDocSet, WireConnection)
from automerge_tpu.sync.chaos import (ChaosProxy, SocketChaosFleet,
                                      canonical, doc_set_view)
from automerge_tpu.sync.transport import (CHANNELS, FRAME_MAGIC,
                                          MAX_FRAME_BYTES, _HEADER,
                                          TransportEndpoint, _PeerLink,
                                          encode_ctl_frame,
                                          encode_frame,
                                          encode_frame_iov)
from automerge_tpu.utils.metrics import FlightRecorder, metrics


def change(actor, seq=1, key='k', value=1, deps=None):
    return {'actor': actor, 'seq': seq, 'deps': deps or {}, 'ops': [
        {'action': 'set', 'obj': ROOT_ID, 'key': key,
         'value': value}]}


def write(ds, doc_id, actor, value, seq=1):
    ds.apply_changes_batch(
        {doc_id: [change(actor, seq=seq, value=value)]})


def env_data(seq=1, payload=None):
    return {'v': 2, 'kind': 'data', 'seq': seq, 'sum': 0,
            'payload': payload if payload is not None
            else {'docs': ['d0'], 'clocks': [{'a': 1}]}}


def total(name):
    return sum(v for k, v in metrics.counters.items()
               if k.endswith(name))


# ---------------------------------------------------------------------------
# frame codec


class TestFrameCodec:
    def test_roundtrip_plain(self):
        frame = encode_frame('fleet', env_data())
        out = FrameDecoder().feed(frame)
        assert out == [('env', 'fleet', env_data())]

    def test_roundtrip_binary_fields(self):
        """bytes-valued payload fields ship raw in the body and come
        back as bytes — JSON never sees (or base64s) a wire blob."""
        payload = {'docs': ['d0'], 'blob': b'\x00\xff' * 300,
                   'tab': b'', 'n': 3, 'name': 'café'}
        frame = encode_frame('fleet', env_data(payload=payload))
        [(kind, dset, env)] = FrameDecoder().feed(frame)
        assert (kind, dset) == ('env', 'fleet')
        assert env['payload']['blob'] == payload['blob']
        assert env['payload']['tab'] == b''
        assert env['payload']['n'] == 3
        assert env['payload']['name'] == 'café'

    def test_empty_binary_field_leaves_no_empty_part(self):
        """A zero-change advert's empty blob is no part of the iovec:
        a trailing zero-length part never leaves the socket
        transport's write buffer, and its close() then never ends."""
        payload = {'docs': ['d0'], 'blob': b''}
        _channel, parts, nbytes = encode_frame_iov(
            'fleet', env_data(payload=payload))
        assert all(len(p) for p in parts)
        assert nbytes == sum(len(p) for p in parts)
        [(_kind, _dset, env)] = FrameDecoder().feed(b''.join(parts))
        assert env['payload']['blob'] == b''

    def test_ctl_roundtrip(self):
        frame = encode_ctl_frame({'hello': 1, 'node': 'n0',
                                  'epoch': 7})
        out = FrameDecoder().feed(frame)
        assert out == [('ctl', None,
                        {'hello': 1, 'node': 'n0', 'epoch': 7})]

    @pytest.mark.parametrize('kind,chan', [
        ('data', 'data'), ('ack', 'ack'), ('busy', 'busy'),
        ('hb', 'hb')])
    def test_channel_byte(self, kind, chan):
        env = dict(env_data())
        env['kind'] = kind
        assert encode_frame('f', env)[2] == CHANNELS[chan]

    def test_state_payload_gets_state_channel(self):
        env = env_data(payload={'docs': ['d0'], 'state': b'snap'})
        assert encode_frame('f', env)[2] == CHANNELS['state']

    def test_byte_at_a_time_feed(self):
        """Interleaved partial reads are the NORMAL stream case: one
        byte per feed still yields every frame, in order."""
        frames = [encode_frame('f', env_data(seq=i))
                  for i in range(4)]
        dec = FrameDecoder()
        out = []
        for b in b''.join(frames):
            out += dec.feed(bytes([b]))
        assert [e['seq'] for _k, _d, e in out] == [0, 1, 2, 3]
        assert dec.buffered == 0


# ---------------------------------------------------------------------------
# framing fuzz (satellite: every case recovers, resets cleanly, or
# raises a counted protocol error — never a hang, never a quarantine)


class TestFramingFuzz:
    def test_truncated_frame_is_a_counted_torn_tail(self):
        frame = encode_frame('f', env_data())
        before = total('transport_partial_frames')
        dec = FrameDecoder()
        assert dec.feed(frame[:len(frame) - 3]) == []
        dec.eof()
        assert total('transport_partial_frames') == before + 1
        # the decoder is reusable after the reset
        assert dec.feed(frame) == [('env', 'f', env_data())]

    def test_bit_flipped_length_prefix_is_rejected_not_buffered(self):
        """A flipped high bit in the length prefix asks the decoder
        to buffer gigabytes for a frame that will never complete —
        MAX_FRAME_BYTES rejects it as a counted error instead."""
        frame = bytearray(encode_frame('f', env_data()))
        frame[3] |= 0x80               # hlen's high byte
        before = total('transport_frame_errors')
        with pytest.raises(FrameError):
            FrameDecoder().feed(bytes(frame))
        assert total('transport_frame_errors') == before + 1

    def test_bad_magic_rejected(self):
        frame = b'XX' + encode_frame('f', env_data())[2:]
        with pytest.raises(FrameError):
            FrameDecoder().feed(frame)

    def test_crc_catches_body_flip(self):
        frame = bytearray(encode_frame('f', env_data(
            payload={'docs': ['d0'], 'blob': b'abcdef'})))
        frame[-2] ^= 0x01
        with pytest.raises(FrameError):
            FrameDecoder().feed(bytes(frame))

    def test_error_resets_stream_then_fresh_frames_decode(self):
        good = encode_frame('f', env_data(seq=9))
        bad = bytearray(good)
        bad[-1] ^= 0xFF
        dec = FrameDecoder()
        with pytest.raises(FrameError):
            dec.feed(bytes(bad) + good)  # good frame after the bad
        # the reset dropped everything buffered (the stream is not
        # trustworthy past a CRC failure) — but the decoder itself
        # keeps working on the re-dialed stream
        assert dec.buffered == 0
        assert dec.feed(good) == [('env', 'f', env_data(seq=9))]

    def test_fuzz_mutations_never_hang_or_mislead(self):
        """Seeded fuzz over whole streams: random byte flips, random
        truncations, random garbage splices, random chunking. Every
        rep must yield a PREFIX-or-subset of the original frames
        (CRC'd frames are either intact or rejected — a mutated frame
        can never decode to different content) or raise a counted
        FrameError."""
        rng = random.Random(0xF7A)
        envs = [env_data(seq=i, payload={
            'docs': [f'd{i}'], 'clocks': [{'a': i + 1}],
            'blob': bytes(rng.randrange(256)
                          for _ in range(rng.randrange(64)))})
            for i in range(6)]
        stream = b''.join(encode_frame('f', e) for e in envs)
        originals = [('env', 'f', e) for e in envs]
        for rep in range(300):
            data = bytearray(stream)
            mode = rep % 3
            if mode == 0:              # flip 1-4 bytes
                for _ in range(rng.randrange(1, 5)):
                    data[rng.randrange(len(data))] ^= \
                        1 << rng.randrange(8)
            elif mode == 1:            # truncate
                del data[rng.randrange(len(data)):]
            else:                      # splice garbage mid-stream
                at = rng.randrange(len(data))
                junk = bytes(rng.randrange(256)
                             for _ in range(rng.randrange(1, 40)))
                data[at:at] = junk
            dec = FrameDecoder()
            out = []
            errors_before = total('transport_frame_errors')
            try:
                at = 0
                while at < len(data):
                    n = rng.randrange(1, 512)
                    out += dec.feed(bytes(data[at:at + n]))
                    at += n
                dec.eof()
            except FrameError:
                assert total('transport_frame_errors') == \
                    errors_before + 1
            # decoded frames are a subset of the originals, intact:
            # corruption can suppress frames, never alter them
            for item in out:
                assert item in originals


class _OracleDecoder:
    """Plain-copy reference decoder: the same frame grammar as
    :class:`FrameDecoder`, implemented the naive way — an immutable
    ``bytes`` buffer re-sliced per feed, a fresh copy per field, no
    ring, no memoryviews. The differential fuzz below holds the
    zero-copy ring decoder to this oracle's exact accept/reject/
    counter behavior, so any divergence introduced by view slicing
    or compaction shows up as a mismatch, not a silent protocol
    drift."""

    def __init__(self, max_frame_bytes=MAX_FRAME_BYTES):
        self.max_frame_bytes = max_frame_bytes
        self._buf = b''
        self.frames_received = 0
        self.frame_errors = 0
        self.partial_frames = 0

    def _error(self, reason):
        self.frame_errors += 1
        self._buf = b''
        raise FrameError(reason)

    def feed(self, data):
        self._buf += bytes(data)
        out = []
        while len(self._buf) >= _HEADER.size:
            magic, _chan, hlen, blen, crc = \
                _HEADER.unpack_from(self._buf, 0)
            if magic != FRAME_MAGIC:
                self._error('bad frame magic')
            if hlen == 0 or hlen + blen > self.max_frame_bytes:
                self._error('frame length out of bounds')
            frame_len = _HEADER.size + hlen + blen
            if len(self._buf) < frame_len:
                break
            head = self._buf[_HEADER.size:_HEADER.size + hlen]
            body = self._buf[_HEADER.size + hlen:frame_len]
            if zlib.crc32(body, zlib.crc32(head)) != crc:
                self._error('frame crc mismatch')
            self._buf = self._buf[frame_len:]
            try:
                obj = json.loads(head.decode('utf-8'))
            except (UnicodeDecodeError, ValueError):
                self._error('frame header is not valid json')
            if not isinstance(obj, dict):
                self._error('frame header is not an object')
            ctl = obj.get('ctl')
            if ctl is not None:
                if not isinstance(ctl, dict):
                    self._error('ctl frame is not an object')
                self.frames_received += 1
                out.append(('ctl', None, ctl))
                continue
            dset = obj.get('d')
            env = obj.get('e')
            if not isinstance(dset, str) or not isinstance(env, dict):
                self._error('frame header missing docset/envelope')
            binfields = obj.get('b')
            if binfields:
                payload = env.get('payload')
                if not isinstance(payload, dict) \
                        or not isinstance(binfields, list):
                    self._error('binary fields without a payload')
                bpos = 0
                for entry in binfields:
                    if not (isinstance(entry, list)
                            and len(entry) == 2
                            and isinstance(entry[0], str)
                            and isinstance(entry[1], int)
                            and entry[1] >= 0):
                        self._error('malformed binary field entry')
                    field, n = entry
                    payload[field] = body[bpos:bpos + n]
                    bpos += n
                if bpos != blen:
                    self._error('binary fields disagree with body')
            self.frames_received += 1
            out.append(('env', dset, env))
        return out

    def eof(self):
        if self._buf:
            self.partial_frames += 1
        self._buf = b''

    @property
    def buffered(self):
        return len(self._buf)


class TestFramingDifferential:
    """Ring decoder vs plain-copy oracle, byte for byte: the seeded
    corpus from TestFramingFuzz runs through both side by side with
    identical chunk boundaries, and every rep must agree on decoded
    frames, raise/no-raise, AND counter deltas. The ring arm runs
    with a tiny compact_at so nearly every consumed frame triggers
    a compaction — the exact machinery the oracle doesn't have."""

    def _corpus(self):
        rng = random.Random(0xF7A)
        envs = [env_data(seq=i, payload={
            'docs': [f'd{i}'], 'clocks': [{'a': i + 1}],
            'blob': bytes(rng.randrange(256)
                          for _ in range(rng.randrange(64)))})
            for i in range(6)]
        stream = b''.join(encode_frame('f', e) for e in envs)
        return rng, stream

    def test_ring_and_oracle_agree_on_fuzzed_streams(self):
        rng, stream = self._corpus()
        for rep in range(300):
            data = bytearray(stream)
            mode = rep % 3
            if mode == 0:              # flip 1-4 bytes
                for _ in range(rng.randrange(1, 5)):
                    data[rng.randrange(len(data))] ^= \
                        1 << rng.randrange(8)
            elif mode == 1:            # truncate
                del data[rng.randrange(len(data)):]
            else:                      # splice garbage mid-stream
                at = rng.randrange(len(data))
                junk = bytes(rng.randrange(256)
                             for _ in range(rng.randrange(1, 40)))
                data[at:at] = junk
            chunks = []
            at = 0
            while at < len(data):
                n = rng.randrange(1, 512)
                chunks.append(bytes(data[at:at + n]))
                at += n
            ring = FrameDecoder(compact_at=97)
            oracle = _OracleDecoder()
            received = total('transport_frames_received')
            errors = total('transport_frame_errors')
            partials = total('transport_partial_frames')
            ring_out, ring_err = [], False
            oracle_out, oracle_err = [], False
            try:
                for chunk in chunks:
                    ring_out += ring.feed(chunk)
                ring.eof()
            except FrameError:
                ring_err = True
            try:
                for chunk in chunks:
                    oracle_out += oracle.feed(chunk)
                oracle.eof()
            except FrameError:
                oracle_err = True
            assert ring_err == oracle_err, f'rep {rep}'
            assert ring_out == oracle_out, f'rep {rep}'
            assert total('transport_frames_received') - received \
                == oracle.frames_received, f'rep {rep}'
            assert total('transport_frame_errors') - errors \
                == oracle.frame_errors, f'rep {rep}'
            assert total('transport_partial_frames') - partials \
                == oracle.partial_frames, f'rep {rep}'

    def test_frame_straddling_the_compaction_point(self):
        """The second frame's head arrives split across a compaction:
        the first frame's consumed bytes pass compact_at with a torn
        tail behind them, so the `del buf[:pos]` slides that tail to
        offset zero mid-frame."""
        first = encode_frame('f', env_data(seq=0, payload={
            'docs': ['d0'], 'blob': b'x' * 200}))
        second = encode_frame('f', env_data(seq=1))
        dec = FrameDecoder(compact_at=len(first) - 8)
        out = dec.feed(first + second[:7])
        assert [e['seq'] for _k, _d, e in out] == [0]
        assert dec.buffered == 7
        out = dec.feed(second[7:])
        assert out == [('env', 'f', env_data(seq=1))]
        assert dec.buffered == 0

    def test_byte_at_a_time_with_constant_compaction(self):
        """compact_at=1 forces a compaction after every consumed
        frame; single-byte feeds make every offset a chunk boundary.
        All frames must still decode intact and in order."""
        frames = [encode_frame('f', env_data(seq=i, payload={
            'docs': ['d0'], 'blob': bytes([i]) * (i * 37 % 64)}))
            for i in range(5)]
        dec = FrameDecoder(compact_at=1)
        out = []
        for b in b''.join(frames):
            out += dec.feed(bytes([b]))
        assert [e['seq'] for _k, _d, e in out] == [0, 1, 2, 3, 4]
        assert dec.buffered == 0

    def test_max_size_frame_at_the_wrap(self):
        """A frame of exactly max_frame_bytes whose bytes land right
        after a compaction decodes; one byte over the cap is rejected
        as a counted error, never buffered."""
        small = encode_frame('f', env_data(seq=0))
        big = encode_frame('f', env_data(seq=1, payload={
            'docs': ['d0'], 'blob': bytes(range(256)) * 4}))
        _m, _c, hlen, blen, _crc = _HEADER.unpack_from(big, 0)
        dec = FrameDecoder(max_frame_bytes=hlen + blen,
                           compact_at=len(small))
        out = dec.feed(small + big[:20])   # compaction fires here
        out += dec.feed(big[20:])
        assert [e['seq'] for _k, _d, e in out] == [0, 1]
        assert dec.buffered == 0
        tight = FrameDecoder(max_frame_bytes=hlen + blen - 1)
        before = total('transport_frame_errors')
        with pytest.raises(FrameError):
            tight.feed(big)
        assert total('transport_frame_errors') == before + 1
        assert tight.buffered == 0


# ---------------------------------------------------------------------------
# delta-encoded clock adverts (satellite 1)


class TestDeltaClocks:
    def _pair(self):
        """A resilient WIRE pair: the ack flow is what folds acked
        clocks into the sender's elision baseline — bare wire
        connections never ack, so they never elide."""
        src, dst = GeneralDocSet(16), GeneralDocSet(16)
        ma, mb = [], []
        ra = ResilientConnection(src, ma.append, batching=True,
                                 wire=True, heartbeat_every=0)
        rb = ResilientConnection(dst, mb.append, batching=True,
                                 wire=True, heartbeat_every=0)
        ra.open()
        rb.open()
        return src, dst, ra, rb, ma, mb

    def _pump(self, ra, rb, ma, mb, rounds=40):
        for _ in range(rounds):
            ra.flush()
            rb.flush()
            if not (ma or mb):
                return
            for m in ma[:]:
                ma.remove(m)
                rb.receive_msg(m)
            for m in mb[:]:
                mb.remove(m)
                ra.receive_msg(m)

    def test_ship_clock_elides_acked_entries(self):
        src, dst, ra, rb, ma, mb = self._pair()
        write(src, 'doc0', 'a', 1)
        self._pump(ra, rb, ma, mb)
        # the first exchange acked {'a': 1}; a later advert for the
        # same doc ships only what GREW past that baseline
        wire = ra._conn
        assert wire._adv_acked.get('doc0') == {'a': 1}
        before = total('sync_wire_clock_entries_elided')
        shipped = wire._ship_clock('doc0', {'a': 1, 'b': 2}, 3)
        assert shipped == {'b': 2}
        assert total('sync_wire_clock_entries_elided') == before + 1

    def test_fresh_session_ships_full_clocks(self):
        """No acked baseline (new or reset session) -> full clocks,
        nothing elided: the fallback IS the old protocol."""
        src = GeneralDocSet(4)
        ca = WireConnection(src, lambda m: None, wire_version=3)
        assert ca._ship_clock('doc0', {'a': 3, 'b': 1}, 3) == \
            {'a': 3, 'b': 1}

    def test_v2_peer_never_sees_deltas(self):
        src, dst, ra, rb, ma, mb = self._pair()
        write(src, 'doc0', 'a', 1)
        self._pump(ra, rb, ma, mb)
        assert ra._conn._ship_clock('doc0', {'a': 1, 'b': 2}, 2) == \
            {'a': 1, 'b': 2}

    def test_fully_elided_advert_ships_whole(self):
        """An advert whose every entry is elided would be WIRE-
        IDENTICAL to a request (empty clock, zero count) — it must
        ship the full clock instead."""
        src, dst, ra, rb, ma, mb = self._pair()
        write(src, 'doc0', 'a', 1)
        self._pump(ra, rb, ma, mb)
        assert ra._conn._ship_clock(
            'doc0', {'a': 1}, 3, advert=True) == {'a': 1}

    def test_regression_heal_resets_the_baseline(self):
        src, dst, ra, rb, ma, mb = self._pair()
        write(src, 'doc0', 'a', 1)
        self._pump(ra, rb, ma, mb)
        ra._conn.note_clock_regressed('doc0', {})
        assert ra._conn._ship_clock('doc0', {'a': 1}, 3) == {'a': 1}

    def test_deltas_converge_identically(self):
        """End to end: a multi-beat session with elision active
        converges to the same views as the doc sets' own state."""
        src, dst, ra, rb, ma, mb = self._pair()
        before = total('sync_wire_clock_entries_elided')
        for beat in range(4):
            for d in range(3):
                write(src, f'doc{d}', f'a{beat}', beat + d,
                      seq=1)
            self._pump(ra, rb, ma, mb)
        assert canonical(doc_set_view(src)) == \
            canonical(doc_set_view(dst))
        assert total('sync_wire_clock_entries_elided') > before


# ---------------------------------------------------------------------------
# membership park (satellite 2)


class TestMembershipPark:
    def _conn(self):
        ds = GeneralDocSet(8)
        sent = []
        conn = ResilientConnection(ds, sent.append, batching=True,
                                   heartbeat_every=4)
        conn.open()
        return ds, conn, sent

    def test_down_parks_retransmits_and_freezes_the_budget(self):
        ds, conn, sent = self._conn()
        write(ds, 'doc0', 'a', 1)
        conn.flush()
        assert conn._sent, 'no unacked envelope to park'
        attempts = {s: r.attempts for s, r in conn._sent.items()}
        conn.set_link_state('down')
        before_parked = total('membership_retries_parked')
        n_sent = len(sent)
        for _ in range(60):            # way past every backoff due
            conn.tick()
        assert len(sent) == n_sent, 'retransmitted against a down peer'
        assert {s: r.attempts for s, r in conn._sent.items()} == \
            attempts, 'retry budget burned while parked'
        assert total('membership_retries_parked') > before_parked

    def test_down_parks_the_heartbeat_too(self):
        ds, conn, sent = self._conn()
        conn.set_link_state('down')
        for _ in range(20):
            conn.tick()
        assert not any(e.get('kind') == 'hb' for e in sent)

    def test_up_re_dues_everything_immediately(self):
        ds, conn, sent = self._conn()
        write(ds, 'doc0', 'a', 1)
        conn.flush()
        conn.set_link_state('down')
        for _ in range(10):
            conn.tick()
        n_sent = len(sent)
        conn.set_link_state('up')
        conn.tick()
        conn.tick()
        assert len(sent) > n_sent, 'no retransmit after the link healed'

    def test_suspect_changes_nothing(self):
        ds, conn, sent = self._conn()
        write(ds, 'doc0', 'a', 1)
        conn.flush()
        conn.set_link_state('suspect')
        n_sent = len(sent)
        for _ in range(20):
            conn.tick()
        assert len(sent) > n_sent, 'suspect must keep retransmitting'

    def test_connection_status_reports_link_state(self):
        ds, conn, _sent = self._conn()
        assert conn.connection_status()['state'] == 'up'
        conn.set_link_state('down')
        assert conn.connection_status()['state'] == 'down'


# ---------------------------------------------------------------------------
# observability: the fast path must be measurable in production


class TestTransportObservability:
    def test_write_read_spans_and_coalescing_counters(self):
        """A traced fleet run leaves transport.write spans (frames +
        bytes per writelines batch), transport.read spans (bytes per
        feed), a frames-per-syscall series and eager-flush counters —
        the figures trace_report prints next to wire MB/s."""
        rec = FlightRecorder(8192)
        metrics.subscribe(rec)
        flushes = total('transport_eager_flushes')
        fps_n = total('transport_frames_per_syscall.count')
        try:
            sets = [GeneralDocSet(8) for _ in range(2)]
            fleet = SocketChaosFleet(sets, seed=5)
            try:
                for t in range(4):
                    write(sets[t % 2], f'doc{t}', f'a{t}', t)
                    fleet.tick()
                fleet.run(max_ticks=200)
            finally:
                fleet.close()
        finally:
            metrics.unsubscribe(rec)
        assert total('transport_eager_flushes') > flushes
        assert total('transport_frames_per_syscall.count') > fps_n
        spans = [e for e in rec.events()
                 if e.get('event') == 'span']
        writes = [e for e in spans
                  if e.get('name') == 'transport.write']
        reads = [e for e in spans
                 if e.get('name') == 'transport.read']
        assert writes, 'no transport.write spans recorded'
        assert reads, 'no transport.read spans recorded'
        assert all(e.get('frames', 0) >= 1 and e.get('bytes', 0) > 0
                   for e in writes)
        assert all(e.get('bytes', 0) > 0 for e in reads)


# ---------------------------------------------------------------------------
# liveness fast path (eager satellite: HELLO / pings / busy replies
# bypass coalescing, and the failure-detector deadlines don't move
# when the eager path is on and the data queue is saturated)


class TestLivenessFastPath:
    def test_liveness_frames_jump_the_data_backlog(self):
        """Keepalive pings and busy replies insert ahead of every
        queued data frame but BEHIND leading ctl frames, so a pending
        HELLO stays first on its socket."""
        ep = TransportEndpoint('n0', {})
        link = _PeerLink('p0')
        hello = encode_ctl_frame({'hello': 1, 'node': 'n0'})
        link.outq.append((CHANNELS['ctl'], [hello], len(hello)))
        for i in range(4):
            f = encode_frame('f', env_data(seq=i))
            link.outq.append((CHANNELS['data'], [f], len(f)))
        ep._enqueue_ctl(link, {'ping': 1}, liveness=True)
        busy = dict(env_data(seq=9))
        busy['kind'] = 'busy'
        ep._enqueue(link, 'f', busy)
        chans = [e[0] for e in link.outq]
        assert chans[0] == CHANNELS['ctl']     # the HELLO stays first
        assert chans[1] == CHANNELS['ctl']     # ping right behind it
        assert chans[2] == CHANNELS['busy']    # busy reply next
        assert all(c == CHANNELS['data'] for c in chans[3:])

    def _detection_ticks(self, saturate):
        """Kill node1, then (optionally) pile writes onto node0 every
        tick so its outgoing data path to the dead peer saturates.
        Returns (ticks-to-suspect, ticks-to-down, frames pushed at
        the dead link during the detection window)."""
        sets = [GeneralDocSet(16) for _ in range(2)]
        fleet = SocketChaosFleet(sets, seed=7, suspect_after=6,
                                 dead_after=12)
        try:
            write(sets[0], 'doc0', 'a0', 1)
            fleet.run(max_ticks=300)
            fleet.kill(1)
            sent0 = total('transport_frames_sent')
            t0 = fleet.now
            ep0 = fleet.endpoints[0]
            suspect_at = down_at = None
            n = 0
            while fleet.now < t0 + 40 and down_at is None:
                if saturate:
                    for _ in range(4):
                        write(sets[0], f'sat{n}', f's{n:02d}', n)
                        n += 1
                fleet.tick()
                state = ep0.membership().get('node1')
                if suspect_at is None and state in ('suspect', 'down'):
                    suspect_at = fleet.now - t0
                if down_at is None and state == 'down':
                    down_at = fleet.now - t0
            pushed = total('transport_frames_sent') - sent0
            return suspect_at, down_at, pushed
        finally:
            fleet.close()

    def test_deadlines_unchanged_under_saturated_eager_queue(self):
        """Regression for the eager fast path: suspect/dead are
        judged on logical ticks and last_seen only — a saturated
        data queue (eager flushes landing every tick) must not move
        either deadline by a single tick."""
        idle = self._detection_ticks(False)
        loaded = self._detection_ticks(True)
        assert idle[1] is not None, 'idle run never detected death'
        assert loaded[1] is not None, 'loaded run never detected death'
        assert loaded[:2] == idle[:2], \
            f'deadlines moved under load: {idle[:2]} -> {loaded[:2]}'
        assert loaded[2] > idle[2] + 20, \
            'saturation arm never actually pushed a data backlog'


# ---------------------------------------------------------------------------
# endpoint: mux, membership, kill/restart acceptance


class TestTransportEndpoint:
    def test_two_nodes_converge_over_real_sockets(self):
        sets = [GeneralDocSet(16) for _ in range(2)]
        fleet = SocketChaosFleet(sets, seed=3)
        try:
            for t in range(6):
                write(sets[t % 2], f'doc{t}', f'a{t}', t)
                fleet.tick()
            fleet.run(max_ticks=300)
            assert canonical(doc_set_view(sets[0])) == \
                canonical(doc_set_view(sets[1]))
            ep = fleet.endpoints[0]
            assert ep.membership() == {'node1': 'up'}
            st = sets[0].fleet_status(docs=False)
            assert st['connections']['node1']['state'] == 'up'
            assert total('transport_frames_sent') > 0
            assert total('transport_bytes_received') > 0
        finally:
            fleet.close()

    def test_one_socket_multiplexes_every_doc_set(self):
        """Two hosted doc sets, ONE socket pair: both converge, and
        only one connect happens per direction."""
        a0, a1 = GeneralDocSet(8), GeneralDocSet(8)
        b0, b1 = GeneralDocSet(8), GeneralDocSet(8)
        loop = asyncio.new_event_loop()
        try:
            ea = TransportEndpoint('a', {'s0': a0, 's1': a1})
            eb = TransportEndpoint('b', {'s0': b0, 's1': b1})

            async def go():
                await ea.start()
                await eb.start()
                await ea.connect('b', '127.0.0.1', eb.port)
                write(a0, 'x', 'w0', 1)
                write(b1, 'y', 'w1', 2)
                for _ in range(120):
                    await ea.tick()
                    await eb.tick()
                    for _ in range(6):
                        await asyncio.sleep(0)
                    if not (ea.pending() or eb.pending()):
                        break
                await ea.close()
                await eb.close()
            loop.run_until_complete(go())
            loop.run_until_complete(asyncio.sleep(0.01))
        finally:
            loop.close()
        assert canonical(doc_set_view(a0)) == \
            canonical(doc_set_view(b0))
        assert canonical(doc_set_view(a1)) == \
            canonical(doc_set_view(b1))

    def test_transparent_reconnect_keeps_sessions(self):
        """A TCP blip (socket dies, process doesn't) re-dials under
        the SAME epoch: the live connections and their v3 session
        tables survive — no session reset, no session resume."""
        sets = [GeneralDocSet(16) for _ in range(2)]
        fleet = SocketChaosFleet(sets, seed=4)
        try:
            for t in range(4):
                write(sets[t % 2], f'doc{t}', f'a{t}', t)
                fleet.tick()
            fleet.run(max_ticks=300)
            ep = fleet.endpoints[0]
            conn_before = ep.connection_for('node1', 'fleet')
            resumes = total('sync_wire_session_resumes')
            resets = total('sync_wire_session_resets')

            async def blip():
                link = ep.peers['node1']
                link.writer.transport.abort()
            fleet._run(blip())
            write(sets[0], 'after', 'z', 1)
            fleet.run(max_ticks=300, min_ticks=3)
            assert canonical(doc_set_view(sets[0])) == \
                canonical(doc_set_view(sets[1]))
            assert ep.connection_for('node1', 'fleet') is conn_before
            assert total('sync_wire_session_resumes') == resumes
            assert total('sync_wire_session_resets') == resets
            assert total('transport_reconnects') > 0
        finally:
            fleet.close()

    def test_kill_detect_incident_restart_resume(self, tmp_path):
        """The acceptance path end to end: kill a peer mid-run ->
        down within the heartbeat deadline, membership health signal
        fires, peer_down incident dumps; writes keep applying locally
        and new births PARK; restart -> resume serves only the
        divergence window (session resumes, recovery bytes a fraction
        of the initial sync) and every signal clears."""
        inner = GeneralDocSet(64)
        serving = ServingDocSet(inner, str(tmp_path / 'srv'),
                                flight_recorder=FlightRecorder(256))
        other = GeneralDocSet(64)
        fleet = SocketChaosFleet([serving, other], seed=11,
                                 suspect_after=6, dead_after=12)
        try:
            bytes_start = total('transport_bytes_sent')
            for t in range(10):
                write(serving, f'doc{t}', f'a{t}', t)
                fleet.tick()
            fleet.run(max_ticks=400)
            initial_bytes = total('transport_bytes_sent') - bytes_start

            fleet.kill(1)
            ep0 = fleet.endpoints[0]
            deadline = fleet.now + 12 + 8   # dead_after + redial grace
            while fleet.now < deadline and \
                    ep0.membership().get('node1') != 'down':
                fleet.tick()
            assert ep0.membership()['node1'] == 'down', \
                'death not detected within the heartbeat deadline'
            health = serving.evaluate_health()
            assert health['state'] != 'green'
            assert health['signals']['membership'] >= 1
            st = serving.fleet_status(docs=False)
            assert st['connections']['node1']['state'] == 'down'
            files = sorted((tmp_path / 'srv' / 'incidents').glob(
                '*peer_down*'))
            assert files, 'no peer_down incident dumped'
            _events, trigger = load_incident(str(files[0]))
            assert trigger['kind'] == 'peer_down'
            assert trigger['peer'] == 'node1'

            # graceful degradation: local writes apply, births park
            write(serving, 'newdoc', 'late', 1)
            for _ in range(3):
                fleet.tick()
            conv = serving.fleet_status(docs=False)['convergence']
            assert conv.get('parked_births', 0) >= 1
            assert total('membership_retries_parked') > 0

            resumes = total('sync_wire_session_resumes')
            bytes_before = total('transport_bytes_sent')
            fleet.restart(1)           # same doc set: durable state
            fleet.run(max_ticks=600)
            recovery_bytes = total('transport_bytes_sent') \
                - bytes_before
            assert total('sync_wire_session_resumes') > resumes, \
                'restart did not take the session resume path'
            assert canonical(doc_set_view(serving)) == \
                canonical(doc_set_view(other))
            # divergence-window accounting: recovery re-serves ONE
            # doc (plus handshake), not the ten-doc initial sync
            assert recovery_bytes < initial_bytes, (
                f'recovery resent too much: {recovery_bytes} vs '
                f'initial {initial_bytes}')
            health = serving.evaluate_health()
            assert health['signals']['membership'] == 0
            assert serving.fleet_status(
                docs=False)['convergence'].get('parked_births') == 0
            assert not serving.quarantined and not other.quarantined
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# shutdown: every close returns, whatever the peer is doing


async def closes_in_time(coro, what):
    try:
        await asyncio.wait_for(coro, 5)
    except asyncio.TimeoutError:
        pytest.fail(f'{what} did not return within 5 s')


class TestShutdown:
    @pytest.mark.parametrize('order', ['dialer_first', 'acceptor_first',
                                       'acceptor_after_peer_froze'])
    def test_endpoint_close_returns(self, order):
        """The dialer-first order is the mux test's. In the last case
        the dialer stops reading (a frozen process): megabytes stay
        queued on the acceptor's socket, and its close must give up
        the flush instead of waiting for it."""
        a, b = GeneralDocSet(8), GeneralDocSet(8)
        loop = asyncio.new_event_loop()
        ea = TransportEndpoint('a', {'s': a})
        eb = TransportEndpoint('b', {'s': b})

        async def go():
            await ea.start()
            await eb.start()
            await ea.connect('b', '127.0.0.1', eb.port)
            write(a, 'x', 'w0', 1)
            write(b, 'y', 'w1', 2)
            for _ in range(120):
                await ea.tick()
                await eb.tick()
                for _ in range(6):
                    await asyncio.sleep(0)
                if not (ea.pending() or eb.pending()):
                    break
            first, second = (ea, eb) if order == 'dialer_first' \
                else (eb, ea)
            if order == 'acceptor_after_peer_froze':
                ea.peers['b'].writer.transport.pause_reading()
                stuck = eb.peers['a'].writer
                stuck.write(b'\0' * (32 << 20))
                await asyncio.sleep(0.05)
                assert stuck.transport.get_write_buffer_size() > 0
            await closes_in_time(first.close(), f'{first.node_id}.close()')
            await closes_in_time(second.close(),
                                 f'{second.node_id}.close()')
        try:
            loop.run_until_complete(go())
        finally:
            loop.close()
        assert canonical(doc_set_view(a)) == canonical(doc_set_view(b))

    def test_write_loop_leaves_a_closing_socket_alone(self):
        """A socket reset that the read loop has not seen yet: the
        write loop detaches it without a writelines() (which would
        register the dead fd with the selector again) and keeps the
        frames queued for the next socket."""
        class Closing:
            def is_closing(self):
                return True

            def writelines(self, parts):
                raise AssertionError('writelines on a closing socket')

            def close(self):
                pass

        ep = TransportEndpoint('n0', {})
        link = _PeerLink('p0')
        link.writer = writer = Closing()
        frame = encode_frame('f', env_data())
        link.outq.append((CHANNELS['data'], [frame], len(frame)))
        asyncio.run(ep._write_loop(link, writer))
        assert link.writer is None
        assert len(link.outq) == 1

    def test_proxy_sever_and_close_with_live_pipe(self):
        """A live pipe through the proxy: sever() and close() both
        return, and the pipe is cut."""
        async def echo(reader, writer):
            try:
                while data := await reader.read(4096):
                    writer.write(data)
                    await writer.drain()
            except ConnectionError:
                pass
            writer.close()

        async def go():
            target = await asyncio.start_server(echo, '127.0.0.1', 0)
            port = target.sockets[0].getsockname()[1]
            proxy = await ChaosProxy(lambda: port).start()
            for step in ('sever', 'close'):
                reader, writer = await asyncio.open_connection(
                    '127.0.0.1', proxy.port)
                writer.write(b'ping')
                assert await asyncio.wait_for(
                    reader.readexactly(4), 5) == b'ping'
                await closes_in_time(getattr(proxy, step)(),
                                     f'proxy.{step}()')
                try:
                    tail = await asyncio.wait_for(reader.read(), 5)
                except ConnectionError:
                    tail = b''
                assert tail == b''
                writer.close()
                await proxy.heal()
            await proxy.close()
            target.close()
            await closes_in_time(target.wait_closed(),
                                 'target.wait_closed()')

        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(go())
        finally:
            loop.close()
