"""Multi-host fabric: spec/verify, fan-out striping + re-striping,
fan-in interleave + gap marking, rejoin resume, membership, affinity,
and the proclog/telemetry host identity (bifrost_tpu.fabric;
docs/fabric.md)."""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu import fabric, proclog
from bifrost_tpu.analysis.verify import verify_fabric
from bifrost_tpu.telemetry import counters, histograms

from util import (NumpySourceBlock, GatherSink, simple_header,
                  free_ports, port_block)

NT, NC = 4, 8
FRAME_NBYTE = NC * 4


@pytest.fixture(autouse=True)
def _fabric_env(tmp_path, monkeypatch):
    """Isolate durable fabric state per test and keep the membership
    timers snappy."""
    monkeypatch.setenv('BF_FABRIC_STATE', str(tmp_path / 'state'))
    monkeypatch.setenv('BF_FABRIC_HEARTBEAT_SECS', '0.05')
    monkeypatch.setenv('BF_FABRIC_DEADLINE_SECS', '0.4')
    monkeypatch.setenv('BF_FABRIC_REJOIN_CAP', '0.05')
    yield
    proclog.set_identity(None)


def _gulps(origin, n, start=0):
    out = []
    for i in range(start, n):
        g = np.zeros((NT, NC), np.float32)
        g[:, 0] = origin
        g[:, 1] = np.arange(i * NT, (i + 1) * NT)
        out.append(g)
    return out


def _delta(before, key):
    return counters.get(key) - before.get(key, 0)


# ---------------------------------------------------------------------------
# spec + static verification
# ---------------------------------------------------------------------------

class TestFabricSpec:
    def test_roundtrip(self):
        spec = fabric.FabricSpec('t', hosts={
            'a': {'address': '10.0.0.1', 'control_port': 7000,
                  'cores': [0, 1], 'role': 'capture'},
            'b': {'address': '10.0.0.2', 'control_port': 7001},
        }, links={
            'l': {'kind': 'pipe', 'src': 'a', 'dst': 'b',
                  'port': 7100, 'window': 4, 'quota_mbps': 10.0,
                  'connect': {'b': ['10.9.9.9', 7200]}},
        })
        spec2 = fabric.FabricSpec.from_dict(spec.to_dict())
        assert spec2.hosts['a'].cores == [0, 1]
        assert spec2.links['l'].window == 4
        assert spec2.links['l'].dial_target(spec2, 'b', 0) == \
            ('10.9.9.9', 7200)
        assert spec2.to_dict() == spec.to_dict()

    def test_endpoint_views(self):
        spec = fabric.FabricSpec('t', hosts={
            'c0': {}, 'c1': {}, 'r': {}, 'l0': {}, 'l1': {},
        }, links={
            'in': {'kind': 'fanin', 'src': ['c0', 'c1'], 'dst': 'r',
                   'port': 7100},
            'out': {'kind': 'fanout', 'src': 'r',
                    'dst': ['l0', 'l1'], 'port': 7200},
        })
        assert [o for o, _ in spec.inbound_links('r')] == \
            [spec.links['in']] * 2
        assert spec.outbound_links('r') == [spec.links['out']]
        assert spec.inbound_links('l1')[0][1] == 1   # leg port offset
        assert spec.peers_of('r') == ['c0', 'c1', 'l0', 'l1']

    def test_unknown_kind_raises(self):
        with pytest.raises(fabric.FabricSpecError):
            fabric.LinkSpec('x', 'broadcast', 'a', 'b', 1)


class TestVerifyFabric:
    def _codes(self, diags):
        return sorted(d.code for d in diags)

    def test_endpoint_mismatch(self):
        spec = {'name': 't', 'hosts': {'a': {}},
                'links': {'l': {'kind': 'pipe', 'src': 'a',
                                'dst': 'ghost', 'port': 7100}}}
        assert 'BF-E200' in self._codes(verify_fabric(spec))

    def test_self_loop(self):
        spec = {'name': 't', 'hosts': {'a': {}},
                'links': {'l': {'kind': 'pipe', 'src': 'a',
                                'dst': 'a', 'port': 7100}}}
        assert 'BF-E200' in self._codes(verify_fabric(spec))

    def test_single_origin_fanin(self):
        spec = {'name': 't', 'hosts': {'a': {}, 'b': {}},
                'links': {'l': {'kind': 'fanin', 'src': ['a'],
                                'dst': 'b', 'port': 7100}}}
        assert 'BF-E200' in self._codes(verify_fabric(spec))

    def test_port_collision(self):
        # the fan-in's origin-1 endpoint (port+1) lands on b's
        # control port
        spec = {'name': 't',
                'hosts': {'a': {}, 'c': {},
                          'b': {'control_port': 7101}},
                'links': {'l': {'kind': 'fanin', 'src': ['a', 'c'],
                                'dst': 'b', 'port': 7100}}}
        assert 'BF-E201' in self._codes(verify_fabric(spec))

    def test_window_and_buffer_sizing(self):
        spec = {'name': 't', 'hosts': {'a': {}, 'b': {}},
                'links': {
                    'bad': {'kind': 'pipe', 'src': 'a', 'dst': 'b',
                            'port': 7100, 'window': 0},
                    'thin': {'kind': 'pipe', 'src': 'a', 'dst': 'b',
                             'port': 7200, 'window': 4,
                             'buffer_spans': 3}}}
        codes = self._codes(verify_fabric(spec))
        assert 'BF-E150' in codes and 'BF-W202' in codes

    def test_quota_below_span(self):
        spec = {'name': 't', 'hosts': {'a': {}, 'b': {}},
                'links': {'l': {'kind': 'pipe', 'src': 'a',
                                'dst': 'b', 'port': 7100,
                                'quota_mbps': 0.0001,
                                'gulp_nbyte': 1 << 20}}}
        assert 'BF-W203' in self._codes(verify_fabric(spec))

    def test_clean_spec(self):
        spec = {'name': 't',
                'hosts': {'a': {'control_port': 7001},
                          'b': {'control_port': 7002}},
                'links': {'l': {'kind': 'pipe', 'src': 'a',
                                'dst': 'b', 'port': 7100,
                                'window': 2}}}
        assert not [d for d in verify_fabric(spec) if d.is_error]


# ---------------------------------------------------------------------------
# loopback fabric: striping, re-striping, cross-host SLO
# ---------------------------------------------------------------------------

def _run_hosts(hosts):
    threads = {h: threading.Thread(
        target=fh.run, kwargs={'install_signals': False})
        for h, fh in hosts.items()}
    for t in threads.values():
        t.start()
    for t in threads.values():
        t.join(timeout=90)
    assert not any(t.is_alive() for t in threads.values()), \
        'fabric deadlocked: %s' % {h: t.is_alive()
                                   for h, t in threads.items()}


class TestFanOutLoopback:
    NSEQ = 6

    def _spec(self, nlegs, policy='block'):
        base = port_block(nlegs)        # legs listen at base + i
        ports = [p for p in free_ports(1 + nlegs)
                 if p not in range(base, base + nlegs)]
        while len(ports) < 1 + nlegs:
            ports += [p for p in free_ports(1)
                      if p not in range(base, base + nlegs)]
        legs = ['leg%d' % i for i in range(nlegs)]
        hosts = {'src': {'address': '127.0.0.1',
                         'control_port': ports[0]}}
        for i, leg in enumerate(legs):
            hosts[leg] = {'address': '127.0.0.1',
                          'control_port': ports[1 + i]}
        return fabric.FabricSpec('fanout_t', hosts=hosts, links={
            'out': {'kind': 'fanout', 'src': 'src', 'dst': legs,
                    'port': base, 'window': 2,
                    'overload_policy': policy}})

    def _build(self, spec, dead_legs=()):
        sinks = {}
        legs = spec.links['out'].dst

        def build_src(ctx):
            hdr = simple_header([-1, NC], 'f32', name='stream',
                                gulp_nframe=NT)
            ctx.sink('out', _MultiSeqSource(self.NSEQ, hdr))

        def build_leg(leg):
            def b(ctx):
                sinks[leg] = GatherSink(ctx.source('out'))
            return b

        hosts = {}
        for leg in legs:
            hosts[leg] = fabric.FabricHost(spec, leg, build_leg(leg))
            hosts[leg].build()
        hosts['src'] = fabric.FabricHost(spec, 'src', build_src)
        hosts['src'].build()
        if dead_legs:
            # choreography stub: membership says these legs are dead
            fanout = [b for b in hosts['src'].pipeline.blocks
                      if isinstance(b, fabric.FanOutBlock)][0]
            fanout.membership = _StubMembership(dead_legs)
        return hosts, sinks

    def test_sequence_striping_and_fabric_slo(self):
        before = counters.snapshot()
        spec = self._spec(2)
        hosts, sinks = self._build(spec)
        _run_hosts(hosts)
        # sequences stripe round-robin: leg0 gets stripes 0,2,4...
        for i, leg in enumerate(('leg0', 'leg1')):
            stripes = [h['_fabric']['stripe']
                       for h in sinks[leg].headers]
            assert stripes == list(range(i, self.NSEQ, 2))
            assert all(h['_fabric']['leg'] == leg
                       for h in sinks[leg].headers)
        # lossless under 'block': every frame of every sequence lands
        total = sum(s.result().shape[0] for s in sinks.values())
        assert total == self.NSEQ * 4 * NT
        # the stream crossed a bridge hop: the cross-host fabric SLO
        # histogram recorded at the leg sinks (skew-corrected age)
        h = histograms.get('slo.fabric_exit_age_s')
        assert h is not None and h.count > 0
        assert _delta(before, 'fabric.fanout.sequences') == self.NSEQ

    def test_restripe_across_survivors_when_leg_dead(self):
        before = counters.snapshot()
        spec = self._spec(2)
        hosts, sinks = self._build(spec, dead_legs=('leg1',))
        _run_hosts(hosts)
        # every sequence re-striped onto the survivor, counted
        assert len(sinks['leg0'].headers) == self.NSEQ
        assert len(sinks['leg1'].headers) == 0
        assert _delta(before, 'fabric.fanout.restripes') == \
            self.NSEQ // 2
        total = sum(s.result().shape[0] for s in sinks.values()
                    if s.gulps)
        assert total == self.NSEQ * 4 * NT


class _MultiSeqSource(NumpySourceBlock):
    """NSEQ short sequences of 4 gulps each (fan-out stripes at
    sequence granularity)."""

    def __init__(self, nseq, hdr, **kwargs):
        NumpySourceBlock.__init__(self, [], hdr, NT, **kwargs)
        self.sourcenames = ['s%d' % i for i in range(nseq)]

    def create_reader(self, name):
        from util import _NumpyReader
        return _NumpyReader(_gulps(int(name[1:]), 4))

    def on_sequence(self, reader, name):
        hdr = dict(self._header)
        hdr['name'] = name
        return [hdr]


class _StubMembership(object):
    def __init__(self, dead):
        self.dead = set(dead)

    def is_dead(self, host):
        return host in self.dead


# ---------------------------------------------------------------------------
# fan-in: interleave, per-origin tagging, gap marking
# ---------------------------------------------------------------------------

class _StallingSource(NumpySourceBlock):
    """One sequence whose gulp stream stalls mid-sequence for
    ``stall_secs`` after ``stall_after`` gulps — the fan-in must mark
    the origin gapped (not stall the merge) and resume it as a tagged
    continuation."""

    def __init__(self, gulps, hdr, stall_after, stall_secs, **kw):
        NumpySourceBlock.__init__(self, gulps, hdr, NT, **kw)
        self._n = 0
        self._stall_after = stall_after
        self._stall_secs = stall_secs

    def on_data(self, reader, ospans):
        self._n += 1
        if self._n == self._stall_after + 1:
            time.sleep(self._stall_secs)
        return NumpySourceBlock.on_data(self, reader, ospans)


class TestFanIn:
    def test_interleave_tags_and_gap(self):
        before = counters.snapshot()
        with bf.Pipeline() as p:
            h0 = simple_header([-1, NC], 'f32', name='origA',
                               gulp_nframe=NT)
            h1 = simple_header([-1, NC], 'f32', name='origB',
                               gulp_nframe=NT)
            src0 = NumpySourceBlock(_gulps(0, 6), h0, NT)
            src1 = _StallingSource(_gulps(1, 6), h1, stall_after=2,
                                   stall_secs=0.8)
            fin = fabric.FanInBlock([src0, src1],
                                    origins=['hostA', 'hostB'],
                                    gap_secs=0.25, link='cap')
            sink = GatherSink(fin)
        p.run()
        # every frame arrives despite the gap (a gap is delay
        # disclosure, not loss)
        frames = np.concatenate(sink.gulps, axis=0)
        for origin in (0, 1):
            sel = np.sort(frames[frames[:, 0] == origin][:, 1])
            assert sel.shape[0] == 6 * NT
            assert (sel == np.arange(6 * NT)).all()
        # per-origin tagging
        origins = {(h['_fabric']['origin'], h['_fabric']['link'])
                   for h in sink.headers}
        assert origins == {('hostA', 'cap'), ('hostB', 'cap')}
        # the stalled origin was marked gapped and resumed as a
        # tagged continuation carrying the _overload disclosure
        assert _delta(before, 'fabric.fanin.gapped') >= 1
        resumed = [h for h in sink.headers
                   if h['_fabric'].get('resumed')]
        assert resumed
        stamped = [h for h in sink.headers
                   if (h.get('_overload') or {}).get('fabric_gapped')]
        assert stamped
        gapinfo = stamped[-1]['_overload']['fabric_gapped']
        assert 'hostB' in gapinfo and gapinfo['hostB']['gaps'] >= 1

    def test_origin_ordinals(self):
        with bf.Pipeline() as p:
            h0 = simple_header([-1, NC], 'f32', name='s',
                               gulp_nframe=NT)
            src = _MultiSeqSource(3, h0)
            fin = fabric.FanInBlock([src], origins=['solo'])
            sink = GatherSink(fin)
        p.run()
        ordinals = [h['_fabric']['origin_seq'] for h in sink.headers]
        assert ordinals == [0, 1, 2]


# ---------------------------------------------------------------------------
# whole-host rejoin: session adoption + resume probe + ack ledger
# ---------------------------------------------------------------------------

class TestRejoin:
    def test_rejoin_replays_only_unacked(self, tmp_path):
        """A sender dies without MSG_END mid-stream; a NEW sender
        (fresh session) probes the receiver's committed frontier and
        replays only the remainder — the receiver adopts the session
        and the merged stream is exactly-once."""
        from bifrost_tpu.io.bridge import (RingSender, query_resume,
                                           connect)
        from bifrost_tpu.ring import Ring, RingWriter
        before = counters.snapshot()

        with bf.Pipeline() as prx:
            bsrc = bf.blocks.bridge_source('127.0.0.1', 0,
                                           adopt_sessions=True)
            sink = GatherSink(bsrc)
        rx_thread = threading.Thread(target=prx.run)
        rx_thread.start()
        try:
            all_gulps = _gulps(7, 6)
            hdr = simple_header([-1, NC], 'f32', name='stream0',
                                gulp_nframe=NT)

            def send(gulps, end, expect_fail=False):
                ring = Ring(space='system', name=None)
                errors = []

                def pump():
                    s = RingSender(
                        ring,
                        dial=lambda: [connect('127.0.0.1',
                                              bsrc.port)])
                    try:
                        s.run()
                    except Exception as exc:
                        errors.append(exc)
                t = threading.Thread(target=pump)
                writer = RingWriter(ring)
                wseq = writer.begin_sequence(dict(hdr), NT,
                                             buf_nframe=8 * NT)
                t.start()
                for g in gulps:
                    span = wseq.reserve(NT)
                    span.data.as_numpy()[:] = g
                    span.commit(NT)
                    span.close()
                if end:
                    wseq.end()
                    ring.end_writing()
                    t.join(timeout=30)
                else:
                    # whole-host death: poison without MSG_END — the
                    # receiver must NOT treat the stream as complete
                    time.sleep(0.5)     # let the spans flush + ack
                    ring.poison(RuntimeError('host died'))
                    t.join(timeout=30)
                assert not t.is_alive()
                if expect_fail:
                    assert errors, 'sender should have died unclean'
                return errors

            # run 1: 3 of 6 gulps, then die without MSG_END
            send(all_gulps[:3], end=False, expect_fail=True)
            # rejoin probe: the receiver reports its committed
            # frontier for the sequence
            frontier = query_resume('127.0.0.1', bsrc.port,
                                    timeout=10.0)
            assert frontier.get('stream0') == 3 * NT
            # run 2 (new session): replay ONLY the unacked remainder
            start = frontier['stream0'] // NT
            errs = send(all_gulps[start:], end=True)
            assert not errs
            rx_thread.join(timeout=30)
            assert not rx_thread.is_alive()
        finally:
            if rx_thread.is_alive():
                prx.shutdown()
                rx_thread.join(timeout=10)
        frames = np.concatenate(sink.gulps, axis=0)
        idx = np.sort(frames[:, 1])
        assert (idx == np.arange(6 * NT)).all()       # exactly once
        assert _delta(before, 'bridge.rx.sessions_adopted') == 1

    def test_ack_ledger_durable(self, tmp_path, monkeypatch):
        monkeypatch.setenv('BF_FABRIC_STATE', str(tmp_path))
        led = fabric.AckLedger('fab', 'h', 'l')
        assert not led.has_history
        led.note_acked('s0', 0, 16, 1024)
        led.note_acked('s0', 16, 16, 1024)
        led.note_acked('s0', 0, 16, 1024)   # re-ack: frontier is max
        led.note_shed(2, 512)
        led.save(force=True)
        led2 = fabric.AckLedger('fab', 'h', 'l')
        assert led2.has_history
        assert led2.acked_frames('s0') == 32
        assert led2.shed_gulps == 2 and led2.shed_bytes == 512


# ---------------------------------------------------------------------------
# membership + affinity + identity
# ---------------------------------------------------------------------------

class TestMembership:
    def test_death_and_rejoin(self):
        ports = free_ports(2)
        spec = fabric.FabricSpec('m', hosts={
            'a': {'address': '127.0.0.1', 'control_port': ports[0]},
            'b': {'address': '127.0.0.1', 'control_port': ports[1]},
        }, links={'l': {'kind': 'pipe', 'src': 'a', 'dst': 'b',
                        'port': 1}})
        before = counters.snapshot()
        ma = fabric.Membership(spec, 'a').start()
        mb = fabric.Membership(spec, 'b').start()
        try:
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and \
                    not ma.peers_snapshot()['b']['alive']:
                time.sleep(0.05)
            assert ma.peers_snapshot()['b']['alive']
            # a never-heartbeating peer is 'unknown', not dead — only
            # a peer that WAS alive can die
            assert not ma.is_dead('b')
            mb.stop()
            # the DETECTION (and its counter) lands on the membership
            # thread's next tick — poll the counted event, not the
            # client-side time math
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and \
                    _delta(before, 'fabric.peers.dead') < 1:
                time.sleep(0.05)
            assert ma.is_dead('b')
            assert _delta(before, 'fabric.peers.dead') >= 1
            # rejoin: a fresh membership on the same control port
            mb = fabric.Membership(spec, 'b').start()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and \
                    _delta(before, 'fabric.peers.rejoined') < 1:
                time.sleep(0.05)
            assert not ma.is_dead('b')
            assert _delta(before, 'fabric.peers.rejoined') >= 1
        finally:
            ma.stop()
            mb.stop()


class TestAffinityAndIdentity:
    def test_affinity_applied_or_skipped(self):
        before = counters.snapshot()
        try:
            cores = sorted(os.sched_getaffinity(0))
        except AttributeError:
            cores = []
        host = fabric.HostSpec('h', cores=cores or [0])
        with bf.Pipeline() as p:
            src = NumpySourceBlock(
                _gulps(0, 1), simple_header([-1, NC], 'f32',
                                            gulp_nframe=NT), NT)
            GatherSink(src)
        state = fabric.apply_affinity(host, p)
        assert state in ('applied', 'skipped')
        key = 'fabric.affinity.%s' % state
        assert _delta(before, key) == 1
        if state == 'applied':
            assert all(b.core is not None for b in p.blocks)

    def test_no_cores_is_none(self):
        assert fabric.apply_affinity(fabric.HostSpec('h')) == 'none'

    def test_proclog_identity_layout(self, tmp_path, monkeypatch):
        monkeypatch.setenv('BF_PROCLOG_DIR', str(tmp_path))
        proclog.set_identity('nodeA', 'capture')
        try:
            entry = proclog.instance_name()
            assert entry == '%d@nodeA.capture' % os.getpid()
            assert proclog.entry_pid(entry) == os.getpid()
            assert proclog.entry_host(entry) == 'nodeA'
            log = proclog.ProcLog('fabric/testlog')
            log.update({'k': 1}, force=True)
            loaded = proclog.load_by_pid(os.getpid())
            assert loaded['fabric']['testlog']['k'] == 1
            # a full instance entry resolves too
            assert proclog.load_by_pid(entry)
        finally:
            proclog.set_identity(None)

    def test_identity_in_snapshot(self):
        from bifrost_tpu import telemetry
        proclog.set_identity('nodeB', 'reduce')
        try:
            ident = telemetry.snapshot()['identity']
            assert ident['fabric_host'] == 'nodeB'
            assert ident['fabric_role'] == 'reduce'
            assert ident['pid'] == os.getpid()
        finally:
            proclog.set_identity(None)


# ---------------------------------------------------------------------------
# overload stamp merge
# ---------------------------------------------------------------------------

class TestIntegration:
    def test_overload_stamp_merges_upstream_fields(self):
        """A drop-policy ring's own _overload stamp must MERGE with an
        upstream stamp riding the header (the fan-in's fabric_gapped
        map), not replace it."""
        from bifrost_tpu.ring import Ring, RingWriter
        ring = Ring(space='system', name=None)
        ring.set_overload_policy('drop_oldest')
        hdr = simple_header([-1, NC], 'f32', gulp_nframe=NT)
        hdr['_overload'] = {'fabric_gapped': {'x': {'gaps': 1}}}
        writer = RingWriter(ring)
        wseq = writer.begin_sequence(hdr, NT, buf_nframe=4 * NT)
        stamped = wseq.header['_overload']
        assert stamped['fabric_gapped'] == {'x': {'gaps': 1}}
        assert stamped['policy'] == 'drop_oldest'
        wseq.end()
        ring.end_writing()
