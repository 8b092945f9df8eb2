"""DCN ring bridge tests: ring -> TCP -> ring over loopback (reference
analogue: the RDMA RingSender/RingReceiver, rdma.py:99-203)."""

import socket
import threading

import numpy as np

from bifrost_tpu.ring import Ring
from bifrost_tpu.io.bridge import RingSender, RingReceiver, _send_msg
from tests.util import simple_header


def test_ring_bridge_loopback():
    src_ring = Ring(space='system', name='bridge_src')
    dst_ring = Ring(space='system', name='bridge_dst')

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(('127.0.0.1', 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    rng = np.random.RandomState(0)
    data = rng.randn(24, 6).astype(np.float32)
    hdr = simple_header([-1, 6], 'f32', name='bridged', gulp_nframe=8)

    def writer():
        with src_ring.begin_writing() as wr:
            with wr.begin_sequence(hdr, gulp_nframe=8,
                                   buf_nframe=24) as seq:
                for k in range(3):
                    with seq.reserve(8) as span:
                        span.data.as_numpy()[...] = data[k * 8:(k + 1) * 8]
                        span.commit(8)

    def sender():
        conn = socket.create_connection(('127.0.0.1', port))
        RingSender(src_ring, conn, gulp_nframe=8).run()
        conn.close()

    def receiver():
        conn, _ = srv.accept()
        RingReceiver(conn, dst_ring).run()
        conn.close()

    threads = [threading.Thread(target=f)
               for f in (receiver, writer, sender)]
    for t in threads:
        t.start()

    got = []
    names = []
    for seq in dst_ring.read(guarantee=True):
        names.append(seq.header['name'])
        for span in seq.read(8):
            got.append(np.array(span.data.as_numpy(), copy=True))
    for t in threads:
        t.join()
    srv.close()
    out = np.concatenate(got, axis=0)
    np.testing.assert_array_equal(out, data)
    assert names == ['bridged']


def test_ring_bridge_multi_sequence_ringlets():
    """Bridge a 2-ringlet stream across two sequences."""
    src_ring = Ring(space='system', name='bridge_src2')
    dst_ring = Ring(space='system', name='bridge_dst2')
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(('127.0.0.1', 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    rng = np.random.RandomState(1)
    datasets = [rng.randn(2, 8, 3).astype(np.float32) for _ in range(2)]

    def writer():
        with src_ring.begin_writing() as wr:
            for s, d in enumerate(datasets):
                hdr = simple_header([2, -1, 3], 'f32',
                                    labels=['beam', 'time', 'chan'],
                                    name='seq%d' % s, gulp_nframe=8)
                hdr['time_tag'] = s
                with wr.begin_sequence(hdr, gulp_nframe=8,
                                       buf_nframe=24) as seq:
                    with seq.reserve(8) as span:
                        span.data.as_numpy()[...] = d
                        span.commit(8)

    def sender():
        conn = socket.create_connection(('127.0.0.1', port))
        RingSender(src_ring, conn, gulp_nframe=8).run()
        conn.close()

    def receiver():
        conn, _ = srv.accept()
        RingReceiver(conn, dst_ring).run()
        conn.close()

    threads = [threading.Thread(target=f)
               for f in (receiver, writer, sender)]
    for t in threads:
        t.start()
    got = {}
    for seq in dst_ring.read(guarantee=True):
        name = seq.header['name']
        for span in seq.read(8):
            got[name] = np.array(span.data.as_numpy(), copy=True)
    for t in threads:
        t.join()
    srv.close()
    for s, d in enumerate(datasets):
        np.testing.assert_array_equal(got['seq%d' % s], d)


def test_ring_bridge_cross_process():
    """Sender in a SEPARATE PROCESS (the real multi-host topology):
    ring -> TCP -> ring across a process boundary."""
    import subprocess
    import sys
    import os

    dst_ring = Ring(space='system', name='bridge_xproc_dst')
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(('127.0.0.1', 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    SENDER = (
        "import sys, socket, numpy as np\n"
        "sys.path.insert(0, %r)\n"
        "sys.path.insert(0, %r)\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from bifrost_tpu.ring import Ring\n"
        "from bifrost_tpu.io.bridge import RingSender\n"
        "from util import simple_header\n"
        "import threading\n"
        "port = int(sys.argv[1])\n"
        "ring = Ring(space='system', name='xproc_src')\n"
        "hdr = simple_header([-1, 6], 'f32', name='xproc',\n"
        "                    gulp_nframe=8)\n"
        "rng = np.random.RandomState(3)\n"
        "data = rng.randn(24, 6).astype(np.float32)\n"
        "def writer():\n"
        "    with ring.begin_writing() as wr:\n"
        "        with wr.begin_sequence(hdr, gulp_nframe=8,\n"
        "                               buf_nframe=32) as seq:\n"
        "            for k in range(3):\n"
        "                with seq.reserve(8) as span:\n"
        "                    span.data.as_numpy()[...] = \\\n"
        "                        data[k * 8:(k + 1) * 8]\n"
        "                    span.commit(8)\n"
        "t = threading.Thread(target=writer)\n"
        "t.start()\n"
        "sock = socket.create_connection(('127.0.0.1', port))\n"
        "RingSender(ring, sock).run()\n"
        "t.join()\n"
        "sock.close()\n"
    ) % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
         os.path.dirname(os.path.abspath(__file__)))

    proc = subprocess.Popen([sys.executable, '-c', SENDER, str(port)])
    srv.settimeout(30)
    try:
        conn, _ = srv.accept()
        got = []

        def reader():
            for seq in dst_ring.read(guarantee=True):
                assert seq.header['name'] == 'xproc'
                for span in seq.read(8):
                    got.append(np.array(span.data.as_numpy(),
                                        copy=True))

        rt = threading.Thread(target=reader)
        rt.start()
        RingReceiver(conn, dst_ring).run()
        rt.join(15)
        assert not rt.is_alive()
        out = np.concatenate(got, axis=0)
        rng = np.random.RandomState(3)
        expect = rng.randn(24, 6).astype(np.float32)
        np.testing.assert_array_equal(out, expect)
        conn.close()
    finally:
        try:
            proc.wait(20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        srv.close()


# ---------------------------------------------------------------------------
# wire v2: zero-copy framing, windowed pipelining, striping
# (docs/networking.md)
# ---------------------------------------------------------------------------

import errno
import pytest

from bifrost_tpu.io.bridge import (BridgeListener, BridgeProtocolError,
                                   connect, connect_striped,
                                   MSG_HEADER, MSG_SPAN, MSG_END_SEQ,
                                   MSG_END)
from bifrost_tpu.header_standard import (serialize_header,
                                         deserialize_header)
from bifrost_tpu.ring import RingPoisonedError, _tensor_info


def _gather(ring, gulp):
    """Read every sequence off ``ring``; returns {name: array}
    (gulps concatenated along the header's time axis)."""
    got = {}
    for seq in ring.read(guarantee=True):
        taxis = seq.header['_tensor']['shape'].index(-1)
        chunks = []
        for span in seq.read(gulp):
            chunks.append(np.array(span.data.as_numpy(), copy=True))
        got[seq.header['name']] = np.concatenate(chunks, axis=taxis) \
            if chunks else None
    return got


def _roundtrip(datasets, hdr_fn, gulp, sender_kw=None, receiver_kw=None,
               nstreams=1, ring_tag='rt'):
    """Write ``datasets`` (one per sequence) into a source ring, bridge
    them over loopback, and return {seq_name: received array}."""
    src = Ring(space='system', name='bsrc_%s' % ring_tag)
    dst = Ring(space='system', name='bdst_%s' % ring_tag)
    lst = BridgeListener('127.0.0.1', 0)
    out = {}
    errors = []

    # buffer the WHOLE stream: the unthrottled test writer must not
    # lap the ring before the sender's guarantee registers (a startup
    # race that in-pipeline topologies eliminate via BridgeSink's
    # pre-barrier prime)
    total_frames = sum(d.shape[hdr_fn(s)['_tensor']['shape'].index(-1)]
                       for s, d in enumerate(datasets))
    # ... and the destination ring likewise.  RingReceiver opens its
    # sequences 3 gulps deep, and _gather's guarantee registers only
    # once it has seen the first of them: until then nothing holds
    # the receiver back, and on a loaded host it laps the reader (a
    # pipeline's downstream block is subject to the same rule; its
    # rings are sized by its readers before data flows).  Rings only
    # grow, so the receiver's own request leaves this size standing.
    info = _tensor_info(hdr_fn(0))
    dst.resize(gulp * info['frame_nbyte'],
               (total_frames + gulp) * info['frame_nbyte'],
               info['nringlet'])

    def writer():
        with src.begin_writing() as wr:
            for s, data in enumerate(datasets):
                hdr = hdr_fn(s)
                taxis = hdr['_tensor']['shape'].index(-1)
                nframe = data.shape[taxis]
                with wr.begin_sequence(hdr, gulp_nframe=gulp,
                                       buf_nframe=total_frames + gulp
                                       ) as seq:
                    off = 0
                    while off < nframe:
                        n = min(gulp, nframe - off)
                        with seq.reserve(n) as span:
                            idx = [slice(None)] * data.ndim
                            idx[taxis] = slice(off, off + n)
                            span.data.as_numpy()[...] = data[tuple(idx)]
                            span.commit(n)
                        off += n

    def sender():
        try:
            socks = connect_striped('127.0.0.1', lst.port, nstreams)
            s = RingSender(src, socks, gulp_nframe=gulp,
                           **(sender_kw or {}))
            s.run()
            s.close()
        except BaseException as exc:    # surfaced by the caller
            errors.append(exc)
            src.poison(exc)

    def receiver():
        try:
            r = RingReceiver(lst, dst, **(receiver_kw or {}))
            r.run()
        except BaseException as exc:
            errors.append(exc)

    threads = [threading.Thread(target=f, daemon=True)
               for f in (receiver, writer, sender)]
    for t in threads:
        t.start()
    out = _gather(dst, gulp)
    for t in threads:
        t.join(30)
    lst.close()
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    return out


def test_bridge_windowed_pipelining():
    """window>1: spans stay acquired until acked; stream must still be
    byte-identical."""
    rng = np.random.RandomState(7)
    data = rng.randn(64, 5).astype(np.float32)
    out = _roundtrip(
        [data], lambda s: simple_header([-1, 5], 'f32', name='w4',
                                        gulp_nframe=8),
        gulp=8, sender_kw={'window': 4}, ring_tag='win')
    np.testing.assert_array_equal(out['w4'], data)


def test_bridge_striping_reassembly():
    """3 striped connections carry interleaved frames; the receiver
    reassembles them in sequence-number order."""
    rng = np.random.RandomState(8)
    data = rng.randn(96, 7).astype(np.float32)
    out = _roundtrip(
        [data], lambda s: simple_header([-1, 7], 'f32', name='striped',
                                        gulp_nframe=8),
        gulp=8, sender_kw={'window': 6}, nstreams=3, ring_tag='str')
    np.testing.assert_array_equal(out['striped'], data)


def test_bridge_partial_final_gulp():
    """A sequence whose frame count is not a gulp multiple ships a
    short final span."""
    rng = np.random.RandomState(9)
    data = rng.randn(20, 3).astype(np.float32)
    out = _roundtrip(
        [data], lambda s: simple_header([-1, 3], 'f32', name='part',
                                        gulp_nframe=8),
        gulp=8, sender_kw={'window': 2}, ring_tag='part')
    np.testing.assert_array_equal(out['part'], data)


def test_bridge_strided_multi_ringlet_v2():
    """Multi-ringlet (strided span) streams scatter per lane on both
    ends, windowed and striped."""
    rng = np.random.RandomState(10)
    datasets = [rng.randn(3, 16, 4).astype(np.float32)
                for _ in range(2)]

    def hdr_fn(s):
        h = simple_header([3, -1, 4], 'f32',
                          labels=['beam', 'time', 'chan'],
                          name='rl%d' % s, gulp_nframe=8)
        h['time_tag'] = s
        return h

    out = _roundtrip(datasets, hdr_fn, gulp=8,
                     sender_kw={'window': 3}, nstreams=2,
                     ring_tag='ringlets')
    for s, d in enumerate(datasets):
        np.testing.assert_array_equal(out['rl%d' % s], d)


def test_bridge_crc_roundtrip():
    """CRC32 integrity word verified per span."""
    rng = np.random.RandomState(11)
    data = rng.randn(32, 6).astype(np.float32)
    out = _roundtrip(
        [data], lambda s: simple_header([-1, 6], 'f32', name='crc',
                                        gulp_nframe=8),
        gulp=8, sender_kw={'window': 2, 'crc': True}, ring_tag='crc')
    np.testing.assert_array_equal(out['crc'], data)
    from bifrost_tpu.telemetry import counters
    assert counters.get('bridge.rx.crc_errors') == 0


def test_bridge_v1_compat_and_naive():
    """A v2 receiver auto-detects and round-trips the legacy v1 wire
    (protocol=1) and the seed implementation's copying loop
    (naive=True) byte-identically."""
    rng = np.random.RandomState(12)
    data = rng.randn(24, 6).astype(np.float32)
    for tag, kw in (('v1', {'protocol': 1}), ('naive', {'naive': True})):
        out = _roundtrip(
            [data], lambda s: simple_header([-1, 6], 'f32',
                                            name='compat',
                                            gulp_nframe=8),
            gulp=8, sender_kw=kw, ring_tag='compat_%s' % tag)
        np.testing.assert_array_equal(out['compat'], data)


def test_bridge_macro_gulp_frames():
    """A macro-gulp aware sender (gulp_batch=K) ships K gulps per
    frame; the receiver's ring still counts LOGICAL gulps and the
    stream stays byte-identical (the PR-4 macro stream contract)."""
    from bifrost_tpu.telemetry import counters
    rng = np.random.RandomState(13)
    raw = np.zeros((64, 2, 8), dtype=np.dtype([('re', 'i1'),
                                               ('im', 'i1')]))
    raw['re'] = rng.randint(-64, 64, raw.shape)
    raw['im'] = rng.randint(-64, 64, raw.shape)

    def hdr_fn(s):
        return simple_header([-1, 2, 8], 'ci8',
                             labels=['time', 'pol', 'fine'],
                             name='macro', gulp_nframe=8)

    counters.reset()
    out = _roundtrip([raw], hdr_fn, gulp=8,
                     sender_kw={'window': 4, 'gulp_batch': 4},
                     ring_tag='macro')
    np.testing.assert_array_equal(out['macro'], raw)
    # 64 frames / (8-frame gulps) = 8 logical gulps, shipped as 2
    # macro frames of K=4 — the receiver credits logical gulps
    dst_gulps = counters.get('ring.bdst_macro.gulps')
    assert dst_gulps == 8, dst_gulps
    assert counters.get('bridge.tx.spans') == 2


def test_bridge_k1_default_roundtrips_macro_stream():
    """Acceptance: the DEFAULT path (single stream, window=1, CRC off,
    K=1 unbatched framing) round-trips the PR-4 macro test stream
    shapes (ci8 structured gulps) byte-identically."""
    rng = np.random.RandomState(3)
    raw = np.zeros((64, 2, 16), dtype=np.dtype([('re', 'i1'),
                                                ('im', 'i1')]))
    raw['re'] = rng.randint(-64, 64, raw.shape)
    raw['im'] = rng.randint(-64, 64, raw.shape)
    out = _roundtrip(
        [raw], lambda s: simple_header([-1, 2, 16], 'ci8',
                                       labels=['time', 'pol', 'fine'],
                                       name='k1', gulp_nframe=16),
        gulp=16, ring_tag='k1macro')
    np.testing.assert_array_equal(out['k1'], raw)


def test_bridge_span_identity_survives_sender_gulp_override(
        monkeypatch, tmp_path):
    """The (trace, seq, gulp) identity joining tx and rx spans across
    hosts must come from the SHIPPED header's gulp_nframe on both
    sides: a sender reading the ring in bigger batches
    (gulp_nframe override) must not skew the tx-side gulp index."""
    from bifrost_tpu.header_standard import ensure_trace_context
    from bifrost_tpu.telemetry import spans
    monkeypatch.setenv('BF_TRACE_FILE', str(tmp_path / 'ids.json'))
    spans.reconfigure()
    spans.reset()
    try:
        src = Ring(space='system', name='bsrc_gmix')
        dst = Ring(space='system', name='bdst_gmix')
        lst = BridgeListener('127.0.0.1', 0)
        data = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)
        hdr = simple_header([-1, 4], 'f32', name='gmix',
                            gulp_nframe=8)
        tid = ensure_trace_context(hdr)['id']

        def writer():
            with src.begin_writing() as wr:
                with wr.begin_sequence(hdr, gulp_nframe=8,
                                       buf_nframe=40) as seq:
                    for k in range(4):
                        with seq.reserve(8) as span:
                            span.data.as_numpy()[...] = \
                                data[k * 8:(k + 1) * 8]
                            span.commit(8)

        def sender():
            conn = socket.create_connection(('127.0.0.1', lst.port))
            # reads the ring 16 frames at a time — TWICE the header's
            # logical gulp
            s = RingSender(src, [conn], gulp_nframe=16)
            s.run()
            s.close()

        def receiver():
            RingReceiver(lst, dst).run()

        threads = [threading.Thread(target=f, daemon=True)
                   for f in (receiver, writer, sender)]
        for t in threads:
            t.start()
        out = _gather(dst, 8)
        for t in threads:
            t.join(30)
        lst.close()
        np.testing.assert_array_equal(out['gmix'], data)

        evs = [ev for _t, ev in spans.events()
               if ev[0].startswith('bridge.')]
        tx = {(ev[4]['trace'], ev[4]['seq'], ev[4]['gulp'])
              for ev in evs if ev[0].startswith('bridge.tx.')}
        rx = {(ev[4]['trace'], ev[4]['seq'], ev[4]['gulp'])
              for ev in evs if ev[0].startswith('bridge.rx.')}
        # 32 frames in two 16-frame wire spans: header-logical gulp
        # indices 0 and 2 on BOTH timelines
        assert {i[2] for i in tx} == {0, 2}
        assert tx == rx
        assert all(i[0] == tid for i in tx)
    finally:
        monkeypatch.delenv('BF_TRACE_FILE', raising=False)
        spans.reconfigure()
        spans.reset()


def test_header_numpy_values_roundtrip():
    """serialize_header coerces numpy scalars/arrays; a header
    transform that injects them must bridge cleanly."""
    hdr = {'np_int': np.int64(7), 'np_float': np.float32(2.5),
           'np_arr': np.arange(3, dtype=np.int32), 'plain': 'x'}
    back = deserialize_header(serialize_header(hdr))
    assert back['np_int'] == 7
    assert abs(back['np_float'] - 2.5) < 1e-6
    assert back['np_arr'] == [0, 1, 2]
    assert back['plain'] == 'x'
    # a bare json.dumps on the same header throws — the satellite bug
    import json as json_mod
    with pytest.raises(TypeError):
        json_mod.dumps(hdr)

    # end-to-end: bridge a ring whose header transform adds numpy
    # values (ring_view applies transforms on the read side)
    from bifrost_tpu.ring import ring_view
    rng = np.random.RandomState(14)
    data = rng.randn(16, 4).astype(np.float32)
    src = Ring(space='system', name='bsrc_nphdr')
    dst = Ring(space='system', name='bdst_nphdr')
    view = ring_view(src, lambda h: dict(h, cal_gain=np.float64(1.5),
                                         chan_map=np.arange(2)))
    lst = BridgeListener('127.0.0.1', 0)

    def writer():
        with src.begin_writing() as wr:
            hdr2 = simple_header([-1, 4], 'f32', name='nphdr',
                                 gulp_nframe=8)
            with wr.begin_sequence(hdr2, gulp_nframe=8,
                                   buf_nframe=24) as seq:
                with seq.reserve(16) as span:
                    span.data.as_numpy()[...] = data
                    span.commit(16)

    def sender():
        sock = connect('127.0.0.1', lst.port)
        RingSender(view, sock, gulp_nframe=8).run()
        sock.close()

    recv_hdrs = []

    def receiver():
        RingReceiver(lst, dst).run()

    threads = [threading.Thread(target=f, daemon=True)
               for f in (receiver, writer, sender)]
    for t in threads:
        t.start()
    got = []
    for seq in dst.read(guarantee=True):
        recv_hdrs.append(dict(seq.header))
        for span in seq.read(8):
            got.append(np.array(span.data.as_numpy(), copy=True))
    for t in threads:
        t.join(20)
    lst.close()
    np.testing.assert_array_equal(np.concatenate(got, axis=0), data)
    assert recv_hdrs[0]['cal_gain'] == 1.5
    assert recv_hdrs[0]['chan_map'] == [0, 1]


# ---------------------------------------------------------------------------
# protocol errors, poison propagation, reconnect-and-resume
# ---------------------------------------------------------------------------

def _poisoned(ring):
    return ring.poisoned


def test_bridge_unknown_message_type_raises():
    """Satellite: unknown message types must raise BridgeProtocolError
    (naming the type), not be silently ignored; the destination ring
    is poisoned."""
    dst = Ring(space='system', name='bdst_unknown')
    lst = BridgeListener('127.0.0.1', 0)
    res = []

    def receiver():
        try:
            RingReceiver(lst, dst).run()
        except BridgeProtocolError as exc:
            res.append(exc)

    t = threading.Thread(target=receiver, daemon=True)
    t.start()
    sock = connect('127.0.0.1', lst.port)
    _send_msg(sock, 42, b'bogus')
    t.join(10)
    sock.close()
    lst.close()
    assert res and '42' in str(res[0])
    assert _poisoned(dst)


def test_bridge_span_before_header_raises():
    """Satellite: MSG_SPAN before any MSG_HEADER is a protocol error
    (the seed implementation crashed with NameError)."""
    dst = Ring(space='system', name='bdst_nohdr')
    lst = BridgeListener('127.0.0.1', 0)
    res = []

    def receiver():
        try:
            RingReceiver(lst, dst).run()
        except BridgeProtocolError as exc:
            res.append(exc)

    t = threading.Thread(target=receiver, daemon=True)
    t.start()
    sock = connect('127.0.0.1', lst.port)
    _send_msg(sock, MSG_SPAN, b'\x00' * 64)
    t.join(10)
    sock.close()
    lst.close()
    assert res and 'MSG_HEADER' in str(res[0])
    assert _poisoned(dst)


def test_bridge_sender_death_poisons_receiver_ring():
    """A connection that dies WITHOUT a clean MSG_END poisons the
    destination ring: downstream readers get RingPoisonedError, not a
    silently truncated stream."""
    dst = Ring(space='system', name='bdst_death')
    lst = BridgeListener('127.0.0.1', 0)
    res = []

    def receiver():
        try:
            RingReceiver(lst, dst).run()
        except ConnectionError as exc:
            res.append(exc)

    t = threading.Thread(target=receiver, daemon=True)
    t.start()
    sock = connect('127.0.0.1', lst.port)
    hdr = simple_header([-1, 4], 'f32', name='dead', gulp_nframe=8)
    _send_msg(sock, MSG_HEADER, serialize_header(hdr))
    _send_msg(sock, MSG_SPAN, b'\x01' * (8 * 4 * 4))
    sock.close()             # mid-stream death, no MSG_END
    t.join(10)
    lst.close()
    assert res, "receiver did not surface the dead sender"
    assert _poisoned(dst)
    with pytest.raises(RingPoisonedError):
        for seq in dst.read(guarantee=True):
            for span in seq.read(8):
                pass


class _FlakySock(object):
    """Socket proxy whose sendmsg starts failing after N calls —
    deterministic mid-stream link death for the reconnect test."""

    def __init__(self, sock, fail_after):
        self._sock = sock
        self._calls = 0
        self._fail_after = fail_after

    def sendmsg(self, bufs):
        self._calls += 1
        if self._calls > self._fail_after:
            raise OSError(errno.ECONNRESET, 'injected link death')
        return self._sock.sendmsg(bufs)

    def __getattr__(self, name):
        return getattr(self._sock, name)


def test_bridge_reconnect_and_resume():
    """Sender link dies mid-stream; the sender redials (reconnect
    callable), retransmits unacked frames, and the receiver RESUMES —
    dropping duplicates by sequence number — to a byte-identical
    stream."""
    rng = np.random.RandomState(15)
    data = rng.randn(48, 4).astype(np.float32)
    src = Ring(space='system', name='bsrc_reconn')
    dst = Ring(space='system', name='bdst_reconn')
    lst = BridgeListener('127.0.0.1', 0)
    errors = []
    redials = []

    def writer():
        with src.begin_writing() as wr:
            hdr = simple_header([-1, 4], 'f32', name='reconn',
                                gulp_nframe=8)
            with wr.begin_sequence(hdr, gulp_nframe=8,
                                   buf_nframe=64) as seq:
                for k in range(6):
                    with seq.reserve(8) as span:
                        span.data.as_numpy()[...] = \
                            data[k * 8:(k + 1) * 8]
                        span.commit(8)

    def reconnect():
        redials.append(1)
        return [connect('127.0.0.1', lst.port)]

    def sender():
        try:
            first = _FlakySock(connect('127.0.0.1', lst.port),
                               fail_after=4)
            s = RingSender(src, [first], gulp_nframe=8, window=4,
                           reconnect=reconnect, reconnect_max=3)
            s.run()
            s.close()
        except BaseException as exc:
            errors.append(exc)
            src.poison(exc)

    def receiver():
        r = RingReceiver(lst, dst, poison_on_error=False)
        while True:
            try:
                r.run()
                return
            except BridgeProtocolError as exc:
                errors.append(exc)   # a protocol error is a test bug
                return
            except (ConnectionError, OSError):
                continue             # re-accept and resume

    threads = [threading.Thread(target=f, daemon=True)
               for f in (receiver, writer, sender)]
    for t in threads:
        t.start()
    out = _gather(dst, 8)
    for t in threads:
        t.join(30)
    lst.close()
    assert not errors, errors
    assert redials, "the flaky link never triggered a redial"
    np.testing.assert_array_equal(out['reconn'], data)


# ---------------------------------------------------------------------------
# pipeline blocks: BridgeSink / BridgeSource under supervision
# ---------------------------------------------------------------------------

def test_bridge_blocks_pipeline():
    """Full block-level topology: NumpySource -> BridgeSink ==TCP==>
    BridgeSource -> GatherSink across two pipelines (the two-host
    shape), striped + windowed, with bridge telemetry observable."""
    import bifrost_tpu as bf
    from tests.util import NumpySourceBlock, GatherSink
    from bifrost_tpu.telemetry import counters

    rng = np.random.RandomState(16)
    NT = 16
    gulps = [rng.randn(NT, 6).astype(np.float32) for _ in range(5)]
    hdr = simple_header([-1, 6], 'f32', name='blkbridge',
                        gulp_nframe=NT)

    counters.reset()
    with bf.Pipeline() as prx:
        bsrc = bf.blocks.bridge_source('127.0.0.1', 0)
        sink = GatherSink(bsrc)
    with bf.Pipeline() as ptx:
        nsrc = NumpySourceBlock(gulps, hdr, gulp_nframe=NT)
        bf.blocks.bridge_sink(nsrc, '127.0.0.1', bsrc.port,
                              nstreams=2, window=3)

    rx_errors = []

    def run_rx():
        try:
            prx.run()
        except BaseException as exc:
            rx_errors.append(exc)

    rx_thread = threading.Thread(target=run_rx, daemon=True)
    rx_thread.start()
    ptx.run()
    rx_thread.join(30)
    assert not rx_thread.is_alive()
    assert not rx_errors, rx_errors
    np.testing.assert_array_equal(sink.result(),
                                  np.concatenate(gulps, axis=0))
    assert counters.get('bridge.tx.spans') == 5
    assert counters.get('bridge.rx.spans') == 5
    assert counters.get('bridge.tx.bytes') == \
        counters.get('bridge.rx.bytes')


def test_bridge_v1_sender_failure_withholds_end():
    """A v1 sender whose source ring dies mid-stream must NOT send a
    clean MSG_END: the receiver sees the connection drop and poisons
    its destination ring (truncation never looks complete)."""
    src = Ring(space='system', name='bsrc_v1fail')
    dst = Ring(space='system', name='bdst_v1fail')
    lst = BridgeListener('127.0.0.1', 0)
    res = []

    def writer():
        with src.begin_writing() as wr:
            hdr = simple_header([-1, 4], 'f32', name='v1fail',
                                gulp_nframe=8)
            with wr.begin_sequence(hdr, gulp_nframe=8,
                                   buf_nframe=24) as seq:
                with seq.reserve(8) as span:
                    span.data.as_numpy()[...] = 1.0
                    span.commit(8)
        # upstream failure after one gulp
        src.poison(RuntimeError("producer died"))

    def sender():
        sock = connect('127.0.0.1', lst.port)
        try:
            RingSender(src, sock, gulp_nframe=8, protocol=1).run()
        except RingPoisonedError as exc:
            res.append(('sender', exc))
        finally:
            sock.close()

    def receiver():
        try:
            RingReceiver(lst, dst).run()
        except ConnectionError as exc:
            res.append(('receiver', exc))

    threads = [threading.Thread(target=f, daemon=True)
               for f in (receiver, writer, sender)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
    lst.close()
    kinds = {k for k, _ in res}
    assert kinds == {'sender', 'receiver'}, res
    assert dst.poisoned, \
        "truncated v1 stream was presented as a clean end"
