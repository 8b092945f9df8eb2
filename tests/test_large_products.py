"""Large products through the pipeline (docs/transfer.md, "Depth by
bytes"; PERF.md section 6, PR 28): ring depth follows from a span's
bytes, the correlator integrates in place and hands its product on as
the two float32 planes it integrated in (PR 31), the dispatch-ahead
queue and the fills in flight are bounded by bytes.  The sizes here
are small; the rule's one constant is patched down to them."""

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu import memory
from bifrost_tpu.ring import Ring
from bifrost_tpu.telemetry import counters

from util import NumpySourceBlock, GatherSink, simple_header


# ---------------------------------------------------------------------------
# ring depth from span bytes
# ---------------------------------------------------------------------------

def test_the_rule_leaves_gulps_under_a_gibibyte_three_deep():
    gpuspec, xcorr_in, product = 268435456, 536870912, 2147483648
    assert memory.span_depth(gpuspec, 3) == 3
    assert memory.span_depth(xcorr_in, 3) == 3
    assert memory.span_depth(memory.LARGE_SPAN_BYTES - 1, 3) == 3
    assert memory.span_depth(memory.LARGE_SPAN_BYTES, 3) == 2
    assert memory.span_depth(product, 3) == 2
    assert memory.span_depth(product, 1) == 1       # never deeper
    # four gpuspec products in flight, one visibility product
    assert 4 * gpuspec <= memory.INFLIGHT_BYTES < 2 * product


@pytest.mark.parametrize('space', ['system', 'tpu'])
@pytest.mark.parametrize('large', [False, True])
def test_reader_depth_follows_span_bytes(space, large, monkeypatch):
    """A reader asks for three spans of a small gulp and two of a
    large one, in either space, and the gauges say what that holds."""
    counters.reset()
    span = 8 * 64 * 4                       # 8 frames of 64 float32
    monkeypatch.setattr(memory, 'LARGE_SPAN_BYTES',
                        span if large else span + 1)
    ring = Ring(space=space)
    hdr = simple_header([-1, 64], 'f32', gulp_nframe=8)
    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 8, 8):
            assert ring.total_span == span          # the writer's one
            with ring.open_earliest_sequence(guarantee=True) as rs:
                rs.resize(8)
                depth = 2 if large else 3
                assert ring.total_span == depth * span
                assert ring.ghost_span == span
                held = depth * span + (span if space == 'system' else 0)
                g = counters.gauges()
                assert g['ring.%s.capacity_bytes' % ring.name] == held
                assert g['ring.held_bytes.%s' % space] >= held
                # an explicit factor is the caller's own
                rs.resize(8, buffer_factor=4)
                assert ring.total_span == 4 * span


# ---------------------------------------------------------------------------
# the correlator integrates in place
# ---------------------------------------------------------------------------

def _voltages(nframe, F, S, P, seed):
    from bifrost_tpu.dtype import ci8 as ci8_dtype
    rng = np.random.RandomState(seed)
    raw = np.zeros((nframe, F, S, P), dtype=ci8_dtype)
    raw['re'] = rng.randint(-64, 64, size=raw.shape)
    raw['im'] = rng.randint(-64, 64, size=raw.shape)
    return raw


def _oracle(raw):
    """x @ x^H over the frames of ``raw`` in int64, as complex64."""
    T, F, S, P = raw.shape
    r = raw['re'].astype(np.int64).reshape(T, F, S * P)
    i = raw['im'].astype(np.int64).reshape(T, F, S * P)
    re = np.einsum('tfi,tfj->fij', r, r) + np.einsum('tfi,tfj->fij', i, i)
    im = np.einsum('tfi,tfj->fij', i, r) - np.einsum('tfi,tfj->fij', r, i)
    return (re + 1j * im).astype(np.complex64).reshape(F, S, P, S, P)


@pytest.mark.parametrize('chunked', [False, True])
def test_correlate_block_integrates_in_place(chunked, monkeypatch):
    """Three integrations of four gulps, exact against int64; the
    first gulp of an integration makes the planes (no zero product),
    every other goes into them, donated, with the channels in one
    chunk or in several; the finished planes go to the ring as they
    are, and no program joins them."""
    import importlib
    C = importlib.import_module('bifrost_tpu.blocks.correlate')
    G, F, S, P, NINT, GPI = 8, 6, 3, 2, 3, 4
    if chunked:     # two channels a chunk: three trips of the loop
        monkeypatch.setattr(C, '_VIS_CHUNK_BYTES', 2 * 8 * (S * P) ** 2)
    assert C._chunk_nchan(F, S * P) == (2 if chunked else F)
    counters.reset()
    raw = _voltages(G * GPI * NINT, F, S, P, seed=7)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'],
                        gulp_nframe=G)
    seen = []
    integrate = C.CorrelateBlock._integrate_in_place

    def spy(self, x, reim):
        before = self._acc
        integrate(self, x, reim)
        seen.append((before, self._acc))
    monkeypatch.setattr(C.CorrelateBlock, '_integrate_in_place', spy)
    with bf.Pipeline() as p:
        src = NumpySourceBlock(
            [raw[k * G:(k + 1) * G] for k in range(GPI * NINT)], hdr,
            gulp_nframe=G)
        b = bf.blocks.copy(src, space='tpu')
        corr = bf.blocks.correlate(b, nframe_per_integration=G * GPI)
        b = bf.blocks.copy(corr, space='system')
        sink = GatherSink(b)
        p.run()
    out = sink.result()
    assert out.shape == (NINT, F, S, P, S, P)
    assert out.dtype == np.complex64
    for k in range(NINT):
        np.testing.assert_array_equal(
            out[k], _oracle(raw[k * G * GPI:(k + 1) * G * GPI]))
    assert counters.get('correlate.acc_in_place') == GPI * NINT
    assert counters.get('correlate.integrations') == NINT
    # two programs a gulp shape (first / add) and none that joins the
    # planes; the first gulp of an integration is given nothing (the
    # last integration's planes are the ring's), and what every other
    # gulp was given is gone: it was donated
    # (a ci8 gulp on one device reaches the program as its words)
    assert sorted(corr._fn) == sorted(
        ((G, F, S, P, 2), 'words', first) for first in (False, True))
    assert [before is None for before, _a in seen] == \
        ([True] + [False] * (GPI - 1)) * NINT
    for before, after in seen:
        assert before is None or all(a.is_deleted() for a in before)
        assert all(a.dtype == np.float32 and
                   a.shape == (1, F, S, P, S, P) for a in after)
    assert corr._acc is None
    assert corr.impl_info['nchan_chunk'] == (2 if chunked else F)


def _correlated(raw, G, GPI, tail):
    """host source -> copy('tpu') -> correlate -> ``tail(corr)``, run;
    what ``tail`` returned."""
    _, F, S, P = raw.shape
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'],
                        gulp_nframe=G)
    with bf.Pipeline() as p:
        src = NumpySourceBlock(
            [raw[k:k + G] for k in range(0, len(raw), G)], hdr,
            gulp_nframe=G)
        corr = bf.blocks.correlate(bf.blocks.copy(src, space='tpu'),
                                   nframe_per_integration=G * GPI)
        out = tail(corr)
        p.run()
    return out


class _Tap(bf.pipeline.SinkBlock):
    """A device reader that keeps what ``take(ispan)`` gives it."""

    def __init__(self, iring, take, **kwargs):
        super(_Tap, self).__init__(iring, **kwargs)
        self.take, self.got = take, []

    def define_valid_input_spaces(self):
        return ('tpu',)

    def on_sequence(self, iseq):
        pass

    def on_data(self, ispan):
        self.got.append(self.take(ispan))


@pytest.mark.parametrize('reader', ['device_block', 'stitched',
                                    'copy_tpu_tpu', 'strict_scope',
                                    'async_off', 'to_host',
                                    'to_host_async'])
def test_readers_of_a_correlator_ring_see_complex64(reader, monkeypatch):
    """The ring holds the product as planes; whoever asks a span for
    its array gets the complex64 it stands for: a device block, a read
    over two products (the stitcher), copy('tpu' -> 'tpu'), strict
    D2H, ``to_host`` and ``to_host_async`` of the pair itself."""
    import jax
    from bifrost_tpu import xfer
    from bifrost_tpu.devrep import ComplexPlanes
    G, F, S, P, NINT, GPI = 4, 6, 3, 2, 4, 2
    raw = _voltages(G * GPI * NINT, F, S, P, seed=11)
    want = np.stack([_oracle(raw[k * G * GPI:(k + 1) * G * GPI])
                     for k in range(NINT)])
    if reader == 'async_off':
        monkeypatch.setenv('BF_XFER_ASYNC', '0')
        xfer.reset_engine()

    def seen(ispan):
        data = ispan.data
        assert isinstance(data, jax.Array) and \
            data.dtype == np.complex64 and \
            isinstance(ispan.planes, ComplexPlanes)
        return np.asarray(data)

    tail = {
        'device_block': lambda corr: _Tap(corr, seen),
        'stitched': lambda corr: GatherSink(corr, gulp_nframe=2),
        'copy_tpu_tpu': lambda corr: GatherSink(
            bf.blocks.copy(corr, space='tpu')),
        'strict_scope': lambda corr: GatherSink(
            bf.blocks.copy(corr, space='system', sync_strict=True)),
        'async_off': lambda corr: GatherSink(
            bf.blocks.copy(corr, space='system')),
        'to_host': lambda corr: _Tap(
            corr, lambda ispan: xfer.to_host(ispan.planes)),
        'to_host_async': lambda corr: _Tap(
            corr, lambda ispan: xfer.to_host_async(ispan.planes).result()),
    }[reader]
    try:
        sink = _correlated(raw, G, GPI, tail)
    finally:
        xfer.reset_engine()
    got = np.concatenate(sink.got if isinstance(sink, _Tap)
                         else sink.gulps)
    assert got.dtype == np.complex64
    np.testing.assert_array_equal(got, want)
    if reader == 'stitched':        # a span over two chunks is no pair
        assert [g.shape[0] for g in sink.gulps] == [2, 2]


def test_planes_handed_on_are_not_written_again():
    """The planes a product went to the ring in belong to the ring:
    the next integration makes its own, so a reader that still holds
    two products finds them whole while the third is integrated and
    after."""
    G, F, S, P, NINT, GPI = 4, 6, 3, 2, 3, 2
    raw = _voltages(G * GPI * NINT, F, S, P, seed=5)
    tap = _correlated(raw, G, GPI,
                      lambda corr: _Tap(corr, lambda ispan: ispan.planes))
    assert len(tap.got) == NINT
    arrays = [a for pair in tap.got for a in (pair.re, pair.im)]
    assert len(set(map(id, arrays))) == 2 * NINT
    for k, pair in enumerate(tap.got):
        assert not pair.is_deleted() and pair.is_ready()
        assert pair.shape == (1, F, S, P, S, P) and \
            pair.dtype == np.complex64 and \
            pair.nbytes == 8 * F * (S * P) ** 2
        np.testing.assert_array_equal(
            (np.asarray(pair.re) + 1j * np.asarray(pair.im))[0],
            _oracle(raw[k * G * GPI:(k + 1) * G * GPI]))


def test_partial_commit_and_sub_span_of_planes():
    """A short commit slices both planes; a read of part of a chunk
    is the complex array's slice, and no pair."""
    import jax
    from bifrost_tpu.devrep import ComplexPlanes
    rng = np.random.RandomState(2)
    re, im = rng.randn(2, 4, 8).astype(np.float32)
    ring = Ring(space='tpu')
    hdr = simple_header([-1, 8], 'cf32', gulp_nframe=4)
    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 4, 8) as seq:
            with seq.reserve(4) as sp:
                sp.set(ComplexPlanes(jax.device_put(re),
                                     jax.device_put(im)))
                sp.commit(3)
            with ring.open_earliest_sequence(guarantee=False) as rs:
                with rs.acquire(0, 3) as span:
                    pair = span.planes
                    assert pair.shape == (3, 8)
                    np.testing.assert_array_equal(
                        np.asarray(span.data), (re + 1j * im)[:3])
                with rs.acquire(1, 2) as span:
                    assert span.planes is None
                    np.testing.assert_array_equal(
                        np.asarray(span.data), (re + 1j * im)[1:3])
    with pytest.raises(ValueError):
        ComplexPlanes(jax.device_put(re), jax.device_put(im[:2]))


def test_correlate_block_float_voltages_in_place():
    """Complex float voltages take the same in-place program."""
    T, F, S, P = 8, 4, 3, 2
    rng = np.random.RandomState(1)
    v = (rng.randn(2 * T, F, S, P) + 1j * rng.randn(2 * T, F, S, P)) \
        .astype(np.complex64)
    hdr = simple_header([-1, F, S, P], 'cf32',
                        labels=['time', 'freq', 'station', 'pol'],
                        gulp_nframe=4)
    counters.reset()
    with bf.Pipeline() as p:
        src = NumpySourceBlock([v[k:k + 4] for k in range(0, 2 * T, 4)],
                               hdr, gulp_nframe=4)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.correlate(b, nframe_per_integration=T)
        b = bf.blocks.copy(b, space='system')
        sink = GatherSink(b)
        p.run()
    out = sink.result()
    assert out.shape == (2, F, S, P, S, P)
    for k in range(2):
        vm = v[k * T:(k + 1) * T].reshape(T, F, S * P)
        np.testing.assert_allclose(
            out[k], np.einsum('tfi,tfj->fij', vm, vm.conj())
            .reshape(F, S, P, S, P), rtol=1e-4, atol=1e-4)
    assert counters.get('correlate.acc_in_place') == 4


# ---------------------------------------------------------------------------
# the whole served chain with everything large
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('ahead', [1, 2])
def test_served_chain_with_large_products(ahead, monkeypatch):
    """host source -> copy('tpu') -> correlate -> copy('system') ->
    sink with the rule's constant below a product: the product rings
    are two deep on both sides, the input rings three; the products
    cross in pieces as real (re, im) pairs cut from the planes the
    correlator handed on, each cut up when its landing starts and
    taken group by group, ``ahead`` groups on their way beside the
    one being taken, one product in flight at a time; the block's
    dispatch-ahead queue holds one (its two planes); every visibility
    is exact."""
    from bifrost_tpu import xfer
    from bifrost_tpu.telemetry import spans
    G, F, S, P, NINT, GPI = 4, 16, 4, 2, 5, 2
    product = F * (S * P) ** 2 * 8                  # 8192 bytes
    gulp = G * F * S * P * 2                        # 1024 bytes
    monkeypatch.setattr(memory, 'LARGE_SPAN_BYTES', product)
    monkeypatch.setattr(memory, 'INFLIGHT_BYTES', 2 * product - 1)
    monkeypatch.setattr(xfer, '_D2H_PIECE_BYTES', 1024)
    monkeypatch.setattr(xfer, '_D2H_GROUP', 2)
    monkeypatch.setattr(xfer, '_D2H_AHEAD', ahead)
    xfer.reset_engine()
    counters.reset()
    spans.reset()
    raw = _voltages(G * GPI * NINT, F, S, P, seed=3)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'],
                        gulp_nframe=G)
    most = []
    fill_of = xfer.TransferEngine.host_fill

    def host_fill(self, dev_arr, dtype, out_view):
        fill = fill_of(self, dev_arr, dtype, out_view)
        with self._lock:
            most.append(sum(f.nbytes for f in self._fills
                            if not f.done and f is not fill))
        return fill
    monkeypatch.setattr(xfer.TransferEngine, 'host_fill', host_fill)
    with bf.Pipeline() as p:
        src = NumpySourceBlock(
            [raw[k * G:(k + 1) * G] for k in range(GPI * NINT)], hdr,
            gulp_nframe=G)
        h2d = bf.blocks.copy(src, space='tpu')
        corr = bf.blocks.correlate(h2d, nframe_per_integration=G * GPI)
        d2h = bf.blocks.copy(corr, space='system')
        sink = GatherSink(d2h)
        p.run()
    try:
        out = sink.result()
        for k in range(NINT):
            np.testing.assert_array_equal(
                out[k], _oracle(raw[k * G * GPI:(k + 1) * G * GPI]))
        g = counters.gauges()

        def cap(block):
            return g['ring.%s.capacity_bytes' % block.orings[0].name]
        assert cap(src) == 4 * gulp         # three and the ghost region
        assert cap(h2d) == 3 * gulp
        assert cap(corr) == 2 * product
        assert cap(d2h) == 3 * product      # two and the ghost region
        assert g['ring.held_bytes.system'] >= 4 * gulp + 3 * product
        assert g['ring.held_bytes.tpu'] >= 3 * gulp + 2 * product
        # every product in pieces, four groups of two each, as pairs,
        # cut from planes: no program made it complex on the way
        assert counters.get('xfer.d2h_piece_bytes') == \
            counters.get('xfer.d2h_pair_bytes') == \
            counters.get('xfer.d2h_plane_bytes') == \
            counters.get('xfer.d2h_cutup_bytes') == \
            counters.get('xfer.d2h_bytes') == NINT * product
        assert 'product' not in corr._fn
        names = [ev[0] for _t, ev in spans.events()]
        assert names.count('d2h.fill') == 4 * NINT
        assert 'd2h.convert' not in names
        # no product was queued behind an unfinished one
        assert len(most) == NINT and max(most) == 0
        assert [[a.dtype for a in gulp]
                for gulp in corr._pending_outputs] == [[np.float32] * 2]
    finally:
        xfer.reset_engine()


def test_accumulate_product_rings_follow_span_bytes(monkeypatch):
    """An accumulate block's product with the rule's constant at its
    size (the gpuspec-hsr product is exactly LARGE_SPAN_BYTES: `>=`):
    the rings that carry it are two deep on both sides of the D2H
    copy, the spectra ring in front of the block too (a gulp's spectra
    are a product's size), the input rings three; the product crosses
    in pieces, cut up when its landing starts; the sums are exact."""
    from bifrost_tpu import xfer
    G, C, N, NINT, NPROD = 1, 8, 64, 3, 4
    product = C * N * 4                             # 2048 bytes
    assert memory.span_depth(1 << 30, 3) == 2       # the real product
    monkeypatch.setattr(memory, 'LARGE_SPAN_BYTES', product)
    monkeypatch.setattr(memory, 'INFLIGHT_BYTES', 2 * product)
    monkeypatch.setattr(xfer, '_D2H_PIECE_BYTES', 256)
    monkeypatch.setattr(xfer, '_D2H_GROUP', 2)
    xfer.reset_engine()
    counters.reset()
    rng = np.random.RandomState(4)
    data = rng.randint(-64, 64, size=(NINT * NPROD, C, N)) \
        .astype(np.float32)
    hdr = simple_header([-1, C, N], 'f32', gulp_nframe=G)
    try:
        with bf.Pipeline() as p:
            src = NumpySourceBlock(list(data[:, None]), hdr, gulp_nframe=G)
            h2d = bf.blocks.copy(src, space='tpu')
            acc = bf.blocks.accumulate(h2d, NINT)
            d2h = bf.blocks.copy(acc, space='system')
            sink = GatherSink(d2h)
            p.run()
        np.testing.assert_array_equal(
            sink.result(), data.reshape(NPROD, NINT, C, N).sum(1))
        g = counters.gauges()

        def cap(block):
            return g['ring.%s.capacity_bytes' % block.orings[0].name]
        # a gulp here is a product's size, so every ring holds two
        # (and the host rings their ghost region)
        assert cap(src) == 3 * product
        assert cap(h2d) == 2 * product
        assert cap(acc) == 2 * product
        assert cap(d2h) == 3 * product
        assert counters.get('accumulate.integrations') == NPROD
        assert counters.get('accumulate.acc_in_place') == \
            (NINT - 1) * NPROD
        assert counters.get('xfer.d2h_piece_bytes') == \
            counters.get('xfer.d2h_cutup_bytes') == \
            counters.get('xfer.d2h_bytes') == NPROD * product
        assert counters.get('xfer.d2h_plane_bytes') == 0
    finally:
        xfer.reset_engine()


def test_dispatch_ahead_queue_is_bounded_by_bytes(monkeypatch):
    """A device block's queue of outputs it has not waited for drains
    to the newest once it holds more than INFLIGHT_BYTES, whatever
    ``sync_depth`` says."""
    G, NG = 8, 12
    data = np.arange(G * NG * 16, dtype=np.float32).reshape(G * NG, 16)
    hdr = simple_header([-1, 16], 'f32', gulp_nframe=G)

    def waits(bound):
        monkeypatch.setattr(memory, 'INFLIGHT_BYTES', bound)
        counters.reset()
        with bf.Pipeline() as p:
            src = NumpySourceBlock(
                [data[k * G:(k + 1) * G] for k in range(NG)], hdr,
                gulp_nframe=G)
            b = bf.blocks.copy(src, space='tpu')
            sink = GatherSink(bf.blocks.copy(b, space='system'))
            p.run()
        np.testing.assert_array_equal(sink.result(), data)
        return counters.get('pipeline.sync_waits')
    deep = waits(1 << 30)               # by count: once in five gulps
    assert deep <= NG // 4
    assert waits(G * 16 * 4) >= NG - 2  # by bytes: every gulp but one
