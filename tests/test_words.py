"""A ci8 gulp on one device as int16 words (devrep.ComplexWords): the
host's bytes in the host's order, bit for bit both ways, the int8
(re, im) pairs to whoever asks for them, the same outputs from every
chain, the pre-warmed program the one a gulp runs; mesh-scoped, ci4 and
ci16 gulps keep the pairs."""

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu import devrep, xfer
from bifrost_tpu.devrep import (ComplexWords, to_device_rep,
                                from_device_rep, device_rep_zeros)
from bifrost_tpu.dtype import DataType
from bifrost_tpu.telemetry import counters
from bifrost_tpu.words import host_words, host_view, words_into

from util import NumpySourceBlock, GatherSink, simple_header


def _ci8(shape, seed=0):
    rng = np.random.RandomState(seed)
    raw = np.zeros(shape, DataType('ci8').as_numpy_dtype())
    raw['re'] = rng.randint(-128, 128, shape)
    raw['im'] = rng.randint(-128, 128, shape)
    return raw


def _pairs(raw):
    return np.stack([raw['re'], raw['im']], axis=-1)


@pytest.fixture
def pair_form(monkeypatch):
    """The form ci8 gulps had until PR 34, for a comparison: int8 with
    a trailing (re, im) axis, from ``to_device_rep`` and
    ``device_rep_zeros`` alike."""
    def on():
        monkeypatch.setattr(devrep, '_as_words', lambda dtype: False)
    return on


# ---------------------------------------------------------------------------
# the chunk type
# ---------------------------------------------------------------------------

def test_host_storage_is_its_words_by_a_view():
    raw = _ci8((16, 2, 256), seed=1)
    view = host_view(raw)
    assert view.shape == (16 * 2 * 256,) and view.dtype == np.int16
    assert np.shares_memory(view, raw) and np.shares_memory(
        host_words(raw), raw)
    strided = np.zeros((16, 2, 256, 3), raw.dtype)[..., 1]
    assert host_view(strided) is None
    assert host_words(strided).shape == (16 * 2 * 256,)


@pytest.mark.parametrize('shape', [(16, 2, 256), (8, 4, 2, 2), (1, 4, 2, 512),
                                   (3, 5), (7,), (0, 2, 256)])
def test_words_and_pairs_are_each_other_bit_for_bit(shape):
    """host -> device -> host: the words are the host's bytes (low
    byte re), the pairs made from them are the int8 array the host
    would have stacked, and both come back as they left."""
    raw = _ci8(shape, seed=len(shape))
    chunk = to_device_rep(raw, 'ci8')
    assert isinstance(chunk, ComplexWords)
    assert chunk.shape == shape + (2,) and chunk.ndim == len(shape) + 1
    assert chunk.dtype == np.int8 and chunk.nbytes == raw.nbytes
    assert tuple(chunk.words.shape) == (int(np.prod(shape)),)
    assert chunk.block_until_ready() is chunk and chunk.is_ready()
    assert not chunk.is_deleted()
    assert len(chunk.sharding.device_set) == 1
    # the words: the storage's own bytes, in its order
    assert np.array_equal(np.asarray(chunk.words).reshape(-1),
                          raw.reshape(-1).view(np.int16))
    assert np.array_equal(host_words(raw), np.asarray(chunk.words))
    # the pairs, made on the device, and the chunk seen from the host
    assert np.array_equal(np.asarray(chunk.pairs()), _pairs(raw))
    assert np.array_equal(np.asarray(chunk), _pairs(raw))
    assert np.array_equal(xfer.to_host(chunk), _pairs(raw))
    for out in (np.zeros_like(raw),
                np.zeros(shape + (3,), raw.dtype)[..., 1]):   # strided
        assert from_device_rep(chunk, 'ci8', out) is out
        assert np.array_equal(out, raw)
    back = np.zeros_like(raw)
    words_into(np.asarray(chunk.words), back)
    assert np.array_equal(back, raw)
    # the pairs themselves go back the old way
    back = np.zeros_like(raw)
    from_device_rep(chunk.pairs(), 'ci8', back)
    assert np.array_equal(back, raw)


def test_a_stretch_of_frames_stays_words_and_anything_else_is_pairs():
    raw = _ci8((16, 2, 256), seed=3)
    chunk = to_device_rep(raw, 'ci8')
    want = _pairs(raw)
    for idx in (slice(2, 9), (slice(0, 4),), slice(5, 5),
                (slice(3, 16), slice(None), slice(None), slice(None))):
        part = chunk[idx]
        assert isinstance(part, ComplexWords), idx
        assert part.shape == want[idx].shape
        assert np.array_equal(np.asarray(part), want[idx])
    for idx in ((Ellipsis, 0), (slice(None), 1), slice(0, 16, 2), 3,
                (slice(None), slice(None), slice(0, 128))):
        part = chunk[idx]
        assert not isinstance(part, ComplexWords), idx
        assert np.array_equal(np.asarray(part), want[idx])
    # a ring with ringlets: the frames are the second axis, and a
    # stretch of them is no stretch of the words
    lets = to_device_rep(_ci8((2, 8, 64), seed=2), 'ci8')
    assert not isinstance(lets[:, 2:5], ComplexWords)
    assert isinstance(to_device_rep(_ci8((1, 8, 64)), 'ci8')[:, 2:5],
                      ComplexWords)
    with pytest.raises(ValueError):
        ComplexWords(chunk.words, (16, 2, 128))
    with pytest.raises(ValueError):
        ComplexWords(chunk.words.reshape(32, 256), (16, 2, 256))
    with pytest.raises(ValueError):
        ComplexWords(chunk.pairs(), (16, 2, 256))


def test_zeros_come_in_the_form_a_gulp_comes_in():
    z = device_rep_zeros((8, 2, 64), 'ci8')
    assert isinstance(z, ComplexWords) and z.shape == (8, 2, 64, 2)
    assert not np.asarray(z).any()
    for dtype, comp in (('ci4', np.int8), ('ci16', np.int16)):
        z = device_rep_zeros((8, 2, 64), dtype)
        assert not isinstance(z, ComplexWords)
        assert z.shape == (8, 2, 64, 2) and z.dtype == comp


def test_mesh_scoped_ci4_and_ci16_gulps_keep_the_pairs():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    raw = _ci8((16, 2, 64), seed=4)
    counters.reset()
    if len(jax.devices()) >= 2:
        mesh = Mesh(np.array(jax.devices()[:2]), ('t',))
        arr = to_device_rep(raw, 'ci8',
                            sharding=NamedSharding(mesh, P('t')))
        assert not isinstance(arr, ComplexWords)
        assert arr.dtype == np.int8 and arr.shape == (16, 2, 64, 2)
        assert len(arr.sharding.device_set) == 2
        assert np.array_equal(np.asarray(arr), _pairs(raw))
    raw16 = np.zeros((16, 8), DataType('ci16').as_numpy_dtype())
    raw16['re'] = np.arange(128).reshape(16, 8) * 257 - 9000
    raw16['im'] = -raw16['re'] - 1
    arr = to_device_rep(raw16, 'ci16')
    assert not isinstance(arr, ComplexWords)
    assert arr.dtype == np.int16 and arr.shape == (16, 8, 2)
    back = np.zeros_like(raw16)
    from_device_rep(arr, 'ci16', back)
    assert np.array_equal(back, raw16)
    raw4 = np.arange(64, dtype=np.uint8).reshape(8, 8) * 3
    arr = to_device_rep(raw4.view(DataType('ci4').as_numpy_dtype())
                        if DataType('ci4').as_numpy_dtype() != np.uint8
                        else raw4, 'ci4')
    assert not isinstance(arr, ComplexWords)
    assert arr.dtype == np.int8 and arr.shape[-1] == 2
    # none of them was counted as words
    assert counters.get('xfer.h2d_word_bytes') == 0
    assert counters.get('xfer.h2d_bytes') > 0


# ---------------------------------------------------------------------------
# through the rings: H2D, a reader of .data, D2H (whole, wrapped, pieces)
# ---------------------------------------------------------------------------

class _Spy(bf.pipeline.TransformBlock):
    """A device block that notes what its input span answers and hands
    the pairs on."""

    def __init__(self, iring, seen):
        super(_Spy, self).__init__(iring)
        self.seen = seen

    def define_valid_input_spaces(self):
        return ('tpu',)

    def on_sequence(self, iseq):
        from copy import deepcopy
        return deepcopy(iseq.header)

    def on_data(self, ispan, ospan):
        self.seen.append((ispan.words, ispan.planes, ispan.data))
        ospan.set(ispan.data)


def test_a_reader_of_data_gets_the_pairs_and_a_reader_of_words_the_words():
    """copy('tpu') sets words; ``ispan.data`` on that ring is the int8
    array with its (re, im) axis, ``ispan.words`` the chunk itself;
    a ring that holds pairs (the spy's output) answers no words; the
    D2H copies of both rings bring the host's bytes back."""
    raw = _ci8((24, 2, 64), seed=5)
    seen = []
    counters.reset()
    with bf.Pipeline() as p:
        hdr = simple_header([-1, 2, 64], 'ci8')
        src = NumpySourceBlock([raw[:8], raw[8:16], raw[16:]], hdr,
                               gulp_nframe=8)
        dev = bf.blocks.copy(src, space='tpu')
        direct = GatherSink(bf.blocks.copy(dev, space='system'))
        spy = _Spy(dev, seen)
        after = GatherSink(bf.blocks.copy(spy, space='system'))
        p.run()
    assert len(seen) == 3
    for k, (words, planes, data) in enumerate(seen):
        assert isinstance(words, ComplexWords) and planes is None
        assert not isinstance(data, ComplexWords)
        assert data.dtype == np.int8 and data.shape == (8, 2, 64, 2)
        assert np.array_equal(np.asarray(data),
                              _pairs(raw[8 * k:8 * k + 8]))
        assert np.array_equal(np.asarray(words.words),
                              host_words(raw[8 * k:8 * k + 8]))
    for sink in (direct, after):
        got = sink.result()
        assert got.dtype == raw.dtype and np.array_equal(got, raw)
    # every byte sent crossed as words
    assert counters.get('xfer.h2d_word_bytes') == \
        counters.get('xfer.h2d_bytes') == raw.nbytes


@pytest.mark.parametrize('pieces', [False, True])
def test_words_land_in_a_span_that_wraps(pieces, monkeypatch):
    """The engine's deferred fill of a host ring span from words: the
    int16 array crosses (in pieces where it is large), into the span
    seen as int16 words, bit for bit, ghost mirror included."""
    from bifrost_tpu.ring import Ring
    if pieces:
        monkeypatch.setattr(xfer, '_D2H_PIECE_BYTES', 256)
    counters.reset()
    nframe, nchan = 24, 128
    raw = _ci8((nframe, nchan), seed=6)
    eng = xfer.engine()
    ring = Ring(space='system')
    hdr = simple_header([-1, nchan], 'ci8', gulp_nframe=8)
    fills = []
    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 8, 20) as seq:
            for g0 in (0, 8, 16):                # [16, 24) wraps at 20
                with seq.reserve(8) as sp:
                    chunk = to_device_rep(raw[g0:g0 + 8], 'ci8')
                    assert isinstance(chunk, ComplexWords)
                    fill = eng.host_fill(chunk, 'ci8', sp.data.as_numpy())
                    sp.set_fill(fill)
                    sp.commit(8)
                    fills.append(fill)
            with ring.open_earliest_sequence(guarantee=False) as rs:
                with rs.acquire(18, 4) as span:
                    got = np.array(span.data.as_numpy(), copy=True)
    assert got.dtype == raw.dtype and np.array_equal(got, raw[18:22])
    assert all(isinstance(f.future, xfer._PieceFuture) == pieces
               for f in fills)
    assert all(f.dtype == 'i16' for f in fills)      # the words crossed
    assert counters.get('xfer.d2h_bytes') == raw.nbytes
    assert counters.get('xfer.d2h_piece_bytes') == \
        (raw.nbytes if pieces else 0)


def test_words_into_a_span_of_a_ring_with_ringlets_go_as_pairs():
    """A destination that is not one stretch of bytes cannot be seen
    as int16 words: the fill is given the pairs."""
    raw = _ci8((4, 8, 32), seed=7)
    chunk = to_device_rep(raw, 'ci8')
    out = np.zeros((4, 16, 32), raw.dtype)[:, 4:12]
    assert not out.flags.c_contiguous
    fill = xfer.engine().host_fill(chunk, 'ci8', out)
    fill.wait()
    assert fill.dtype == 'ci8' and np.array_equal(out, raw)


# ---------------------------------------------------------------------------
# the chains: the same outputs from words and from pairs
# ---------------------------------------------------------------------------

def _spectrometer_chain(raw, gulp, reduce_to=None, acc=None):
    from bifrost_tpu.stages import FftStage, DetectStage, ReduceStage
    stages = [FftStage('fine_time', axis_labels='freq'),
              DetectStage('stokes', axis='pol')]
    if reduce_to:
        stages.append(ReduceStage('freq', reduce_to))
    with bf.Pipeline() as p:
        hdr = simple_header([-1, 2, raw.shape[-1]], 'ci8',
                            labels=['time', 'pol', 'fine_time'])
        src = NumpySourceBlock(
            [raw[k:k + gulp] for k in range(0, len(raw), gulp)], hdr,
            gulp_nframe=gulp)
        b = bf.blocks.copy(src, space='tpu')
        fb = b = bf.blocks.fused(b, stages)
        if acc:
            b = bf.blocks.accumulate(b, acc)
        sink = GatherSink(bf.blocks.copy(b, space='system'))
        p.run()
    return sink.result(), fb


@pytest.mark.parametrize('chain', ['fft_detect', 'fft_detect_reduce',
                                   'fft_detect_accumulate'])
def test_fused_chains_give_the_bits_the_pair_form_gives(chain, pair_form):
    raw = _ci8((32, 2, 64), seed=8)
    kw = {'fft_detect': {}, 'fft_detect_reduce': {'reduce_to': 4},
          'fft_detect_accumulate': {'acc': 4}}[chain]
    counters.reset()
    got, fb = _spectrometer_chain(raw, 8, **kw)
    assert fb.impl_info['input'] == 'words'
    assert counters.get('spectrometer.word_gulps') == \
        counters.get('spectrometer.gulps') == 4
    # one plan, built before the first gulp
    assert counters.get('fused.plan_builds') == 1
    assert sorted(k[1] for k in fb._plans) == ['words']
    pair_form()
    counters.reset()
    want, fb = _spectrometer_chain(raw, 8, **kw)
    assert 'input' not in fb.impl_info
    assert counters.get('spectrometer.word_gulps') == 0
    assert counters.get('spectrometer.gulps') == 4
    assert sorted(k[1] for k in fb._plans) == ['int8']
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def test_correlate_block_gives_the_bits_the_pair_form_gives(pair_form):
    t, f, s, p_ = 16, 6, 3, 2
    raw = _ci8((t, f, s, p_), seed=9)

    def run():
        with bf.Pipeline() as p:
            hdr = simple_header([-1, f, s, p_], 'ci8',
                                labels=['time', 'freq', 'station', 'pol'],
                                gulp_nframe=4)
            src = NumpySourceBlock([raw[k:k + 4] for k in range(0, t, 4)],
                                   hdr, gulp_nframe=4)
            b = bf.blocks.copy(src, space='tpu')
            corr = bf.blocks.correlate(b, nframe_per_integration=8)
            sink = GatherSink(bf.blocks.copy(corr, space='system'))
            p.run()
        return sink.result(), corr
    got, corr = run()
    assert sorted(k[1] for k in corr._fn) == ['words', 'words']
    pair_form()
    want, corr = run()
    assert sorted(k[1] for k in corr._fn) == ['int8', 'int8']
    assert got.shape == want.shape == (2, f, s, p_, s, p_)
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    # and against the definition: exact integer visibilities
    v = (raw['re'].astype(np.int64) + 1j * raw['im']).reshape(2, 8, f, s * p_)
    ref = np.einsum('ktfi,ktfj->kfij', v, v.conj()).reshape(got.shape)
    assert np.array_equal(got, ref.astype(np.complex64))


def test_the_kernel_from_words_is_the_kernel_from_pairs():
    """ops.spectrometer.fused_spectrometer (interpreted here) and
    long_spectrometer: the same bits from the gulp's words, on one
    axis as a ring holds them or as the rows the kernel reads, as from
    its int8 pairs."""
    import jax.numpy as jnp
    from bifrost_tpu.ops import spectrometer as spec
    for nfft in (64, 256):
        raw = _ci8((8, 2, nfft), seed=nfft)
        chunk = to_device_rep(raw, 'ci8')
        want = np.asarray(spec.fused_spectrometer(
            chunk.pairs(), rfactor=4, time_tile=4, interpret=True))
        for words in (chunk.words, chunk.words.reshape(16, nfft)):
            got = np.asarray(spec.fused_spectrometer(
                words, nfft=nfft, rfactor=4, time_tile=4, interpret=True))
            assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    for bad, nfft in ((jnp.zeros((3 * 64,), jnp.int16), 64),
                      (jnp.zeros((4 * 64,), jnp.int16), None)):
        with pytest.raises(ValueError):
            spec.fused_spectrometer(bad, nfft=nfft, interpret=True)
    raw = _ci8((2, 3, 2, 1 << 15), seed=15)
    chunk = to_device_rep(raw, 'ci8')
    want = np.asarray(spec.long_spectrometer(chunk.pairs(), (8, 32, 128)))
    got = np.asarray(spec.long_spectrometer(
        chunk.words.reshape(raw.shape), (8, 32, 128)))
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    got = np.asarray(spec.long_spectrometer(chunk.words, (8, 32, 128)))
    assert got.shape == (6, 4, 1 << 15)
    assert np.array_equal(got.view(np.uint32).reshape(want.shape),
                          want.view(np.uint32))
    with pytest.raises(ValueError):
        spec.long_spectrometer(chunk.words[:-2], (8, 32, 128))


# ---------------------------------------------------------------------------
# pre-warm: the program compiled at the sequence's start is the gulp's
# ---------------------------------------------------------------------------

def test_nothing_compiles_after_the_first_gulp(monkeypatch):
    """``jit.compiles`` as each gulp enters the fused block and the
    correlator: it does not rise after the first gulp has gone through
    the whole pipeline (the pre-warm compiled the words' plan, the
    first gulp the transfer's and the correlator's own), and the fused
    block builds one plan, outside ``on_data``."""
    from bifrost_tpu.blocks.fused import FusedBlock
    from bifrost_tpu.stages import FftStage, DetectStage
    from bifrost_tpu.telemetry import spans
    spans.watch_jax()
    raw = _ci8((40, 2, 64), seed=10)
    compiles, building = [], []
    on_data, build = FusedBlock.on_data, FusedBlock._build_plan

    def spy_on_data(self, ispan, ospan):
        building.append(True)
        try:
            return on_data(self, ispan, ospan)
        finally:
            building.pop()
            compiles.append(counters.get('jit.compiles'))

    def spy_build(self, shape, dtype, **kw):
        assert not building, 'a plan was built inside on_data'
        assert kw.get('words') is True
        return build(self, shape, dtype, **kw)
    monkeypatch.setattr(FusedBlock, 'on_data', spy_on_data)
    monkeypatch.setattr(FusedBlock, '_build_plan', spy_build)
    counters.reset()
    with bf.Pipeline() as p:
        hdr = simple_header([-1, 2, 64], 'ci8',
                            labels=['time', 'pol', 'fine_time'])
        src = NumpySourceBlock([raw[k:k + 8] for k in range(0, 40, 8)],
                               hdr, gulp_nframe=8)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fused(b, [FftStage('fine_time', axis_labels='freq'),
                                DetectStage('stokes', axis='pol')])
        sink = GatherSink(bf.blocks.copy(b, space='system'))
        p.run()
    assert sink.result().shape == (40, 4, 64)
    assert counters.get('fused.plan_builds') == 1
    assert len(compiles) == 5
    # the fused block itself compiled nothing in any gulp ...
    assert compiles[1:] == [compiles[1]] * 4
    # ... and nobody did once the second gulp had gone in
    assert counters.get('jit.compiles') == compiles[1]
