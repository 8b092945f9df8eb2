"""The long transform (ops.fft.long_fft: three levels of DFT matrices,
chosen from the length alone) and the chain that runs it in the
gpuspec-hsr deployment: FftStage -> DetectStage('stokes'), fused, then
an accumulate block that sums on the device in place.  Sizes are
small; the function and the blocks are the ones the chip runs."""

import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu.pipeline import SourceBlock
from bifrost_tpu.stages import FftStage, DetectStage
from bifrost_tpu.telemetry import counters

from util import GatherSink

#: the module (``bifrost_tpu.ops.fft`` is also the name of a function
#: the package re-exports)
F = importlib.import_module('bifrost_tpu.ops.fft')

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the transform alone
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('natural', [True, False],
                         ids=['frequency_order', 'k1_major'])
@pytest.mark.parametrize('factors', [(4, 8, 16), (16, 2, 4), (8, 8, 8),
                                     (2, 32, 4), (32, 16, 128)],
                         ids=lambda f: 'x'.join(map(str, f)))
def test_long_fft_against_float64(factors, natural):
    """Three levels at explicit factors, through the function the
    chip runs, against numpy's float64 transform of the same integer
    voltages; both orders of the output."""
    import jax
    import jax.numpy as jnp
    n = int(np.prod(factors))
    rng = np.random.default_rng(n)
    x = rng.integers(-64, 64, (3, n)) + 1j * rng.integers(-64, 64, (3, n))
    want = np.fft.fft(x, axis=-1)
    yr, yi = jax.jit(lambda a, b: F.long_fft(a, b, factors,
                                             natural=natural))(
        jnp.asarray(x.real, jnp.float32), jnp.asarray(x.imag, jnp.float32))
    assert yr.dtype == yi.dtype == jnp.float32 and yr.shape == (3, n)
    got = np.asarray(yr).astype(np.float64) + 1j * np.asarray(yi)
    if not natural:
        n1, n2, n3 = factors        # X[k1 + n1 k2 + n1 n2 k3] at [k1, k2, k3]
        got = got.reshape(3, n1, n2, n3).transpose(0, 3, 2, 1).reshape(3, n)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6


@pytest.mark.parametrize('precision', ['high', 'highest'])
@pytest.mark.parametrize('natural', [True, False],
                         ids=['frequency_order', 'k1_major'])
@pytest.mark.parametrize('factors', [(4, 8, 16), (32, 16, 128)],
                         ids=lambda f: 'x'.join(map(str, f)))
def test_long_fft_inverse_from_either_order(factors, natural, precision):
    """The inverse takes the spectrum in the order ``natural`` names,
    frequency or the levels' own (as the forward leaves it), and
    returns numpy's ifft times n in time order either way; the inverse
    of the forward's level-ordered spectrum is the voltages times n."""
    import jax
    import jax.numpy as jnp
    n = int(np.prod(factors))
    n1, n2, n3 = factors
    rng = np.random.default_rng(n + 1)
    y = rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n))
    want = np.fft.ifft(y, axis=-1) * n
    if not natural:             # Y[k1 + n1 k2 + n1 n2 k3] at [k1, k2, k3]
        y = y.reshape(3, n3, n2, n1).transpose(0, 3, 2, 1).reshape(3, n)

    def inv(a, b):
        return F.long_fft(a, b, factors, precision=precision,
                          natural=natural, inverse=True)
    zr, zi = jax.jit(inv)(jnp.asarray(y.real, jnp.float32),
                          jnp.asarray(y.imag, jnp.float32))
    got = np.asarray(zr).astype(np.float64) + 1j * np.asarray(zi)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6
    x = rng.integers(-64, 64, (3, n)) + 1j * rng.integers(-64, 64, (3, n))
    back = jax.jit(lambda a, b: inv(*F.long_fft(
        a, b, factors, precision=precision, natural=natural)))(
        jnp.asarray(x.real, jnp.float32), jnp.asarray(x.imag, jnp.float32))
    got = np.asarray(back[0]).astype(np.float64) + 1j * np.asarray(back[1])
    assert np.max(np.abs(got - n * x)) / (n * np.max(np.abs(x))) < 2e-6


@pytest.mark.parametrize('nrow,keep,want', [(12, 1, 4), (12, 2, 4),
                                            (12, 3, 3), (10, 1, 2),
                                            (6, 6, 6), (3, 1, 3)])
def test_chunks_hold_whole_groups_of_rows(nrow, keep, want, monkeypatch):
    """The largest divisor of the rows that is a multiple of ``keep``
    and whose spectra fit the chunk's bytes; ``keep`` rows at least."""
    monkeypatch.setattr(F, '_CHUNK_BYTES', 4 * 8 * 512)     # four rows
    assert F._chunk_rows(nrow, 512, keep) == want
    # at the deployment's size: one coarse channel (both pol) a chunk
    monkeypatch.undo()
    assert F._chunk_rows(128, 1 << 20, 2) == 2


@pytest.mark.parametrize('natural', [True, False])
def test_long_fft_in_chunks_with_an_epilogue(natural, monkeypatch):
    """Twelve transforms through the loop three at a time: the planes
    joined are the whole transform's; an epilogue is given each
    chunk's float32 planes and its results are joined; integer planes
    are cast inside."""
    import jax
    factors, n = (4, 8, 16), 512
    monkeypatch.setattr(F, '_CHUNK_BYTES', 3 * 8 * n)
    rng = np.random.default_rng(9)
    xr = rng.integers(-64, 64, (2, 6, n), dtype=np.int8)
    xi = rng.integers(-64, 64, (2, 6, n), dtype=np.int8)
    want = np.fft.fft(xr.astype(np.float64) + 1j * xi, axis=-1)
    if not natural:
        want = want.reshape(2, 6, 16, 8, 4).transpose(0, 1, 4, 3, 2) \
            .reshape(2, 6, n)
    seen = []

    def power(yr, yi):
        seen.append((yr.shape, yr.dtype))
        return (yr * yr + yi * yi).reshape(-1, 3, n).sum(1)
    yr, yi = jax.jit(lambda a, b: F.long_fft(a, b, factors,
                                             natural=natural))(xr, xi)
    got = np.asarray(yr).astype(np.float64) + 1j * np.asarray(yi)
    assert got.shape == (2, 6, n)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6
    p = np.asarray(jax.jit(lambda a, b: F.long_fft(
        a, b, factors, natural=natural, then=power, keep=3))(xr, xi))
    assert seen == [((3, n), np.float32)]       # traced once, a chunk
    ref = (np.abs(want) ** 2).reshape(4, 3, n).sum(1)
    assert p.shape == (4, n)
    assert np.max(np.abs(p - ref)) / np.max(ref) < 2e-6


def test_long_fft_refuses_a_length_its_factors_do_not_make():
    import jax.numpy as jnp
    z = jnp.zeros((2, 512), jnp.float32)
    with pytest.raises(ValueError, match='do not factor'):
        F.long_fft(z, z, (4, 8, 8))


@pytest.mark.parametrize('n,want', [
    (1 << 20, (128, 64, 128)), (1 << 21, (128, 128, 128)),
    (1 << 15, (16, 16, 128)), (1 << 16, (32, 16, 128)),
    (1 << 17, (32, 32, 128)), (1 << 14, None), (4096, None),
    (1 << 22, None), (3 << 15, None)])
def test_levels_follow_from_the_length(n, want):
    """Two levels of 128 reach 16384; a power of two past it, up to
    128^3, takes three; nothing else does."""
    assert F.long_factors(n) == want
    if want:
        assert int(np.prod(want)) == n and max(want) <= F.MAX_FACTOR


def test_path_is_chosen_from_the_shape_and_the_documented_variables(
        monkeypatch):
    long = {'path': 'long', 'factors': [16, 16, 128], 'precision': 'high'}
    assert F.fft_path((1, 4, 2, 1 << 15), [3]) == long
    assert F.fft_path((1, 4, 2, 1 << 15), [-1]) == long
    assert F.fft_path((16384, 2, 4096), [2]) == {'path': 'xla'}
    # the inverse as the forward (coherent dedispersion's second
    # transform); not the last axis, several axes, double precision
    assert F.fft_path((2, 1 << 15), [1], inverse=True) == long
    assert F.fft_path((4, 1 << 15, 2), [1]) == {'path': 'xla'}
    assert F.fft_path((1 << 15, 1 << 15), [0, 1]) == {'path': 'xla'}
    assert F.fft_path((2, 1 << 15), [1], dtype='complex128') == \
        {'path': 'xla'}
    monkeypatch.setenv('BF_FFT_DFT_DTYPE', 'bf16')
    assert F.fft_path((2, 1 << 15), [1]) == dict(long, precision='default')
    monkeypatch.setenv('BF_FFT_IMPL', 'dftmm')
    assert F.fft_path((2, 1 << 15), [1]) == {'path': 'dftmm'}


def test_dispatch_takes_the_long_path_past_two_levels():
    """fftn_dispatch of a 32768-point axis is long_fft's result,
    joined; of a 4096-point axis, jnp.fft's."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    for n in (1 << 15, 4096):
        x = (rng.standard_normal((2, n)) + 1j * rng.standard_normal((2, n))) \
            .astype(np.complex64)
        got = np.asarray(jax.jit(lambda v: F.fftn_dispatch(v, [1]))(
            jnp.asarray(x)))
        assert got.dtype == np.complex64
        want = np.fft.fft(x.astype(np.complex128), axis=1)
        assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6
    text = jax.jit(lambda v: F.fftn_dispatch(v, [1])).lower(
        jax.ShapeDtypeStruct((2, 1 << 15), jnp.complex64)).as_text()
    assert 'fft' not in text.lower().replace('fftn_dispatch', '')
    text = jax.jit(lambda v: F.fftn_dispatch(v, [1])).lower(
        jax.ShapeDtypeStruct((2, 4096), jnp.complex64)).as_text()
    assert 'fft' in text.lower().replace('fftn_dispatch', '')


# ---------------------------------------------------------------------------
# the chain at rehearsal size, against the configuration's reference
# ---------------------------------------------------------------------------

def _config():
    path = os.path.join(ROOT, 'perfbench', 'configs', 'gpuspec_hsr')
    spec = importlib.util.spec_from_file_location('cfg_gpuspec_hsr',
                                                  path + '.py')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(path + '.json') as f:
        cfg = json.load(f)
    over = cfg['rehearse']
    cfg = dict(cfg, **{k: v for k, v in over.items() if k != 'input'})
    cfg['input'] = dict(cfg['input'], **over['input'])
    return cfg, mod


class _Sequences(SourceBlock):
    """A host source of several sequences, one a name, each a list of
    one-frame gulps in ci8 storage."""

    def __init__(self, sequences, header):
        super(_Sequences, self).__init__(sorted(sequences), 1)
        self._sequences, self._header = sequences, header

    def create_reader(self, name):
        import contextlib
        return contextlib.nullcontext(iter(self._sequences[name]))

    def on_sequence(self, reader, name):
        return [dict(self._header, name=name)]

    def on_data(self, reader, ospans):
        gulp = next(reader, None)
        if gulp is None:
            return [0]
        dst = ospans[0].data.as_numpy()
        np.copyto(dst.view(np.uint8), gulp.view(np.uint8))
        return [1]


def _voltages(cfg, n, seed):
    ci8 = np.dtype([('re', np.int8), ('im', np.int8)])
    shape = (1,) + tuple(cfg['input']['frame_shape'])
    rng = np.random.default_rng(seed)
    return [rng.integers(-64, 64, shape + (2,), dtype=np.int8)
            .view(ci8).reshape(shape) for _ in range(n)]


def test_chain_against_the_reference_over_sequences():
    """host ring -> copy('tpu') -> fused(FFT, Stokes) -> accumulate ->
    copy('system') -> sink, as the benchmark builds it: a first
    sequence of three whole integrations and one gulp more, which is
    dropped with its sequence; a second of two.  Every product against
    the float64 reference of the gulps that made it; the long path
    took every gulp; the sum was made once an integration and added
    to, donated, for every other gulp."""
    cfg, mod = _config()
    nint = mod.gulps_per_product(cfg)
    nchan, _npol, nfine = cfg['input']['frame_shape']
    assert F.long_factors(nfine) is not None and nint >= 3
    first = _voltages(cfg, 3 * nint + 1, seed=1)
    second = _voltages(cfg, 2 * nint, seed=2)
    counters.reset()
    seen = []
    with bf.Pipeline() as p:
        src = _Sequences({'a': first, 'b': second}, mod.header(cfg))
        dev = bf.blocks.copy(src, space='tpu')
        acc = mod.chain(bf, dev, cfg)
        program = type(acc)._program

        def spy(self, x, idtype, is_first):
            seen.append((is_first, self._acc))
            return program(self, x, idtype, is_first)
        type(acc)._program = spy
        try:
            sink = GatherSink(bf.blocks.copy(acc, space='system'))
            p.run()
        finally:
            type(acc)._program = program
    out = sink.result()
    assert out.shape == (5, nchan, 4, nfine) and out.dtype == np.float32
    idx = np.arange(nchan)
    gulps = [first[k * nint:(k + 1) * nint] for k in range(3)] + \
        [second[k * nint:(k + 1) * nint] for k in range(2)]
    for k, made in enumerate(gulps):
        name, err = mod.compare(out[k], mod.reference(made, idx, cfg))
        assert name == 'rel_err' and err < 2e-6, (k, err)
    # the headers: a product's frame is nint gulps long, in both
    hdrs = sink.headers
    assert [h['name'] for h in hdrs] == ['a', 'b']
    for h in hdrs:
        t = h['_tensor']
        assert t['shape'] == [-1, nchan, 4, nfine] and t['dtype'] == 'f32'
        assert t['scales'][0][1] == nint
        assert t['labels'] == ['time', 'freq', 'pol', 'fine_time']
    ngulp = len(first) + len(second)
    assert counters.get('spectrometer.gulps') == ngulp
    assert counters.get('spectrometer.long_gulps') == ngulp
    assert counters.get('accumulate.gulps') == ngulp
    assert counters.get('accumulate.integrations') == 5
    # six integrations were begun; every other gulp was added in place
    assert counters.get('accumulate.acc_in_place') == ngulp - 6
    assert [f for f, _a in seen] == \
        ([True] + [False] * (nint - 1)) * 3 + [True] + \
        ([True] + [False] * (nint - 1)) * 2
    for is_first, before in seen[1:]:
        # what a gulp's program was given is gone: donated, or handed
        # to the ring (the first of the next integration finds none)
        assert before is None if is_first else before.is_deleted()
    assert acc._acc is None
    assert acc.impl_info == {
        'impl': 'long-spectrometer', 'accumulate': nint,
        'input': 'words',       # the chain's program starts from them
        'fft': dict(F.fft_path((1, nchan, 2, nfine), [3]), nfft=[nfine])}


@pytest.mark.parametrize('chunked', [False, True])
@pytest.mark.parametrize('lead', [(2,), (1, 3)])
def test_long_spectrometer_is_the_stage_chain(lead, chunked, monkeypatch):
    """compose_stages substitutes ops.spectrometer.long_spectrometer
    for FftStage -> DetectStage('stokes') on ci8 dual-pol voltages
    whose transform is past two levels, with or without a coarse
    channel axis, in one chunk or several: its Stokes spectra are the
    unsubstituted chain's, and float64's."""
    import jax
    from bifrost_tpu.stages import walk_headers, compose_stages
    n = 1 << 15
    if chunked:
        monkeypatch.setattr(F, '_CHUNK_BYTES', 2 * 8 * n)
    shape = lead + (2, n, 2)
    labels = ['time', 'freq', 'pol', 'fine_time'][-(len(lead) + 2):]
    labels[0] = 'time'
    hdr = {'_tensor': {'shape': [-1] + list(shape[1:-1]), 'dtype': 'ci8',
                       'labels': labels, 'scales': [[0, 1]] * len(labels),
                       'units': [None] * len(labels)}}
    rng = np.random.default_rng(4)
    v = rng.integers(-64, 64, shape, dtype=np.int8)
    stages = [FftStage('fine_time'), DetectStage('stokes')]
    headers = walk_headers(stages, hdr)
    fn, info = compose_stages(stages, headers, shape, np.dtype('int8'))
    assert info == {'impl': 'long-spectrometer',
                    'fft': {'path': 'long', 'factors': [16, 16, 128],
                            'precision': 'high', 'nfft': [n]}}
    plain, pinfo = compose_stages(stages, headers, shape, np.dtype('int8'),
                                  substitute=False)
    assert pinfo == {'impl': 'xla-fused', 'fft': info['fft']}
    got = np.asarray(jax.jit(fn)(v))
    assert got.shape == lead + (4, n) and got.dtype == np.float32
    x = v[..., 0].astype(np.float64) + 1j * v[..., 1]
    s = np.fft.fft(x, axis=-1)
    a, b = s[..., 0, :], s[..., 1, :]
    ab = a * np.conj(b)
    want = np.stack([abs(a) ** 2 + abs(b) ** 2, abs(a) ** 2 - abs(b) ** 2,
                     2 * ab.real, -2 * ab.imag], axis=-2)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 2e-6
    other = np.asarray(jax.jit(plain)(v))
    assert np.max(np.abs(got - other)) / np.max(np.abs(want)) < 2e-6


def test_other_chains_are_not_substituted():
    """A transform two levels reach, another detection, float
    voltages, a third stage: the stage chain runs as it is."""
    from bifrost_tpu.stages import (ReduceStage, walk_headers,
                                    match_long_spectrometer)

    def plan(stages, n=1 << 15, dtype='ci8', labels=None):
        labels = labels or ['time', 'pol', 'fine_time']
        hdr = {'_tensor': {'shape': [-1, 2, n], 'dtype': dtype,
                           'labels': labels, 'scales': [[0, 1]] * 3,
                           'units': [None] * 3}}
        shape = (4, 2, n, 2) if dtype == 'ci8' else (4, 2, n)
        return match_long_spectrometer(
            stages, walk_headers(stages, hdr), shape,
            np.dtype('int8' if dtype == 'ci8' else 'complex64'))
    fft, det = FftStage('fine_time'), DetectStage('stokes')
    assert plan([fft, det]) is not None
    assert plan([fft, det], n=4096) is None
    assert plan([fft, DetectStage('stokes_i')]) is None
    assert plan([fft, det], dtype='cf32') is None
    assert plan([FftStage('fine_time', apply_fftshift=True), det]) is None
    assert plan([fft, det, ReduceStage('fine_time', 4)]) is None
    assert plan([fft]) is None


@pytest.mark.parametrize('space', ['tpu', 'system'])
def test_accumulate_other_types_and_the_host_path(space):
    """The block's other uses keep their results: complex voltages in
    ci8 integrate as cf32 on the device, a host ring of float32
    integrates with numpy; both count their gulps."""
    ci8 = np.dtype([('re', np.int8), ('im', np.int8)])
    rng = np.random.default_rng(3)
    raw = rng.integers(-64, 64, (6, 5, 2), dtype=np.int8)
    if space == 'tpu':
        data, dtype, odtype = raw.view(ci8).reshape(6, 5), 'ci8', 'cf32'
        want = (raw[..., 0].astype(np.float32) + 1j * raw[..., 1]) \
            .reshape(2, 3, 5).sum(1)
    else:
        data, dtype, odtype = raw[..., 0].astype(np.float32), 'f32', None
        want = data.reshape(2, 3, 5).sum(1)
    hdr = {'name': 'v', 'time_tag': 0,
           '_tensor': {'shape': [-1, 5], 'dtype': dtype,
                       'labels': ['time', 'x'], 'scales': [[0, 2]] * 2,
                       'units': [None] * 2}}
    counters.reset()
    with bf.Pipeline() as p:
        b = _Sequences({'a': [data[k:k + 1] for k in range(6)]}, hdr)
        if space == 'tpu':
            b = bf.blocks.copy(b, space='tpu')
        b = bf.blocks.accumulate(b, 3, dtype=odtype)
        if space == 'tpu':
            b = bf.blocks.copy(b, space='system')
        sink = GatherSink(b)
        p.run()
    np.testing.assert_array_equal(sink.result(), want)
    assert sink.headers[0]['_tensor']['scales'][0] == [0, 6]
    assert counters.get('accumulate.gulps') == 6
    assert counters.get('accumulate.integrations') == 2
    assert counters.get('accumulate.acc_in_place') == \
        (4 if space == 'tpu' else 0)
