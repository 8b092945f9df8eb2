"""The short channeliser on the gulp's word rows
(stages.match_channeliser, ops.channelise): FftStage over a ci8
gulp's last axis of a length that divides 128 -> DetectStage('stokes_i')
-> ReduceStage over the frame axis [-> TransposeStage] as one
block-diagonal product a row of words, against the ``xla-fused``
composition of the same stages; the chains it leaves alone; its
lower-precision control; FusedBlock's counters."""

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu.stages import (DetectStage, FftStage, ReduceStage,
                                TransposeStage, compose_stages,
                                from_words, match_channeliser,
                                walk_headers)
from bifrost_tpu.telemetry import counters

from util import GatherSink, NumpySourceBlock

#: (frame shape (freq, pol, fine_time), spectra summed, frames): the
#: bl-frb-search rehearsal's (a frame of 64 words, one row) and a
#: frame of 1024 words, eight rows of 128, as at the deployment
SHAPES = {'rehearsal': ((4, 2, 8), 16, 64),
          'rows_of_128': ((64, 2, 8), 128, 256)}
LABELS = ['time', 'freq', 'pol', 'fine_time']
CORNER = ['pol', 'freq', 'fine_freq', 'time']


def _header(frame):
    return {'name': 'channeliser', '_tensor': {
        'shape': [-1] + list(frame), 'dtype': 'ci8', 'labels': LABELS,
        'scales': [[0, 1e-6], [1100.0, 2.9296875], [0, 1], [0, 1e-6]],
        'units': ['s', 'MHz', None, 's']}}


def _chain(nspec, shift=True, corner=True, detect='stokes_i',
           axis='fine_time'):
    stages = [FftStage(axis, axis_labels='fine_freq',
                       apply_fftshift=shift),
              DetectStage(detect), ReduceStage('time', nspec)]
    return stages + ([TransposeStage(CORNER)] if corner else [])


def _gulp(frame, ntime, seed=1):
    rng = np.random.default_rng(seed)
    return rng.integers(-64, 64, size=(ntime,) + tuple(frame) + (2,),
                        dtype=np.int8)


@pytest.mark.parametrize('form', ['words', 'pairs'])
@pytest.mark.parametrize('corner', [True, False], ids=['corner', 'no_corner'])
@pytest.mark.parametrize('shift', [True, False], ids=['shift', 'no_shift'])
@pytest.mark.parametrize('size', sorted(SHAPES))
def test_row_plan_equals_the_xla_fused_composition(size, shift, corner,
                                                   form):
    import jax
    frame, nspec, ntime = SHAPES[size]
    stages = _chain(nspec, shift, corner)
    headers = walk_headers(stages, _header(frame))
    shape = (ntime,) + frame + (2,)
    fn, info = compose_stages(stages, headers, shape, np.dtype('int8'))
    assert info == {'impl': 'row-channeliser', 'nfft': 8, 'shift': shift,
                    'precision': 'split'}
    plain, pinfo = compose_stages(stages, headers, shape,
                                  np.dtype('int8'), substitute=False)
    assert pinfo['impl'] == 'xla-fused'
    pairs = _gulp(frame, ntime)
    want = np.asarray(jax.jit(plain)(pairs))
    if form == 'words':
        got = jax.jit(from_words(fn, shape))(pairs.view(np.int16).ravel())
    else:
        got = jax.jit(fn)(pairs)
    got = np.asarray(got)
    assert got.shape == want.shape and got.dtype == want.dtype == np.float32
    assert list(want.shape) == [n if n != -1 else ntime // nspec
                                for n in headers[-1]['_tensor']['shape']]
    assert np.max(np.abs(got - want)) <= 1e-6 * np.max(want)


def test_row_plan_against_float64():
    """The shifted spectra's order and scale from numpy's transform:
    fine channels ascending from -nfft/2, Stokes I summed over the
    spectra, the corner turn last."""
    import jax
    frame, nspec, ntime = SHAPES['rows_of_128']
    stages = _chain(nspec)
    shape = (ntime,) + frame + (2,)
    fn, _ = compose_stages(stages, walk_headers(stages, _header(frame)),
                           shape, np.dtype('int8'))
    pairs = _gulp(frame, ntime, seed=5)
    v = pairs[..., 0].astype(np.float64) + 1j * pairs[..., 1]
    power = np.abs(np.fft.fftshift(np.fft.fft(v), axes=-1)) ** 2
    want = power.sum(axis=2).reshape((ntime // nspec, nspec) + frame[::2]) \
        .sum(axis=1).transpose(1, 2, 0)[None]
    got = np.asarray(jax.jit(fn)(pairs))
    assert np.max(np.abs(got - want)) <= 1e-6 * np.max(want)


def _match(stages, frame=(4, 2, 8), ntime=64, **kwargs):
    headers = walk_headers(stages, _header(frame))
    return match_channeliser(stages, headers, (ntime,) + frame + (2,),
                             np.dtype('int8'), **kwargs)


def test_chains_it_leaves_alone():
    import jax
    from jax.sharding import Mesh
    assert _match(_chain(16)) is not None
    # all four Stokes: the pols are not summed
    assert _match(_chain(16, detect='stokes')) is None
    # a transform of a gpuspec length: no row holds one
    assert _match(_chain(16), frame=(4, 2, 4096), ntime=32) is None
    # a transform of an axis that is not the last
    assert _match([FftStage('freq', axis_labels='fine_freq'),
                   DetectStage('stokes_i'), ReduceStage('time', 16)]) is None
    # no sum over spectra
    assert _match(_chain(16)[:2]) is None
    # a sum over another axis than the frames'
    assert _match([FftStage('fine_time'), DetectStage('stokes_i'),
                   ReduceStage('freq', 2)]) is None
    # across a mesh, where a shard comes as pairs
    mesh = Mesh(np.array(jax.devices()[:2]), ('time',))
    assert _match(_chain(16), mesh=mesh) is None


@pytest.mark.parametrize('cell', ['gpuspec', 'gpuspec-hsr'])
def test_the_spectrometer_chains_keep_their_selection(cell):
    """The gpuspec chain (4096 points, Stokes, a reduce over frequency)
    and gpuspec-hsr's (2^20 points, Stokes) are not the channeliser's:
    compose_stages gives them the plans of the matchers tried before
    it, as before it existed."""
    nfft, stages, impl = {
        'gpuspec': (4096, [FftStage('fine_time', axis_labels='freq'),
                           DetectStage('stokes', axis='pol'),
                           ReduceStage('freq', 4)],
                    ('pallas-spectrometer', 'xla-fused')),
        'gpuspec-hsr': (1 << 20, [FftStage('fine_time'),
                                  DetectStage('stokes')],
                        ('long-spectrometer',))}[cell]
    hdr = {'_tensor': {'shape': [-1, 2, nfft], 'dtype': 'ci8',
                       'labels': ['time', 'pol', 'fine_time'],
                       'scales': [[0, 1]] * 3, 'units': [None] * 3}}
    headers = walk_headers(stages, hdr)
    shape = (16, 2, nfft, 2)
    assert match_channeliser(stages, headers, shape, 'int8') is None
    _, info = compose_stages(stages, headers, shape, np.dtype('int8'))
    assert info['impl'] in impl


def test_lower_precision_control_is_one_pass(monkeypatch):
    """BF_FFT_DFT_DTYPE=bf16, the matrix forms' control: the record
    says one pass, and the spectra depart from float32's by a bfloat16
    matrix's rounding."""
    import jax
    frame, nspec, ntime = SHAPES['rows_of_128']
    stages = _chain(nspec)
    headers = walk_headers(stages, _header(frame))
    shape = (ntime,) + frame + (2,)
    pairs = _gulp(frame, ntime)
    fn, info = compose_stages(stages, headers, shape, np.dtype('int8'))
    want = np.asarray(jax.jit(fn)(pairs))
    monkeypatch.setenv('BF_FFT_DFT_DTYPE', 'bf16')
    fn, info = compose_stages(stages, headers, shape, np.dtype('int8'))
    assert info['precision'] == 'default'
    err = np.max(np.abs(np.asarray(jax.jit(fn)(pairs)) - want)) / \
        np.max(want)
    assert 1e-5 < err < 1e-2


def _run(stages, frame, ntime, hdr=None, ngulp=3):
    hdr = dict(hdr or _header(frame), gulp_nframe=ntime)
    gulps = [_gulp(frame, ntime, seed=s).view(
        np.dtype([('re', np.int8), ('im', np.int8)]))[..., 0]
        for s in range(ngulp)]
    counters.reset()
    with bf.Pipeline() as p:
        src = NumpySourceBlock(gulps, hdr, ntime)
        blk = bf.blocks.fused(bf.blocks.copy(src, space='tpu'), stages)
        sink = GatherSink(bf.blocks.copy(blk, space='system'))
        p.run()
    return blk, sink, counters.snapshot()


def test_fused_block_counts_the_channelised_gulps():
    frame, nspec, ntime = SHAPES['rehearsal']
    blk, sink, counts = _run(_chain(nspec), frame, ntime)
    info = dict(blk.impl_info)
    # what the process's automatic selections refused before, if any
    info.pop('refused', None)
    assert info == {'impl': 'row-channeliser', 'nfft': 8, 'shift': True,
                    'precision': 'split', 'input': 'words'}
    assert len(sink.gulps) == 3
    assert counts['channelise.gulps'] == counts['channelise.row_gulps'] == 3
    # a transform of the chain all the same, on no long path
    assert counts['spectrometer.gulps'] == 3
    assert counts.get('spectrometer.long_gulps', 0) == 0


def test_fused_block_counts_a_short_transform_off_the_rows():
    """A chain that opens with an 8-point transform and is not the
    channeliser's (all four Stokes): counted, at 0 rows."""
    frame, nspec, ntime = SHAPES['rehearsal']
    blk, _sink, counts = _run(_chain(nspec, detect='stokes'), frame, ntime)
    assert blk.impl_info['impl'] == 'xla-fused'
    assert counts['channelise.gulps'] == 3
    assert counts['channelise.row_gulps'] == 0


def test_fused_block_counts_nothing_for_a_transform_no_row_holds():
    """A chain whose transform (256 points) no row of 128 words holds
    counts nothing of the channeliser's."""
    hdr = {'name': 'long', '_tensor': {
        'shape': [-1, 2, 256], 'dtype': 'ci8',
        'labels': ['time', 'pol', 'fine_time'],
        'scales': [[0, 1]] * 3, 'units': [None] * 3}}
    stages = [FftStage('fine_time'), DetectStage('stokes_i'),
              ReduceStage('time', 4)]
    blk, _sink, counts = _run(stages, (2, 256), 8, hdr=hdr, ngulp=1)
    assert blk.impl_info['impl'] == 'xla-fused'
    assert 'channelise.gulps' not in counts
    assert counts['spectrometer.gulps'] == 1
