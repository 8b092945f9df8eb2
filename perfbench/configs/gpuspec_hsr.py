"""gpuspec-hsr: the chain, its plain reference and its work count.

Breakthrough Listen's high-spectral-resolution product as its
reduction makes it (``rawspec -f 1048576 -t 51``): every coarse
channel of a recording through a 2^20-point transform, detected, 51
spectra summed.  The chain is upstream's ``gpuspec_simple.py`` with
those numbers: FftStage('fine_time') -> DetectStage('stokes'), fused,
then ``accumulate(51)``, on ci8 dual-polarisation voltages labelled
(time, freq, pol, fine_time), one transform's stretch of all coarse
channels a gulp.  The reference below imports nothing of the program
and is float64 numpy throughout.
"""

import math

import numpy as np


def shapes(cfg):
    nchan, npol, nfine = cfg['input']['frame_shape']
    return cfg['gulp_nframe'], nchan, npol, nfine


def header(cfg):
    _, nchan, npol, nfine = shapes(cfg)
    return {'name': 'perfbench-gpuspec-hsr', 'time_tag': 0,
            '_tensor': {'shape': [-1, nchan, npol, nfine], 'dtype': 'ci8',
                        'labels': ['time', 'freq', 'pol', 'fine_time'],
                        'scales': [[0, 1]] * 4, 'units': [None] * 4}}


def chain(bf, upstream, cfg):
    """The device chain, downstream of a 'tpu'-space ring, at the
    program's defaults."""
    from bifrost_tpu.stages import FftStage, DetectStage
    spectra = bf.blocks.fused(upstream, [FftStage('fine_time'),
                                         DetectStage('stokes')])
    return bf.blocks.accumulate(spectra, cfg['nframe_per_integration'])


def gulps_per_product(cfg):
    return cfg['nframe_per_integration'] // cfg['gulp_nframe']


def control_env(cfg):
    """The program's own nearest-lower-precision path at this length
    (the control on the chip): the three-level transform with one
    bf16 pass of the MXU a matrix product, where float32 accuracy
    takes three."""
    return {'BF_FFT_DFT_DTYPE': 'bf16'}


def _product_index(rng):
    """Index of the product ``rng`` was made for: the sampler
    (traffic.Sampler.where) seeds it with [seed, 3, k]."""
    try:
        return int(rng.bit_generator.seed_seq.entropy[2])
    except (AttributeError, IndexError, TypeError):
        return None


def pick(rng, cfg, full):
    """Coarse channels of one product that are compared, each whole
    (every fine bin, all four Stokes): one seeded channel from each of
    ``channels_per_product`` equal stretches of the band, so two, one
    from each half; of product 0 the first and the last channel."""
    nchan = cfg['input']['frame_shape'][0]
    n = min(cfg['sample']['channels_per_product'], nchan)
    edges = np.arange(n + 1) * nchan // n
    idx = rng.integers(edges[:-1], edges[1:])
    if _product_index(rng) == 0:
        idx[0], idx[-1] = 0, nchan - 1
    return idx


def take(product, idx):
    """The compared part of one product (1, nchan, 4, nfine): a copy
    of the picked channels' spectra."""
    return product[0][idx]


def _bf16(x):
    import ml_dtypes
    return x.astype(np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def _split(n):
    """Factors of n, each at most 128, the first the smallest: the
    control's levels."""
    out = []
    while n > 128:
        out.append(128)
        n //= 128
    return [n] + out


def _rounded_fft(v, factors):
    """The transform of the last axis of complex128 ``v`` as levels of
    DFT matrix products (decimation in time, one factor a level) with
    every product's operands, data and matrix, rounded to bfloat16 and
    summed in float64: what one bf16 pass of the MXU computes.  The
    twiddles between levels stay float64."""
    n = v.shape[-1]
    n1 = factors[0]
    i = np.arange(n1)
    f = np.exp(-2j * np.pi * ((i[:, None] * i[None, :]) % n1) / n1)
    fr, fi = _bf16(f.real), _bf16(f.imag)
    if len(factors) == 1:
        ar, ai = _bf16(v.real), _bf16(v.imag)
        return (ar @ fr - ai @ fi) + 1j * (ar @ fi + ai @ fr)
    m = n // n1
    x = v.reshape(v.shape[:-1] + (n1, m))
    ar, ai = _bf16(x.real), _bf16(x.imag)
    a = (fr @ ar - fi @ ai) + 1j * (fr @ ai + fi @ ar)      # (..., k1, m)
    a *= np.exp(-2j * np.pi * (i[:, None] * np.arange(m)[None, :]) / n)
    y = _rounded_fft(a, factors[1:])                        # (..., k1, km)
    # X[k1 + n1 km]
    return np.swapaxes(y, -1, -2).reshape(v.shape)


def _stokes(gulp, idx, precision):
    """(len(idx), 4, nfine) float64 Stokes spectra of the picked
    channels of one host gulp (1, nchan, npol, nfine) in ci8 storage."""
    frames = gulp[0][idx]
    # (re, im) int8 pairs -> complex128, by bytes (gpuspec.py)
    v = frames.view(np.int8).reshape(frames.shape + (2,)) \
        .astype(np.float64).view(np.complex128)[..., 0]
    if precision == 'float64':
        s = np.fft.fft(v, axis=-1)
    elif precision == 'bfloat16':
        s = _rounded_fft(v, _split(v.shape[-1]))
    else:
        raise ValueError('unknown precision %r' % precision)
    x, y = s[:, 0], s[:, 1]
    xx = x.real ** 2 + x.imag ** 2
    yy = y.real ** 2 + y.imag ** 2
    xy = x * np.conj(y)
    return np.stack([xx + yy, xx - yy, 2 * xy.real, -2 * xy.imag], axis=1)


def reference(gulps, idx, cfg, precision='float64'):
    """What ``take`` should hold for the product of ``gulps`` (host
    gulps in ci8 storage, in the order they were integrated): the sum
    of their Stokes spectra.  The pool holds few distinct gulps, so
    each one's spectra are computed once and counted as often as it
    came.  ``precision='bfloat16'`` is the control (:func:`_rounded_fft`)."""
    distinct, times = {}, {}
    for g in gulps:
        distinct[id(g)] = g
        times[id(g)] = times.get(id(g), 0) + 1
    total = 0.0
    for key, g in distinct.items():
        total = total + times[key] * _stokes(g, idx, precision)
    return total


def compare(got, want):
    """('rel_err', value): worst error over the reference's peak."""
    got = np.asarray(got, dtype=np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return 'rel_err', float('inf')
    return 'rel_err', float(np.max(np.abs(got - want)) /
                            (np.max(np.abs(want)) or 1.0))


def work(cfg):
    """Per gulp, from the shapes alone, whatever implements it: ci8 in
    and a gulp's share of the float32 Stokes product out, and
    5 N log2 N flops per transform."""
    ntime, nchan, npol, nfine = shapes(cfg)
    return {'samples': ntime * nchan * npol * nfine,
            'bytes': ntime * nchan * npol * nfine * 2
            + nchan * 4 * nfine * 4 / gulps_per_product(cfg),
            'flops': 5.0 * nfine * math.log2(nfine) * ntime * nchan * npol,
            'int8_ops': 0.0}
