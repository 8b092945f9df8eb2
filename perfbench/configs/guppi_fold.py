"""guppi-fold: the chain, its plain reference and its work count.

GUPPI's coherent-dedispersion fold mode, as NANOGrav times its pulsars
with it, at one node's share of the band: every 1.5625 MHz channel of
8-bit complex dual-polarisation voltages through overlap-save windows,
forward transform, the chirp that undoes the ISM's transfer function,
inverse transform, half the windows' overlap discarded at each end;
detected to Stokes I, Q, U, V; folded into 2048 phase bins of the
pulsar's period by an integer rule; 17 gulps summed.  A gulp is one
RAW block whose first ``overlap`` samples repeat the previous block's
tail, so each block is dedispersed on its own.  The chain is the program's fused block over
three stages, then ``accumulate(17)``, built through its public API at
its defaults.

The reference below imports nothing of the program and is float64
numpy throughout, written from the equations:

    H(f) = exp(+2 pi i 1e6 KDM DM f^2 / (f_c^2 (f_c + f))),
    f, f_c in MHz, KDM = 4.148808e3 s MHz^2 / (pc cm^-3);
    bin(n) = floor(nbin ((period_den n) mod period_num) / period_num)

H is the medium's transfer function in numpy's convention (the higher
frequencies arrive first), and the windows' spectra are multiplied by
its conjugate.  The chirp's response reaches both ways in time, so a
window keeps its outputs from ``overlap // 2`` to ``overlap // 2 +
new / nwin``, and output j of gulp g is absolute sample n = g * new + j
- lag, lag = overlap - overlap // 2, n counted from the first new
sample of the stream (the header's time origin).  The fold's
phase depends on where a gulp lies, so ``pick`` carries the product's
index in front of the channels it picks, read from the sampler's seed
as gpuspec_hsr.py reads it.  Departures from the source: a constant
rational period in place of a polyco; Stokes in place of GUPPI's
coherences (a fixed linear map of them); float32 profiles without
PSRFITS's scale and offset; a replayed block's overlap is not its
predecessor's tail, which nothing reads across blocks.
"""

import math

import numpy as np

#: the dispersion constant, s MHz^2 / (pc cm^-3)
KDM = 4.148808e3


def shapes(cfg):
    """(ntime, nchan, npol, nsamp, nfft, overlap, nwin, new): a gulp's
    frames, channels, polarisations and samples a block; the windows
    and the new samples of a block."""
    nchan, npol, nsamp = cfg['input']['frame_shape']
    nfft = cfg['transform']['nfft']
    overlap = cfg['transform']['overlap']
    nwin = (nsamp - overlap) // (nfft - overlap)
    return (cfg['gulp_nframe'], nchan, npol, nsamp, nfft, overlap, nwin,
            nwin * (nfft - overlap))


def header(cfg):
    _, nchan, npol, nsamp, _, _, _, new = shapes(cfg)
    band = cfg['band']
    df = band['chan_mhz']
    dt = 1e-6 / df
    return {'name': 'perfbench-guppi-fold', 'time_tag': 0,
            '_tensor': {'shape': [-1, nchan, npol, nsamp], 'dtype': 'ci8',
                        'labels': ['time', 'freq', 'pol', 'fine_time'],
                        'scales': [[0, new * dt],
                                   [band['lowest_mhz'] + df / 2, df],
                                   [0, 1], [-cfg['transform']['overlap']
                                            * dt, dt]],
                        'units': ['s', 'MHz', None, 's']}}


def chain(bf, upstream, cfg):
    """The device chain, downstream of a 'tpu'-space ring, at the
    program's defaults."""
    from bifrost_tpu.stages import (CoherentDedispersionStage, DetectStage,
                                    FoldStage)
    fold = cfg['fold']
    folded = bf.blocks.fused(upstream, [
        CoherentDedispersionStage(cfg['dedispersion']['dm'],
                                  cfg['transform']['nfft'],
                                  cfg['transform']['overlap']),
        DetectStage('stokes'),
        FoldStage(fold['nbin'], fold['period_num'], fold['period_den'])])
    return bf.blocks.accumulate(folded, cfg['nframe_per_integration'])


def gulps_per_product(cfg):
    return cfg['nframe_per_integration'] // cfg['gulp_nframe']


def control_env(cfg):
    """The program's own nearest-lower-precision path: both three-level
    transforms with one bf16 pass of the MXU a matrix product, where
    float32 accuracy takes three."""
    return {'BF_FFT_DFT_DTYPE': 'bf16'}


def _product_index(rng):
    """Index of the product ``rng`` was made for: the sampler
    (traffic.Sampler.where) seeds it with [seed, 3, k]."""
    try:
        return int(rng.bit_generator.seed_seq.entropy[2])
    except (AttributeError, IndexError, TypeError):
        return None


def pick(rng, cfg, full):
    """[k, channels...]: the product's index k, which the reference
    needs for the fold's phase, then the channels of it that are
    compared, each whole (every bin, all four Stokes): one seeded
    channel from each of ``channels_per_product`` equal stretches of
    the band; of product 0 the first and the last."""
    k = _product_index(rng)
    if k is None:
        raise ValueError('guppi-fold: the sampler\'s generator does not '
                         'say which product it picks for')
    nchan = cfg['input']['frame_shape'][0]
    n = min(cfg['sample']['channels_per_product'], nchan)
    edges = np.arange(n + 1) * nchan // n
    idx = rng.integers(edges[:-1], edges[1:])
    if k == 0:
        idx[0], idx[-1] = 0, nchan - 1
    return np.concatenate([[k], idx]).astype(np.int64)


def take(product, idx):
    """The compared part of one product (1, nchan, 4, nbin): a copy of
    the picked channels' profiles."""
    return product[0][np.asarray(idx[1:])]


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
    """The forward transform of the last axis of complex128 ``v`` as
    levels of DFT matrix products with every product's operands, data
    and matrix, rounded to bfloat16 and summed in float64: what one
    bf16 pass of the MXU computes (as gpuspec_hsr.py's control)."""
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
    a = (fr @ ar - fi @ ai) + 1j * (fr @ ai + fi @ ar)
    a *= np.exp(-2j * np.pi * (i[:, None] * np.arange(m)[None, :]) / n)
    y = _rounded_fft(a, factors[1:])
    return np.swapaxes(y, -1, -2).reshape(v.shape)


def _transforms(precision):
    """(forward, unnormalised inverse) of the last axis."""
    if precision == 'float64':
        return (lambda v: np.fft.fft(v, axis=-1),
                lambda v: np.fft.ifft(v, axis=-1) * v.shape[-1])
    if precision == 'bfloat16':
        def fwd(v):
            return _rounded_fft(v, _split(v.shape[-1]))
        return fwd, lambda v: np.conj(fwd(np.conj(v)))
    raise ValueError('unknown precision %r' % precision)


def _chirp(cfg, chan):
    """H*(f) of channel ``chan`` at the transform's bins, numpy's
    order: the conjugate of the medium's transfer function."""
    nfft = cfg['transform']['nfft']
    df = cfg['band']['chan_mhz']
    fc = cfg['band']['lowest_mhz'] + df * (chan + 0.5)
    f = np.fft.fftfreq(nfft, d=1.0 / df)
    return np.exp(-2j * np.pi * 1e6 * KDM * cfg['dedispersion']['dm']
                  * f ** 2 / (fc ** 2 * (fc + f)))


def _stokes(frame, chan, cfg, precision):
    """(4, new) float64 Stokes I, Q, U, V of channel ``chan`` of one
    frame (nchan, npol, nsamp) in ci8 storage, dedispersed."""
    _, _, npol, _, nfft, overlap, nwin, new = shapes(cfg)
    step = nfft - overlap
    # (re, im) int8 pairs -> complex128, by bytes
    v = frame[chan].view(np.int8).reshape(npol, -1, 2) \
        .astype(np.float64).view(np.complex128)[..., 0]
    w = np.stack([v[:, i * step:i * step + nfft] for i in range(nwin)],
                 axis=1)                                 # (npol, nwin, nfft)
    fwd, inv = _transforms(precision)
    head = overlap // 2
    z = inv(fwd(w) * _chirp(cfg, chan))[..., head:head + step] \
        .reshape(npol, new)
    x, y = z
    xx = x.real ** 2 + x.imag ** 2
    yy = y.real ** 2 + y.imag ** 2
    xy = x * np.conj(y)
    return np.stack([xx + yy, xx - yy, 2 * xy.real, -2 * xy.imag])


def bins(frame_index, cfg):
    """The bin of each dedispersed sample of the frame ``frame_index``
    of the stream (from 0), n = frame_index * new + j - lag: the integer
    rule, with Python's integers for the start residue and numpy's
    int64 after it."""
    new, overlap = shapes(cfg)[-1], cfg['transform']['overlap']
    fold = cfg['fold']
    P, D, nbin = fold['period_num'], fold['period_den'], fold['nbin']
    lag = overlap - overlap // 2
    r0 = (D * (int(frame_index) * new - lag)) % P
    j = np.arange(new, dtype=np.int64)
    return (nbin * ((r0 + D * j) % P)) // P


def reference(gulps, idx, cfg, precision='float64'):
    """What ``take`` should hold for the product ``idx[0]`` made of
    ``gulps`` (host gulps in ci8 storage, in the order they were
    integrated): the sum of every frame's folded Stokes, each at its
    own phase.  A distinct gulp's Stokes are computed once a channel
    and binned at each place it came.  ``precision='bfloat16'`` is the
    control (:func:`_rounded_fft`)."""
    k, chans = int(idx[0]), [int(c) for c in idx[1:]]
    ntime = cfg['gulp_nframe']
    nbin = cfg['fold']['nbin']
    first = k * gulps_per_product(cfg) * ntime
    out = np.zeros((len(chans), 4, nbin))
    memo = {}
    for i, g in enumerate(gulps):
        for t in range(ntime):
            b = bins(first + i * ntime + t, cfg)
            for n, c in enumerate(chans):
                key = (id(g), t, c)
                if key not in memo:
                    memo[key] = _stokes(g[t], c, cfg, precision)
                for s in range(4):
                    out[n, s] += np.bincount(b, weights=memo[key][s],
                                             minlength=nbin)
    return out


def compare(got, want):
    """('rel_err', value): worst error over the reference's peak."""
    got = np.asarray(got, dtype=np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return 'rel_err', float('inf')
    return 'rel_err', float(np.max(np.abs(got - want)) /
                            (np.max(np.abs(want)) or 1.0))


def work(cfg):
    """Per gulp, from the shapes alone, whatever implements it: the
    gulp's new samples (what real time is measured in); the gulp's
    ci8 bytes in and a gulp's share of the float32 product out; two
    transforms of 5 N log2 N flops a window and 6 flops a point for
    the chirp."""
    ntime, nchan, npol, nsamp, nfft, _, nwin, new = shapes(cfg)
    nbin = cfg['fold']['nbin']
    windows = ntime * nchan * npol * nwin
    return {'samples': ntime * nchan * npol * new,
            'bytes': ntime * nchan * npol * nsamp * 2
            + nchan * 4 * nbin * 4 / gulps_per_product(cfg),
            'flops': 2 * 5.0 * nfft * math.log2(nfft) * windows
            + 6.0 * nfft * windows,
            'int8_ops': 0.0}
