"""Coherent dedispersion by overlap-save (Hankins & Rickett 1975).

The interstellar medium delays the offset f (MHz) from the centre f_c
of a channel by KDM * DM * ((f_c + f)^-2 - f_c^-2) seconds (DM in
pc cm^-3): the higher frequencies arrive first.  In the transform
convention of numpy and cuFFT (forward exp(-2 pi i ...)) the medium's
transfer function is

    H(f) = exp(+2 pi i * 1e6 * KDM * DM * f^2 / (f_c^2 (f_c + f))),

and the chirp that undoes it is its inverse, the conjugate H*(f).  Its
group delay is zero at the channel's centre and takes both signs
across the channel, so its impulse response reaches about half the
chirp's span (:func:`chirp_span_samples`) each way in time.

A channel's voltages are cut into windows of ``nfft`` samples that
overlap by ``overlap``; each window goes forward through a transform,
is multiplied by the channel's chirp, and goes back through the
unnormalised inverse (cuFFT's convention: a factor ``nfft``).  The
circular convolution wraps at both ends of a window, so ``overlap //
2`` outputs are dropped from its head and the rest of the overlap from
its tail (:func:`head`), as DSPSR drops its filter's negative and
positive halves.  Windows step by ``nfft - overlap``, so the kept
outputs tile the stream: a block of ``overlap + nwin * (nfft -
overlap)`` samples gives ``nwin * (nfft - overlap)``, from its sample
``overlap // 2`` on.  GUPPI writes every RAW block with its
predecessor's last ``overlap`` samples in front (the header's OVERLAP),
so a block's output starts ``overlap - overlap // 2`` samples before its
first new sample and ends as far before its last, and the next block's
output goes on from there: no sample is lost or made twice.

The chirp is made here once, on the host, in float64 (its phase runs
to thousands of radians at a timing array's DMs), and reaches the
device as two float32 planes that are ARGUMENTS of the gulp's program,
not constants folded into it.  Between the two transforms a window's
spectrum is only multiplied by the chirp, element by element, so it is
held in whatever order the forward transform leaves it in: on the
three-level path (ops.fft.long_fft) the levels' own order, (k1, k2,
k3) flattened, which the inverse takes as it is, and the chirp is
permuted to that order once a sequence, on the host
(:func:`in_transform_order`); on jnp.fft's path frequency order.  The
path record (:func:`transform_path`) decides both, and says which
(``'order': 'levels'``).
"""

from __future__ import annotations

import numpy as np

__all__ = ['KDM', 'chirp', 'chirp_span_samples', 'window_count', 'head',
           'windows', 'transform_path', 'transform_paths',
           'in_transform_order', 'dedisperse']

#: dispersion constant: the delay of frequency f (MHz) is
#: KDM * DM * f^-2 seconds (Hankins & Rickett 1975)
KDM = 4.148808e3


def chirp(dm, centres_mhz, chan_mhz, nfft):
    """(nchan, nfft) complex128: the dedispersing chirp H*(f) of each
    channel at the transform's bins, in its order (numpy's
    ``fftfreq``: 0 first); ``chan_mhz`` is the channel width, the
    sample rate of its critically sampled voltages, negative for an
    inverted band."""
    f = np.fft.fftfreq(int(nfft), d=1.0 / abs(float(chan_mhz)))
    if chan_mhz < 0:
        f = -f
    fc = np.asarray(centres_mhz, np.float64).reshape(-1, 1)
    phase = 2.0 * np.pi * 1e6 * KDM * float(dm) * f ** 2 / \
        (fc ** 2 * (fc + f))
    return np.exp(-1j * phase)


def chirp_span_samples(dm, centre_mhz, chan_mhz):
    """Samples between the arrivals of a channel's two edges: how long
    the chirp's impulse response is, which ``overlap`` must cover."""
    lo = centre_mhz - abs(chan_mhz) / 2.0
    hi = centre_mhz + abs(chan_mhz) / 2.0
    return KDM * dm * (lo ** -2 - hi ** -2) * abs(chan_mhz) * 1e6


def window_count(nsamp, nfft, overlap):
    """Windows a block of ``nsamp`` samples holds, or ValueError where
    they do not tile it."""
    step = int(nfft) - int(overlap)
    if not 0 < overlap < nfft:
        raise ValueError('overlap must lie in (0, nfft): %d, %d'
                         % (overlap, nfft))
    if nsamp <= overlap or (nsamp - overlap) % step:
        raise ValueError('%d samples are not %d of overlap and whole '
                         'steps of %d' % (nsamp, overlap, step))
    return (nsamp - overlap) // step


def head(overlap):
    """The outputs dropped from a window's head, and the block's sample
    that its first kept output belongs to: half the overlap (the rest
    is dropped from the window's tail)."""
    return int(overlap) // 2


def transform_path(nfft, inverse=False):
    """The path a window's transform takes (ops.fft.fft_path: three
    levels of DFT matrices past 16384 points, forward or inverse),
    with the precision of their matrix products raised from three bf16
    passes to six ('highest') where float32 accuracy is asked for.
    Three passes leave an error of ~1.7e-5 of a 65536-point spectrum
    (rms, TPU v5e) that is the same in every sample, so a fold over
    thousands of samples does not average it away: the profile read
    5.5e-5 from float64, six passes 3e-7 (PERF.md section 6, guppi-fold).
    ``BF_FFT_DFT_DTYPE=bf16`` (one pass, the control) is kept.  On the
    three-level path the spectrum between the transforms is in the
    levels' order (``'order': 'levels'``; module docstring)."""
    from .fft import fft_path
    path = dict(fft_path((int(nfft),), [0], inverse=inverse),
                nfft=[int(nfft)])
    if path.get('precision') == 'high':
        path['precision'] = 'highest'
    if path['path'] == 'long':
        path['order'] = 'levels'
    return path


def transform_paths(nfft):
    """What a chain records of its transforms (``impl_info['fft']``):
    the forward one's path, and the inverse's under ``'inverse'``."""
    return dict(transform_path(nfft),
                inverse=transform_path(nfft, inverse=True))


def in_transform_order(h, nfft):
    """The chirp ``h`` (nchan, nfft), made in frequency order
    (:func:`chirp`), in the order the spectrum is held in between the
    transforms of :func:`transform_path`: the levels' (k1, k2, k3),
    k = k1 + n1 k2 + n1 n2 k3, where it says ``'order': 'levels'``,
    else as it is."""
    path = transform_path(nfft)
    if path.get('order') != 'levels':
        return h
    n1, n2, n3 = path['factors']
    return h.reshape(-1, n3, n2, n1).transpose(0, 3, 2, 1) \
        .reshape(h.shape)


def windows(x, nfft, overlap):
    """(..., nsamp) -> (..., nwin, nfft): the overlap-save windows, cut
    where they lie (static slices)."""
    import jax.numpy as jnp
    step = nfft - overlap
    nwin = window_count(x.shape[-1], nfft, overlap)
    return jnp.stack([x[..., w * step:w * step + nfft]
                      for w in range(nwin)], axis=-2)


def dedisperse(x, chirp_re, chirp_im, nfft, overlap, chan_axis):
    """Complex voltages ``x`` (..., nsamp), channels on ``chan_axis``
    (not the last), through the whole overlap-save (module docstring),
    each transform by the path :func:`transform_path` gives, the chirp's
    planes in its order (:func:`in_transform_order`) ->
    (..., nwin * (nfft - overlap)) complex64.  The stage
    form: the spectra and the dedispersed voltages are arrays of the
    program, written whole."""
    import jax
    import jax.numpy as jnp
    from .fft import fftn_dispatch, long_fft
    step = nfft - overlap
    w = windows(x, nfft, overlap)                    # (..., nwin, nfft)
    bshape = [1] * w.ndim
    bshape[chan_axis % x.ndim] = chirp_re.shape[0]
    bshape[-1] = nfft
    h = jax.lax.complex(chirp_re, chirp_im).reshape(bshape)

    def transform(z, inverse):
        path = transform_path(nfft, inverse)
        if path['path'] != 'long':
            return fftn_dispatch(z, [-1], inverse=inverse)
        return jax.lax.complex(*long_fft(
            jnp.real(z), jnp.imag(z), tuple(path['factors']),
            precision=path['precision'], inverse=inverse,
            natural=path['order'] != 'levels'))
    y = transform(transform(w.astype(jnp.complex64), False) * h, True)
    first = head(overlap)
    return y[..., first:first + step].reshape(
        x.shape[:-1] + (w.shape[-2] * step,))
