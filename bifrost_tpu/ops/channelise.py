"""A short channeliser on the gulp's word rows: the transform of every
channel and polarisation of ci8 dual-pol voltages over their last axis,
of a length that divides 128, detected to Stokes I and summed over
``nspec`` spectra, as one block-diagonal real matrix product on the MXU.

A gulp on one device is its int16 words on one axis
(``devrep.ComplexWords``), which the runtime lays out in ``T(1024)``
tiles: a bitcast of rows of 128 words.  Where the transform is the
gulp's last axis and its length divides 128, such a row is whole
transforms side by side (rawspec's high-time-resolution product, 8
points: 8 channels x 2 pol x 8 samples a row), so the transforms of a
row are one product of its ``[re | im]`` (256 values) with a
block-diagonal (256, 256) real DFT matrix, the fftshift folded into the
matrix's columns.  The squares are summed over the spectra on a major
axis, and the (re, im) halves and the polarisations are added on the
reduced array.  Nothing is laid out with the transform's points minor:
the ``xla-fused`` composition of the same stages folds the gulp to
``[..., 8]`` with the 8 minor in 128-lane tiles, 16 times the words'
bytes, and turns it time-minor for the FFT, two passes that took 68 of
its 136 ms a gulp on the v5e (PERF.md section 5, bl-frb-search).
"""

from __future__ import annotations

import functools
import math

import numpy as np

__all__ = ['row_width', 'dft_rows', 'row_channeliser']

#: the lanes of a TPU vector register, and the most words a row holds
LANES = 128
#: the products of one chunk of rows, float32, hold at most this many
#: bytes, so that they stay in the chip's fast memory from the product
#: to the sum (as ops.fft.long_fft's chunks do)
_CHUNK_BYTES = 1 << 24


def row_width(frame_words):
    """Words a row: the largest divisor of 128 that divides a frame of
    ``frame_words`` words (128 where a frame is whole rows of 128, the
    frame itself where it holds fewer), so that a frame is whole
    rows."""
    return math.gcd(int(frame_words), LANES)


@functools.lru_cache(maxsize=8)
def dft_rows(width, nfft, shift):
    """(2 width, 2 width) float64: the block-diagonal real matrix that
    takes a row ``[re_0 .. re_w-1 | im_0 .. im_w-1]`` of ``width /
    nfft`` transforms side by side to ``[Re X | Im X]``, X_k = sum_n
    x_n exp(-2 pi i n k / nfft) in each block; its bins in ascending
    frequency from -nfft/2 where ``shift`` (fftshift), else k = 0 ..
    nfft - 1."""
    n = np.arange(nfft)
    k = (n - nfft // 2) % nfft if shift else n
    ang = 2 * np.pi * np.outer(n, k) / nfft
    eye = np.eye(width // nfft)
    c, s = np.kron(eye, np.cos(ang)), np.kron(eye, np.sin(ang))
    # X = sum (re + i im)(cos - i sin)
    return np.block([[c, -s], [s, c]])


def _split3(m):
    """A float32 matrix as three bfloat16 parts, stacked on the first
    axis, whose sum is it to float32's 24 bits."""
    import ml_dtypes
    parts, rest = [], np.asarray(m, np.float32)
    for _ in range(3):
        part = rest.astype(ml_dtypes.bfloat16)
        parts.append(part)
        rest = rest - part.astype(np.float32)
    return np.concatenate(parts, axis=0)


def _chunk_rows(nrow, group, width):
    """Rows a chunk: the largest multiple of ``group`` (the rows of one
    summed spectrum) that divides ``nrow`` and whose products fit
    ``_CHUNK_BYTES``; ``group`` at least."""
    fit = max(_CHUNK_BYTES // (8 * width) // group, 1) * group
    return next(r for r in range(min(fit, nrow), 0, -group)
                if nrow % r == 0)


def row_channeliser(volt, shape, nspec, pol_axis, shift=True,
                    precision='split'):
    """ci8 dual-pol voltages -> Stokes I spectra, ``nspec`` summed.

    ``volt``: the gulp's int16 words on one axis (low byte re, as a
    gulp on one device is held), or its int8 pairs (..., 2);
    ``shape``: the logical (T, ..., nfft), the frame axis first, the
    transform's axis last and a polarisation axis of two at
    ``pol_axis``.  Returns float32 (T / nspec, ...) with that axis of
    length one: the semantics of FftStage(last axis, c2c forward,
    ``shift``) -> DetectStage('stokes_i') -> ReduceStage(frame axis,
    nspec, 'sum').

    The rows go through in chunks (:func:`_chunk_rows`), sliced where
    they lie, each through the product, the squares and the sum before
    the next is begun.  ``precision``: 'split' (the matrix as three
    bfloat16 parts, stacked along the contraction, against the
    voltages, which bfloat16 holds exactly: float32's accuracy in three
    passes of the MXU, where ``HIGHEST`` takes six; a two-part split,
    the MXU's ``high``, leaves an error in the matrix that is the same
    in every sample, which the sums over spectra and over the FDMT's
    channels do not average away, as the coherent fold's did: PERF.md
    section 6) or 'default' (one bfloat16 pass: the lower-precision
    control).  On the v5e a bl-frb-search gulp (2^28 samples) took
    7.2 ms so, 10.8 at ``HIGHEST``, 15.3 at ``HIGHEST`` in one piece,
    against 94.0 for the ``xla-fused`` composition (PERF.md section 6,
    the FRB search's channeliser)."""
    import jax.numpy as jnp
    from jax import lax
    shape = tuple(int(n) for n in shape)
    ntime, nfft = shape[0], shape[-1]
    frame = int(np.prod(shape[1:]))
    width = row_width(frame)
    per_frame = frame // width
    if ntime % nspec or width % nfft or shape[pol_axis] != 2:
        raise ValueError('row_channeliser: no rows of whole transforms '
                         'in %r summed by %d' % (shape, nspec))
    if precision not in ('split', 'default'):
        raise ValueError('row_channeliser: precision %r' % (precision,))
    if volt.dtype != jnp.int16:
        # the pairs' words, one a sample
        volt = lax.bitcast_convert_type(volt, jnp.int16)
    flat = volt.reshape(-1)
    group = nspec * per_frame
    nrow = ntime * per_frame
    rows = _chunk_rows(nrow, group, width)
    m = dft_rows(width, nfft, bool(shift))
    mat = jnp.asarray(_split3(m) if precision == 'split' else m,
                      jnp.bfloat16)

    def one(k):
        """The chunk ``k``'s sums: (rows / group, per_frame, width)."""
        w = lax.dynamic_slice_in_dim(flat, k * rows * width, rows * width)
        w = w.reshape(rows, width)
        # sign-extending shifts: low byte re, high byte im
        x = jnp.concatenate([(w << 8) >> 8, w >> 8], axis=1) \
            .astype(jnp.bfloat16)
        if precision == 'split':
            x = jnp.concatenate([x, x, x], axis=1)
        y = jnp.dot(x, mat, preferred_element_type=jnp.float32)
        p = (y * y).reshape(rows // group, nspec, per_frame, 2 * width) \
            .sum(axis=1)
        return p[..., :width] + p[..., width:]
    if rows == nrow:
        power = one(0)
    else:
        power = lax.map(one, jnp.arange(nrow // rows))
    out = power.reshape((ntime // nspec,) + shape[1:])
    return out.sum(axis=pol_axis, keepdims=True)
