"""N-dimensional batched FFT (reference: src/fft.cu:57-230, 384-413;
python/bifrost/fft.py).

The reference builds cuFFT plans embedding strides, with load callbacks
fusing 4/8-bit unpacking and fftshift into the transform
(reference: src/fft_kernels.cu CallbackData).  Here the plan is a cached
``jax.jit`` function: jnp.fft plus any pre-unpack/shift/scale is traced
once and XLA fuses the lot — callbacks for free.
"""

from __future__ import annotations

import functools

import numpy as np

from ..dtype import DataType
from .common import as_jax, logical_dtype

__all__ = ['Fft', 'fft']


class Fft(object):
    """Plan-style FFT op, mirroring bfFftInit/bfFftExecute
    (reference: python/bifrost/fft.py:41-70)."""

    def __init__(self):
        self._fn = None
        self._key = None

    def init(self, iarray, oarray, axes=None, apply_fftshift=False):
        ishape = tuple(iarray.shape)
        idt = logical_dtype(iarray)
        odt = logical_dtype(oarray)
        if axes is None:
            axes = list(range(len(ishape)))
        elif np.isscalar(axes):
            axes = [axes]
        axes = [a % len(ishape) for a in axes]
        real_input = idt.is_real
        real_output = odt.is_real
        self._key = (ishape, str(idt), str(odt), tuple(axes), apply_fftshift)
        import jax
        import jax.numpy as jnp

        def plan(x):
            if real_input:                      # r2c
                x = x.astype(jnp.float32 if idt.nbits <= 32
                             else jnp.float64)
                y = jnp.fft.rfftn(x, axes=axes)
            elif real_output:                   # c2r
                sizes = [oarray.shape[a] for a in axes]
                y = jnp.fft.irfftn(x, s=sizes, axes=axes)
                # match cuFFT's unnormalized c2r convention
                y = y * np.prod([oarray.shape[a] for a in axes])
            else:                               # c2c
                x = x.astype(jnp.complex64 if idt.nbits <= 32
                             else jnp.complex128)
                y = fftn_dispatch(x, axes)
            if apply_fftshift:
                y = jnp.fft.fftshift(y, axes=axes)
            target = jnp.dtype(odt.as_jax_dtype())
            if y.dtype != target:
                y = y.astype(target)
            return y

        def plan_inverse(x):
            if apply_fftshift:
                x = jnp.fft.ifftshift(x, axes=axes)
            if real_output:
                sizes = [oarray.shape[a] for a in axes]
                y = jnp.fft.irfftn(x, s=sizes, axes=axes)
                y = y * np.prod(sizes)
            else:
                # cuFFT inverse is unnormalized (reference: fft.cu uses
                # CUFFT_INVERSE without scaling)
                y = fftn_dispatch(x, axes, inverse=True)
            return y.astype(odt.as_jax_dtype())

        self._fn = jax.jit(plan)
        self._fn_inverse = jax.jit(plan_inverse)
        self.workspace_size = 0   # XLA owns scratch
        return self

    def execute(self, iarray, oarray, inverse=False):
        x = as_jax(iarray)
        y = self._fn_inverse(x) if inverse else self._fn(x)
        return _writeback(y, oarray)

    def execute_workspace(self, iarray, oarray, workspace_ptr=None,
                          workspace_size=None, inverse=False):
        return self.execute(iarray, oarray, inverse=inverse)


def _writeback(y, oarray):
    from ..ndarray import ndarray as bf_ndarray
    from ..xfer import to_host
    if isinstance(oarray, bf_ndarray):
        if oarray.space == 'tpu':
            oarray._buf = y
        else:
            from .map import _from_logical
            dt = oarray.dtype
            _from_logical(to_host(y),
                          DataType('%s%d' % (dt.kind, dt.nbits)),
                          out_buf=oarray.as_numpy())
        return oarray
    return y


def fft(iarray, oarray=None, axes=None, inverse=False, apply_fftshift=False):
    """One-shot functional FFT; returns the output array."""
    if oarray is None:
        oarray = iarray   # dtype/shape template only
    plan = Fft().init(iarray, oarray, axes=axes,
                      apply_fftshift=apply_fftshift)
    return plan.execute(iarray, oarray, inverse=inverse)

# ---------------------------------------------------------------------------
# DFT-as-matmul alternative (MXU path)
# ---------------------------------------------------------------------------

def _split_factor(n):
    """Factor n = n1 * n2 with n1 ~ sqrt(n) (radix split)."""
    import math
    n1 = int(math.isqrt(n))
    while n1 > 1 and n % n1:
        n1 -= 1
    return n1, n // n1


_dft_cache = {}


def _dft_matrices(n1, n2, inverse, dtype_name):
    """Twiddle/DFT factor matrices for the four-step transform, cached
    host-side per (n1, n2, direction, dtype)."""
    import numpy as np_
    key = (n1, n2, inverse, dtype_name)
    hit = _dft_cache.get(key)
    if hit is not None:
        return hit
    sgn = +1 if inverse else -1
    f1 = np_.exp(sgn * 2j * np_.pi *
                 np_.outer(np_.arange(n1), np_.arange(n1)) / n1)
    f2 = np_.exp(sgn * 2j * np_.pi *
                 np_.outer(np_.arange(n2), np_.arange(n2)) / n2)
    tw = np_.exp(sgn * 2j * np_.pi *
                 np_.outer(np_.arange(n1), np_.arange(n2)) / (n1 * n2))
    cdt = np_.complex128 if dtype_name == 'c128' else np_.complex64
    out = tuple(m.astype(cdt) for m in (f1, f2, tw))
    _dft_cache[key] = out
    return out


def _const_complex(m, acc):
    """Embed a host complex matrix as a jit constant from re/im float
    planes recombined on device (the xfer.py convention; the local v5e
    would also take the complex matrix directly — chip_smoke.py fact
    ii, PR 21)."""
    import jax
    import jax.numpy as jnp
    ft = jnp.float64 if acc == jnp.complex128 else jnp.float32
    return jax.lax.complex(
        jnp.asarray(np.ascontiguousarray(m.real), dtype=ft),
        jnp.asarray(np.ascontiguousarray(m.imag), dtype=ft))


def dft_matmul_fft(x, axis=-1, inverse=False, compute_dtype=None):
    """c2c FFT along one axis as two MXU matmuls (Cooley-Tukey
    four-step: reshape N -> (N1, N2), DFT_N1, twiddle, DFT_N2).

    The FLOP count is ~N*(N1+N2) complex MACs vs the FFT's ~5N log2 N —
    more arithmetic, but it rides the MXU systolic array instead of the
    VPU.  On hardware where matmul throughput dwarfs vector throughput
    this wins; select with BF_FFT_IMPL=dftmm (per-axis unnormalized
    forward/inverse, cuFFT conventions, like the rest of ops.fft).
    ``compute_dtype``: 'bf16' runs the matmuls in bfloat16 (faster,
    ~2-3 decimal digits) — BF_FFT_DFT_DTYPE=bf16.
    """
    import jax.numpy as jnp
    n = x.shape[axis]
    n1, n2 = _split_factor(n)
    # preserve double precision end to end for complex128 inputs
    dtn = 'c128' if x.dtype == jnp.complex128 else 'c64'
    acc = jnp.complex128 if dtn == 'c128' else jnp.complex64
    if n1 == 1:            # prime length: plain DFT matmul
        fn = _dft_matrices(n, 1, inverse, dtn)[0]
        xm = jnp.moveaxis(x, axis, -1)
        y = jnp.einsum('...k,kj->...j', xm, _const_complex(fn, acc),
                       preferred_element_type=acc)
        return jnp.moveaxis(y, -1, axis)
    f1, f2, tw = _dft_matrices(n1, n2, inverse, dtn)
    xm = jnp.moveaxis(x, axis, -1)
    shp = xm.shape[:-1]
    xm = xm.reshape(shp + (n1, n2))

    def mm(a, b):
        if compute_dtype == 'bf16':
            ar, ai = jnp.real(a).astype(jnp.bfloat16), \
                jnp.imag(a).astype(jnp.bfloat16)
            br, bi = jnp.real(b).astype(jnp.bfloat16), \
                jnp.imag(b).astype(jnp.bfloat16)
            rr = jnp.matmul(ar, br, preferred_element_type=jnp.float32)
            ii = jnp.matmul(ai, bi, preferred_element_type=jnp.float32)
            ri = jnp.matmul(ar, bi, preferred_element_type=jnp.float32)
            ir = jnp.matmul(ai, br, preferred_element_type=jnp.float32)
            return (rr - ii) + 1j * (ri + ir)
        return jnp.matmul(a, b, preferred_element_type=acc)

    # DFT over the n1 axis: contract with F1 on the left
    y = mm(jnp.swapaxes(xm, -1, -2),
           _const_complex(f1.T, acc))                      # (..., n2, n1)
    y = jnp.swapaxes(y, -1, -2) * _const_complex(tw, acc)  # twiddle
    y = mm(y, _const_complex(f2, acc))                     # (..., n1, n2)
    # output index k = k1*n2 + k2? four-step ordering: k = k2*n1 + k1
    y = jnp.swapaxes(y, -1, -2).reshape(shp + (n,))
    return jnp.moveaxis(y, -1, axis)


def fft_impl_choice():
    import os
    return os.environ.get('BF_FFT_IMPL', '').strip().lower()


def fft_path(shape, axes, inverse=False, dtype='complex64'):
    """What :func:`fftn_dispatch` runs for a transform of ``axes`` of
    an array of ``shape``, as a record a block can publish: from the
    shape and the documented variables alone.  ``{'path': 'long',
    'factors': ..., 'precision': ...}`` for a forward complex64
    transform of one last axis whose length takes three levels
    (:func:`long_factors`); ``'dftmm'`` where BF_FFT_IMPL forces the
    two-level matrix form; else ``'xla'`` (jnp.fft)."""
    import os
    if fft_impl_choice() == 'dftmm':
        return {'path': 'dftmm'}
    ndim = len(shape)
    axes = [a % ndim for a in axes]
    factors = long_factors(shape[axes[0]]) if axes == [ndim - 1] else None
    if factors is None or inverse or str(dtype) != 'complex64':
        return {'path': 'xla'}
    # BF_FFT_DFT_DTYPE=bf16 is the matrix forms' lower-precision
    # switch: one bf16 pass a product where float32 accuracy takes three
    bf16 = os.environ.get('BF_FFT_DFT_DTYPE', '').strip().lower() \
        in ('bf16', 'bfloat16')
    return {'path': 'long', 'factors': list(factors),
            'precision': 'default' if bf16 else 'high'}


def fftn_dispatch(x, axes, inverse=False):
    """The transform of ``axes`` by the one implementation its shape
    selects (:func:`fft_path`): jnp.fft.fftn/ifftn (unnormalized
    inverse); three levels of DFT matrices (:func:`long_fft`) for a
    length past LONG_NFFT; the DFT-matmul path when BF_FFT_IMPL=dftmm
    (per axis; MXU-bound)."""
    import os
    import jax
    import jax.numpy as jnp
    plan = fft_path(x.shape, axes, inverse, x.dtype)
    if plan['path'] == 'dftmm':
        cdt = os.environ.get('BF_FFT_DFT_DTYPE', '').strip().lower() \
            or None
        y = x
        for ax in axes:
            y = dft_matmul_fft(y, ax, inverse=inverse,
                               compute_dtype=cdt)
        return y
    if plan['path'] == 'long':
        # real() and imag() of a complex64 made of planes are those
        # planes again once XLA has simplified the program, and the
        # TPU computes complex64 on separate planes anyway
        return jax.lax.complex(*long_fft(
            jnp.real(x), jnp.imag(x), tuple(plan['factors']),
            precision=plan['precision']))
    if inverse:
        y = jnp.fft.ifftn(x, axes=axes)
        import numpy as np_
        return y * np_.prod([x.shape[a] for a in axes])
    return jnp.fft.fftn(x, axes=axes)


# ---------------------------------------------------------------------------
# Long transforms: three levels of DFT matrices
# ---------------------------------------------------------------------------

#: the largest DFT matrix a level multiplies by: the MXU's width, and
#: the lane count a level's minor dimension keeps
MAX_FACTOR = 128
#: two levels of such matrices reach this length (the fused
#: spectrometer's kernel, ops/spectrometer.py, and jnp.fft below it);
#: a power of two past it, up to MAX_FACTOR ** 3, takes three
LONG_NFFT = MAX_FACTOR ** 2


def long_factors(n):
    """(n1, n2, n3), n = n1 * n2 * n3, for a power of two ``n`` past
    two levels, or None where three levels of at most MAX_FACTOR do
    not reach (or ``n`` is no such length): the minor factor n3 is
    the lane count, the other two share what is left, the larger
    first (2^20: 128, 64, 128)."""
    if n <= LONG_NFFT or n > MAX_FACTOR ** 3 or n & (n - 1):
        return None
    rest = (n // MAX_FACTOR).bit_length() - 1
    n1 = 1 << ((rest + 1) // 2)
    return n1, (n // MAX_FACTOR) // n1, MAX_FACTOR


@functools.lru_cache(maxsize=4)
def _long_consts(factors):
    """Host-built float32 (re, im) planes of the three DFT matrices
    and the two twiddle tables of :func:`long_fft`, from float64."""
    n1, n2, n3 = factors
    m = n2 * n3

    def planes(z):
        return (np.ascontiguousarray(z.real, np.float32),
                np.ascontiguousarray(z.imag, np.float32))

    def dft(k):
        i = np.arange(k)
        return planes(np.exp(-2j * np.pi * ((i[:, None] * i[None, :]) % k)
                             / k))
    # level 1's twiddle W_N^(k1 * (n3*n2' + n3')), level 2's W_M^(k2 n3')
    t1 = np.exp(-2j * np.pi * (np.arange(n1)[:, None] * np.arange(m)[None, :])
                / (n1 * m)).reshape(n1, n2, n3)
    t2 = np.exp(-2j * np.pi * (np.arange(n2)[:, None]
                               * np.arange(n3)[None, :]) / m)
    return dft(n1), planes(t1), dft(n2), planes(t2), dft(n3)


#: the spectra of one chunk of a long transform, float32 (re, im)
#: planes, hold at most this many bytes: a level's intermediates then
#: stay in the chip's fast memory between the products.  On the v5e a
#: 268 MB gulp of 128 transforms of 2^20 points, detected, takes 48.0 ms
#: whole, 41.7 in chunks of 128 MiB, 22.4 of 64 and of 32, 20.1 of 16
#: (one coarse channel's two polarisations): PERF.md section 6, PR 33
_CHUNK_BYTES = 1 << 24


def _chunk_rows(nrow, n, keep):
    """Transforms a chunk: the largest divisor of ``nrow`` that is a
    multiple of ``keep`` (rows that must stay together: a pair of
    polarisations) and whose spectra fit ``_CHUNK_BYTES``; ``keep`` at
    least."""
    fit = max(_CHUNK_BYTES // (8 * n), keep)
    return next(r for r in range(min(fit, nrow), 0, -1)
                if nrow % r == 0 and r % keep == 0)


def long_fft(xr, xi, factors, precision='high', natural=True,
             then=None, keep=1):
    """Forward c2c transform over the last axis of the real planes
    ``xr``, ``xi`` (..., n), or of their rows of n where they come on
    one axis, in three levels of DFT matrix products,
    n = n1 * n2 * n3 = ``factors``: with n = (n2 n3) a + n3 b + c and
    k = k1 + n1 k2 + n1 n2 k3,

        A[k1, b, c]  = sum_a F1[k1, a] x[a, b, c]  * W_n^(k1 (n3 b + c))
        B[k1, k2, c] = sum_b F2[k2, b] A[k1, b, c] * W_(n2 n3)^(k2 c)
        X[k1, k2, k3] = sum_c F3[k3, c] B[k1, k2, c]

    The planes (any real dtype) are cast to float32; every product is
    real planes against real planes, four a level, summed in float32
    (stacked as one product against [[Fr, -Fi], [Fi, Fr]] a level read
    28.8 ms where this reads 21.2: PERF.md section 6, PR 33).
    ``precision``: 'high' (three bf16 passes of the MXU a
    product: float32 accuracy) or 'default' (one: the lower-precision
    control; six, ``HIGHEST``, read 3e-8 for 1.6e-6 at 27.8 ms for
    22.4 and have no caller).  The leading axes go through a chunk
    at a time (:func:`_chunk_rows`, ``keep`` rows never apart), and
    ``then(yr, yi)``, given the float32 planes (rows, n) of a chunk's
    spectra, is applied to each chunk before the next is begun (a
    spectrometer's detection: the spectra never reach HBM whole); its
    result's leading axis must be the chunk's.  Returns the planes
    (..., n), or ``then``'s results joined along their first axis: in
    frequency order where ``natural``, else as (n1, n2, n3) flattened,
    k1 major, which saves the reordering for a caller that sums
    spectra before it needs their order."""
    import jax
    import jax.numpy as jnp
    n1, n2, n3 = factors
    n = n1 * n2 * n3
    # planes on one axis longer than a transform are rows of n end to
    # end (made from a gulp's words, devrep.ComplexWords)
    flat = xr.ndim == 1 and xr.shape[0] > n and xr.shape[0] % n == 0
    lead = (xr.shape[0] // n,) if flat else xr.shape[:-1]
    if not flat and xr.shape[-1] != n:
        raise ValueError('long_fft: %d points do not factor as %r'
                         % (xr.shape[-1], (factors,)))
    prec = {'high': jax.lax.Precision.HIGH,
            'default': jax.lax.Precision.DEFAULT}[precision]
    f1, t1, f2, t2, f3 = _long_consts(tuple(factors))

    def level(spec, f, ar, ai):
        fr, fi = (jnp.asarray(p) for p in f)
        mm = functools.partial(jnp.einsum, spec, precision=prec,
                               preferred_element_type=jnp.float32)
        return mm(fr, ar) - mm(fi, ai), mm(fr, ai) + mm(fi, ar)

    def twiddle(ar, ai, t):
        tr, ti = (jnp.asarray(p) for p in t)
        return ar * tr - ai * ti, ar * ti + ai * tr

    def one(xr, xi):
        """(rows, n) planes -> the chunk's spectra, or then() of them."""
        ar = xr.astype(jnp.float32).reshape(-1, n1, n2, n3)
        ai = xi.astype(jnp.float32).reshape(-1, n1, n2, n3)
        ar, ai = twiddle(*level('ka,rabc->rkbc', f1, ar, ai), t1)
        ar, ai = twiddle(*level('kb,rabc->rakc', f2, ar, ai), t2)
        ar, ai = level('kc,rabc->rkba' if natural else 'kc,rabc->rabk',
                       f3, ar, ai)
        ar, ai = ar.reshape(-1, n), ai.reshape(-1, n)
        return (ar, ai) if then is None else then(ar, ai)

    nrow = int(np.prod(lead, dtype=np.int64))
    rows = _chunk_rows(nrow, n, keep)
    if rows == nrow:
        out = one(xr.reshape(nrow, n), xi.reshape(nrow, n))
    elif flat:
        # a chunk is a stretch of the one axis, sliced where it lies:
        # folding a whole plane to (chunks, rows, n) first is a
        # relayout of the gulp, and one that libtpu takes 19 s to
        # compile from 128 rows of 2^20 (PERF.md section 6, PR 34)
        def chunk(k):
            return one(*(jax.lax.dynamic_slice_in_dim(
                p, k * rows * n, rows * n).reshape(rows, n)
                for p in (xr, xi)))
        out = jax.lax.map(chunk, jnp.arange(nrow // rows))
    else:
        out = jax.lax.map(lambda planes: one(*planes),
                          (xr.reshape(nrow // rows, rows, n),
                           xi.reshape(nrow // rows, rows, n)))
    if rows != nrow:
        out = jax.tree_util.tree_map(
            lambda y: y.reshape((-1,) + y.shape[2:]), out)
    if then is not None:
        return out
    return out[0].reshape(lead + (n,)), out[1].reshape(lead + (n,))
