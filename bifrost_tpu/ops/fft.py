"""N-dimensional batched FFT (reference: src/fft.cu:57-230, 384-413;
python/bifrost/fft.py).

The reference builds cuFFT plans embedding strides, with load callbacks
fusing 4/8-bit unpacking and fftshift into the transform
(reference: src/fft_kernels.cu CallbackData).  Here the plan is a cached
``jax.jit`` function: jnp.fft plus any pre-unpack/shift/scale is traced
once and XLA fuses the lot — callbacks for free.
"""

from __future__ import annotations

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


def fftn_dispatch(x, axes, inverse=False):
    """jnp.fft.fftn/ifftn (unnormalized inverse), or the DFT-matmul
    path when BF_FFT_IMPL=dftmm (per axis; MXU-bound)."""
    import os
    import jax.numpy as jnp
    if fft_impl_choice() == 'dftmm':
        cdt = os.environ.get('BF_FFT_DFT_DTYPE', '').strip().lower() \
            or None
        y = x
        for ax in axes:
            y = dft_matmul_fft(y, ax, inverse=inverse,
                               compute_dtype=cdt)
        return y
    if inverse:
        y = jnp.fft.ifftn(x, axes=axes)
        import numpy as np_
        return y * np_.prod([x.shape[a] for a in axes])
    return jnp.fft.fftn(x, axes=axes)
