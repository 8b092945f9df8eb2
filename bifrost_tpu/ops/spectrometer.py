"""Fused spectrometer: unpack -> FFT -> Stokes -> freq-reduce in ONE
Pallas kernel.

This is the TPU answer to the reference's flagship GPU pipeline
(reference: testbench/gpuspec_simple.py:44-58 driving src/fft.cu +
blocks/detect.py + src/reduce.cu as three separate kernels with HBM
round-trips between them, mitigated there by cuFFT load callbacks,
src/fft_kernels.cu CallbackData).  On TPU the XLA FFT is an opaque
custom call, so the fused XLA chain still moves ~36 B/sample through
HBM (ci8 read + c64 unpack write + FFT read/write + detect read + f32
write).  This kernel keeps the whole chain in VMEM and touches HBM for
exactly the ci8 input (2 B/sample) and the reduced Stokes output
(~2 B/sample).

The FFT is a four-step Cooley-Tukey factorization N = N1*N2 computed as
two batched matrix multiplies on the MXU (same math as
ops/fft.py:dft_matmul_fft), with the DFT factor matrices resident in
VMEM:

    x[p, q]   (p slow, q fast; n = N2*p + q)
    y[q, r]   = sum_p x[p, q] * exp(-2pi i p r / N1)     (matmul 1)
    y[q, r]  *= exp(-2pi i q r / N)                      (twiddle)
    X[N1*s+r] = sum_q y[q, r] * exp(-2pi i q s / N2)     (matmul 2)

MOSAIC SHAPE DISCIPLINE (measured on the target backend, not guessed):
the TPU vector layout rejects reshapes that split the minor (lane)
dimension into small factors and rejects 3-D ``swapaxes``, but supports
(a) reshapes whose new minor dimension is lane-native (a multiple of
128), (b) ``dot_general`` contracting the MIDDLE dimension of a 3-D
operand (which is how both FFT steps avoid materializing a transpose),
(c) 2-D transposes, and (d) int16 loads with shift arithmetic.  The
kernel is built strictly from that set:

- ci8 samples enter as one int16 per complex sample, rows of
  ``nfft`` words, (time, pol) collapsed, and are split with
  sign-extending shifts in-kernel.  That is the host's own order, and
  since PR 34 a gulp on one device is held as those words on one axis
  (``devrep.ComplexWords``): :func:`fused_spectrometer` given the
  words folds them to rows (one pass of the device, a relayout of
  1024-word tiles to ``T(8,128)`` ones; none where they come as the
  rows already) and feeds ``pallas_call``.  Given the int8 array with
  its trailing (re, im) axis it makes the words by a bitcast, which
  this docstring used to call "an XLA bitcast, free".  On the chip it
  is not.  The runtime keeps ``s8[16384,2,4096,2]`` as
  ``{2,0,3,1:T(8,128)(4,1)}`` ((re, im) far from minor-most), and the
  compiled program opened with ``shift-left_reduce_fusion`` (re |
  im << 8: the device interleaves again), ``bitcast_convert_type``,
  ``copy`` (back to the host's order of axes) and ``reshape``: 3.5 ms
  of a 15.9 ms gulp (PERF.md section 6, PR 34;
  tests/test_tpu_compile.py holds the forms);
- both FFT matmuls are ``dot_general`` with contracting dim 1, so the
  data never transposes between steps;
- the frequency reduce groups the fast output index r (a SUBLANE
  reshape + sum, exact f32 on the VPU);
- the one unavoidable Bailey-transpose (the 4-step FFT's output index
  order k = N1*s + r vs the natural s-major flattening) is either a
  loop of supported 2-D transposes in-kernel or an XLA epilogue
  transpose of the REDUCED output (adds ~4 B/sample).  The in-kernel
  form leaves j = N1/rfactor as the output's minor dim; unless j is a
  multiple of the 128 lanes the TPU layout pads it to 128 in VMEM and
  in HBM (16x at the flagship's j = 8: a 4.3 GB output buffer for a
  268 MB gulp, and Mosaic refuses tile 16 at the full gulp for 0.5 MB
  of scoped VMEM) — so :func:`resolve_transpose` takes the epilogue
  unless j is lane-native; BF_SPEC_TRANSPOSE forces either.

Complex matmuls use the 3-real-matmul (Karatsuba) decomposition:
    RE = Ar Br - Ai Bi
    IM = (Ar + Ai)(Br + Bi) - Ar Br - Ai Bi
which trades one MXU pass for a few VPU adds (25% fewer MXU cycles on
the dominant cost).
"""

from __future__ import annotations

import functools

import numpy as np

__all__ = ['fused_spectrometer', 'long_spectrometer',
           'spectrometer_oracle',
           'spectrometer_accuracy', 'choose_precision',
           'spectrometer_mode', 'resolve_transpose']


def _choose_split(n, rfactor):
    """n = n1 * n2 for the 4-step factorization.

    Preferred: lane-native n2 (a multiple of 128, the TPU vector lane
    count) so the in-kernel reshape (rows, n) -> (rows, n1, n2) keeps
    the minor dimension register-shaped — the only split Mosaic
    compiles.  Fallback (interpret mode / CPU tests): the most-square
    power-of-two split.  BF_SPEC_SPLIT=<n1> overrides when valid.

    Raises ValueError when no split supports ``rfactor`` (the caller
    surfaces this; the XLA chain handles such shapes instead).
    """
    import math
    import os
    import warnings
    if n & (n - 1) or n < 4:
        raise ValueError("fused spectrometer requires power-of-two nfft")
    raw = os.environ.get('BF_SPEC_SPLIT', '0')
    try:
        o = int(raw)
    except ValueError:
        o = 0
    if (o >= 1 and n % o == 0 and (o & (o - 1)) == 0
            and o % rfactor == 0):
        return o, n // o
    if raw.strip() not in ('', '0'):
        # the tuning knob must never silently do nothing: an override
        # incompatible with (n, rfactor) falls through to the default
        # split, loudly
        warnings.warn(
            "BF_SPEC_SPLIT=%r ignored: need a power-of-two divisor of "
            "nfft=%d that rfactor=%d divides; using the default split"
            % (raw, n, rfactor), RuntimeWarning)
    # lane-native: largest n1 <= 128 with n2 % 128 == 0
    n1 = n // 128
    while n1 > 128:
        n1 //= 2
    if n1 >= 1 and n1 % rfactor == 0:
        return n1, n // n1
    # square fallback (compiles under interpret; the on-chip accuracy
    # gate rejects it for real Mosaic lowering)
    h = int(math.log2(n))
    n1 = 1 << (h // 2)
    if n1 % rfactor:
        raise ValueError(
            "rfactor must divide the radix split n1=%d" % n1)
    return n1, n // n1


def resolve_transpose(transpose, nfft, rfactor):
    """'kernel' or 'epilogue' for a requested ``transpose``: an
    explicit mode wins, then a valid BF_SPEC_TRANSPOSE, then the
    shape: in-kernel only when its output minor dim n1/rfactor is
    lane-native (module docstring).  One rule for the kernel and for
    stages.match_spectrometer."""
    import os
    if transpose in ('kernel', 'epilogue'):
        return transpose
    env = os.environ.get('BF_SPEC_TRANSPOSE', '').strip().lower()
    if env in ('kernel', 'epilogue'):
        return env
    n1, _ = _choose_split(nfft, rfactor)
    return 'kernel' if (n1 // rfactor) % 128 == 0 else 'epilogue'


@functools.lru_cache(maxsize=8)
def _dft_consts(n1, n2):
    """(f1, tw, f2) factor matrices as (re, im) float32 pairs.

    f1[p, r] = exp(-2pi i p r / n1)        contraction over p (step 1)
    tw[q, r] = exp(-2pi i q r / (n1 n2))   twiddle
    f2[q, s] = exp(-2pi i q s / n2)        contraction over q (step 2)
    """
    w1 = np.exp(-2j * np.pi *
                np.outer(np.arange(n1), np.arange(n1)) / n1)
    tw = np.exp(-2j * np.pi *
                np.outer(np.arange(n2), np.arange(n1)) / (n1 * n2))
    w2 = np.exp(-2j * np.pi *
                np.outer(np.arange(n2), np.arange(n2)) / n2)
    pack = lambda m: (np.ascontiguousarray(m.real, np.float32),
                      np.ascontiguousarray(m.imag, np.float32))
    return pack(w1), pack(tw), pack(w2)


def _split_bf16(m):
    """m (f32) as a (hi, lo) bf16 pair with hi + lo ~ m to ~2^-18."""
    import ml_dtypes
    hi = m.astype(ml_dtypes.bfloat16)
    lo = (m - hi.astype(np.float32)).astype(ml_dtypes.bfloat16)
    return hi, lo


@functools.lru_cache(maxsize=8)
def _kernel_consts(n1, n2, mode):
    """Host-built factor matrices for the kernel, keyed by precision
    mode.  When 3*n1 <= 128 the three step-1 Karatsuba products ride
    ONE padded MXU pass via a block-diagonal factor
    blockdiag(F1r, F1i, F1r+F1i); 'high' mode carries every factor as
    a bf16 (hi, lo) pair for the manual 3-pass split."""
    (f1r, f1i), (twr, twi), (f2r, f2i) = _dft_consts(n1, n2)
    c = {'twr': twr, 'twi': twi}
    f1s = f1r + f1i
    f2s = f2r + f2i
    use_bd = 3 * n1 <= 128
    if use_bd:
        z = np.zeros((n1, n1), np.float32)
        bd1 = np.block([[f1r, z, z], [z, f1i, z], [z, z, f1s]])
        step1 = {'bd1': bd1}
    else:
        step1 = {'f1r': f1r, 'f1i': f1i, 'f1s': f1s}
    step2 = {'f2r': f2r, 'f2i': f2i, 'f2s': f2s}
    if mode == 'high':
        for d in (step1, step2):
            for k in list(d):
                d[k + 'h'], d[k + 'l'] = _split_bf16(d.pop(k))
    c.update(step1)
    c.update(step2)
    return c, use_bd


def _kernel(n1, n2, rfactor, mode, kernel_transpose, names, use_bd,
            v_ref, *refs):
    import jax
    import jax.numpy as jnp
    o_ref = refs[-1]
    rows = v_ref.shape[0]           # 2 * time_tile (x,y pol interleaved)
    tt = rows // 2
    j = n1 // rfactor
    C = {k: r[...] for k, r in zip(names, refs[:-1])}

    # middle-dim contraction: (rows, K, M) x (K, N) -> (rows, M, N)
    dn = (((1,), (0,)), ((), ()))
    hp = jax.lax.Precision.HIGHEST if mode == 'highest' else None

    def dot(a, b):
        return jax.lax.dot_general(a, b, dn, precision=hp,
                                   preferred_element_type=jnp.float32)

    def split(x):
        """f32 -> (hi, lo) bf16 planes for the manual 3-pass split."""
        h = x.astype(jnp.bfloat16)
        l = (x - h.astype(jnp.float32)).astype(jnp.bfloat16)
        return h, l

    def cmm(ar, ai, nm):
        """Karatsuba complex matmul against factor ``nm``: three real
        products rr = ar@Br, ii = ai@Bi, ss = (ar+ai)@(Br+Bi).
        'high' runs each as hi/lo bf16 passes (dropping the lo*lo
        term, ~2^-18 relative).
        """
        a_s = ar + ai
        if mode != 'high':
            rr = dot(ar, C[nm + 'r'])
            ii = dot(ai, C[nm + 'i'])
            ss = dot(a_s, C[nm + 's'])
        else:
            out = []
            for a, suf in ((ar, 'r'), (ai, 'i'), (a_s, 's')):
                bh, bl = C[nm + suf + 'h'], C[nm + suf + 'l']
                ah, al = split(a)
                out.append(dot(ah, bh) + dot(ah, bl) + dot(al, bh))
            rr, ii, ss = out
        return rr - ii, ss - rr - ii

    # ---- unpack: one int16 per complex sample; low byte = re,
    # high byte = im (little-endian bitcast, verified on-device)
    v32 = v_ref[...].astype(jnp.int32)
    re = ((v32 << 24) >> 24).astype(jnp.float32).reshape(rows, n1, n2)
    im = (v32 >> 8).astype(jnp.float32).reshape(rows, n1, n2)
    # ---- step 1: contract p (dim 1) -> y[row, q, r].  int8 voltages
    # (and their pairwise sums) are EXACT in bf16, so 'high' needs only
    # the factor-side split (2 passes)
    if use_bd:
        acat = jnp.concatenate([re, im, re + im], axis=1)
        if mode == 'high':
            ab = acat.astype(jnp.bfloat16)
            y = dot(ab, C['bd1h']) + dot(ab, C['bd1l'])
        else:
            y = dot(acat, C['bd1'])
        rr = y[..., :n1]
        ii = y[..., n1:2 * n1]
        ss = y[..., 2 * n1:]
        yr, yi = rr - ii, ss - rr - ii
    elif mode == 'high':
        out = []
        for a, suf in ((re, 'r'), (im, 'i'), (re + im, 's')):
            ab = a.astype(jnp.bfloat16)     # exact: int8-valued
            out.append(dot(ab, C['f1' + suf + 'h']) +
                       dot(ab, C['f1' + suf + 'l']))
        rr, ii, ss = out
        yr, yi = rr - ii, ss - rr - ii
    else:
        yr, yi = cmm(re, im, 'f1')
    # ---- twiddle: y[row, q, r] *= tw[q, r]
    twr = C['twr'][None]
    twi = C['twi'][None]
    tr = yr * twr - yi * twi
    ti = yr * twi + yi * twr
    # ---- step 2: contract q (dim 1) -> z[row, r, s]; freq k = n1*s + r
    zr, zi = cmm(tr, ti, 'f2')
    zr = zr.reshape(tt, 2, n1, n2)
    zi = zi.reshape(tt, 2, n1, n2)
    xr_, yr_ = zr[:, 0], zr[:, 1]
    xi_, yi_ = zi[:, 0], zi[:, 1]
    # ---- Stokes (blocks/detect.py): I, Q, U, V
    xx = xr_ * xr_ + xi_ * xi_
    yy = yr_ * yr_ + yi_ * yi_
    xyr = xr_ * yr_ + xi_ * yi_       # x * conj(y)
    xyi = xi_ * yr_ - xr_ * yi_
    planes = (xx + yy, xx - yy, 2.0 * xyr, -2.0 * xyi)
    # ---- reduce freq by rfactor.  k = n1*s + r and rfactor | n1, so
    # groups are r-subgroups at fixed s: a SUBLANE reshape + exact f32
    # VPU sum.  Natural output bin g = (n1//rfactor)*s + j needs
    # (tt, j, s) -> (tt, s, j): statically-unrolled 2-D transposes
    # (Mosaic supports 2-D transpose but not 3-D swapaxes).
    for k, plane in enumerate(planes):
        red = plane.reshape(tt, j, rfactor, n2).sum(axis=2)  # (tt,j,s)
        if kernel_transpose:
            for t in range(tt):
                o_ref[t, k] = red[t].T
        else:
            o_ref[:, k] = red                   # j-major; XLA reorders


def fused_spectrometer(volt, nfft=None, rfactor=4, time_tile=32,
                       precision=None, interpret=False,
                       transpose='auto'):
    """ci8 dual-pol voltages -> reduced Stokes spectra, one kernel.

    volt: the gulp's int16 words, one a complex sample (low byte re),
    in (time, pol, fine_time) order: on one axis with ``nfft`` given,
    as a ci8 gulp on one device is held
    (``devrep.ComplexWords.words``: folded to rows here, one pass of
    the device), or as the rows (2 T, nfft) the kernel reads, which it
    then reads as they are.  Or (T, 2, nfft, 2) int8 — (time, pol,
    fine_time, re/im), the pairs form of dtype 'ci8' gulps — from
    which the words are made first (four passes over the gulp on the
    chip: module docstring).
    Returns (T, 4, nfft // rfactor) float32 ordered [I, Q, U, V],
    identical semantics to the fused stage chain
    FftStage -> DetectStage('stokes') -> ReduceStage('freq', rfactor).

    precision: None (backend default: one bf16 MXU pass per matmul),
    'high' (3-pass bf16, ~f32 accuracy), or 'highest' (6-pass, full
    f32).  The auto mode (choose_precision) picks the cheapest one
    that passes the f32 accuracy gate on the actual backend.

    transpose: 'kernel' (Bailey reorder as in-kernel 2-D transposes),
    'epilogue' (XLA transpose of the reduced output; ~4 B/sample extra
    HBM but no in-kernel loop), or 'auto' (:func:`resolve_transpose`:
    BF_SPEC_TRANSPOSE, else by shape).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    words = volt.dtype == jnp.int16
    if words:
        if nfft is None and volt.ndim == 2:
            nfft = volt.shape[1]
        T = volt.size // (2 * nfft) if nfft else 0
        if not nfft or T * 2 * nfft != volt.size:
            raise ValueError("expected the int16 words of (time, 2 pol, "
                             "nfft) ci8 input, and nfft with them")
    else:
        T, npol, n, two = volt.shape
        if npol != 2 or two != 2:
            raise ValueError("expected (time, 2 pol, nfft, re/im) ci8 "
                             "input, or its int16 words")
        if nfft is None:
            nfft = n
        if n != nfft:
            raise ValueError("nfft mismatch")
    if nfft % rfactor:
        raise ValueError("rfactor must divide nfft")
    n1, n2 = _choose_split(nfft, rfactor)
    transpose = resolve_transpose(transpose, nfft, rfactor)
    tt = min(time_tile, T)
    while T % tt:
        tt -= 1
    mode = precision if precision in ('high', 'highest') else 'default'
    consts, use_bd = _kernel_consts(n1, n2, mode)
    nout = nfft // rfactor
    j = n1 // rfactor

    names = sorted(consts)
    cvals = [jnp.asarray(consts[k]) for k in names]
    cspecs = [pl.BlockSpec(v.shape,
                           (lambda nd: lambda i: (0,) * nd)(v.ndim))
              for v in cvals]
    kern = functools.partial(_kernel, n1, n2, rfactor, mode,
                             transpose == 'kernel', tuple(names),
                             use_bd)
    rows_tile = 2 * tt
    # one int16 per complex sample (little-endian: low byte = re):
    # the words folded to rows (one pass; none where they are rows),
    # else a bitcast of the (re, im) int8 pairs, which costs the
    # device four passes over the gulp (module docstring)
    if not words:
        volt = jax.lax.bitcast_convert_type(volt, jnp.int16)  # (T, 2, n)
    flat = volt.reshape(T * 2, nfft)
    grid = (T // tt,)
    if transpose == 'kernel':
        out_spec = pl.BlockSpec((tt, 4, n2, j), lambda i: (i, 0, 0, 0))
        out_shape = jax.ShapeDtypeStruct((T, 4, n2, j), jnp.float32)
    else:
        out_spec = pl.BlockSpec((tt, 4, j, n2), lambda i: (i, 0, 0, 0))
        out_shape = jax.ShapeDtypeStruct((T, 4, j, n2), jnp.float32)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((rows_tile, nfft), lambda i: (i, 0))]
                 + cspecs,
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(flat, *cvals)
    if transpose == 'kernel':
        # (T, 4, s, j): flattening (s, j) IS natural frequency order
        return out.reshape(T, 4, nout)
    # epilogue: (T, 4, j, s) -> (T, 4, s, j) -> natural order
    return jnp.swapaxes(out, 2, 3).reshape(T, 4, nout)


def long_spectrometer(volt, factors, precision='high'):
    """ci8 dual-pol voltages -> Stokes spectra for a transform past the
    kernel's two levels: (..., 2, nfft, 2) int8, (pol, fine_time,
    re/im) last, or int16, the same samples as words (low byte re),
    with those axes or all on one (a gulp's words as a device ring
    holds them; nfft is ``factors``' product; split here with
    sign-extending shifts, one pass, into planes on one axis, of which
    long_fft slices its chunks where they lie),
    -> (..., 4, nfft) float32 ordered [I, Q, U, V] ((rows / 2, 4, nfft)
    from one axis), the
    semantics of FftStage -> DetectStage('stokes').  The transform is
    ops.fft.long_fft (three levels of DFT matrices, ``factors``); the
    detection runs inside its loop over chunks of the leading axes,
    both polarisations of a spectrum in one chunk, so that the
    complex spectra (8 B a sample) never reach HBM: the program reads
    the voltages (2 B a sample) and writes the Stokes planes (8)."""
    import jax.numpy as jnp
    from .fft import long_fft
    if volt.dtype == jnp.int16:
        nfft = int(np.prod(factors))
        if volt.ndim == 1:
            lead, npol = (volt.size // (2 * nfft),), 2
        else:
            lead, npol = volt.shape[:-2], volt.shape[-2]
        if volt.size != int(np.prod(lead)) * 2 * nfft or \
                (volt.ndim > 1 and volt.shape[-1] != nfft):
            raise ValueError("expected the int16 words of (..., 2 pol, "
                             "%d) ci8 input" % nfft)
        flat = volt.reshape(-1)
        re = ((flat << 8) >> 8).astype(jnp.int8)
        im = (flat >> 8).astype(jnp.int8)
    else:
        if volt.shape[-1] != 2:
            raise ValueError("expected (..., 2 pol, nfft, re/im) ci8 "
                             "input, or its int16 words")
        re, im = volt[..., 0], volt[..., 1]
        lead, npol, nfft = re.shape[:-2], re.shape[-2], re.shape[-1]
    if npol != 2:
        raise ValueError("expected (..., 2 pol, nfft, re/im) ci8 input")

    def stokes(zr, zi):
        zr, zi = zr.reshape(-1, 2, nfft), zi.reshape(-1, 2, nfft)
        xr_, yr_, xi_, yi_ = zr[:, 0], zr[:, 1], zi[:, 0], zi[:, 1]
        xx = xr_ * xr_ + xi_ * xi_
        yy = yr_ * yr_ + yi_ * yi_
        xyr = xr_ * yr_ + xi_ * yi_       # x * conj(y)
        xyi = xi_ * yr_ - xr_ * yi_
        return jnp.stack([xx + yy, xx - yy, 2.0 * xyr, -2.0 * xyi],
                         axis=1)
    out = long_fft(re, im, factors,
                   precision=precision, then=stokes, keep=2)
    return out.reshape(lead + (4, nfft))


def spectrometer_oracle(volt, rfactor=4):
    """float64 numpy reference for the fused kernel (testing)."""
    v = volt[..., 0].astype(np.float64) + 1j * volt[..., 1]
    s = np.fft.fft(v, axis=-1)
    x, y = s[:, 0], s[:, 1]
    xy = x * np.conj(y)
    stokes = np.stack([np.abs(x) ** 2 + np.abs(y) ** 2,
                       np.abs(x) ** 2 - np.abs(y) ** 2,
                       2 * xy.real, -2 * xy.imag], axis=1)
    T, four, nf = stokes.shape
    return stokes.reshape(T, 4, nf // rfactor, rfactor).sum(-1)


def spectrometer_mode():
    """BF_SPEC_IMPL: 'auto' (default — Pallas on TPU when it meets the
    f32 accuracy gate), 'pallas' (force, BF_SPEC_PREC selects
    precision), or 'xla' (never substitute the kernel)."""
    import os
    return os.environ.get('BF_SPEC_IMPL', 'auto').strip().lower()


#: probe results per configuration: the measured relative error
#: (spectrometer_accuracy) / True (kernel_usable), or the refusal line
#: mprobe.refused recorded.  A compile refusal is deterministic for a
#: process (one toolchain, one chip), so it is probed once, reported
#: once and remembered — never re-paid on a plan rebuild.
_acc_cache = {}
_usable_cache = {}


def spectrometer_accuracy(precision, nfft=4096, rfactor=4):
    """Measured on-device relative error of the kernel vs the float64
    oracle at the GIVEN fft length and reduce factor (the accumulation
    length — and so the rounding behavior — scales with the radix
    split, so the gate must probe the shape actually substituted).
    Cached per (precision, nfft, rfactor, split).  This is the
    automatic selection's probe: a configuration the backend refuses
    is reported through ``mprobe.refused`` (one warning with the
    compiler's message, an entry in the published impl record) and
    returns a large finite sentinel so artifacts stay strict-JSON."""
    from . import mprobe
    try:
        # the effective radix split is part of the key: BF_SPEC_SPLIT
        # changes the contraction/accumulation lengths (and so
        # rounding) and the gate must probe the shape substituted
        key = (precision, nfft, rfactor) + _choose_split(nfft, rfactor)
    except ValueError:
        return 1e9       # no split for this shape: not a candidate
    if key not in _acc_cache:
        try:
            import jax.numpy as jnp
            rng = np.random.RandomState(11)
            volt = rng.randint(-64, 64,
                               size=(8, 2, nfft, 2)).astype(np.int8)
            got = np.asarray(fused_spectrometer(
                jnp.asarray(volt), rfactor=rfactor, time_tile=8,
                precision=precision))
            want = spectrometer_oracle(volt, rfactor=rfactor)
            _acc_cache[key] = float(np.max(np.abs(got - want)) /
                                    (np.max(np.abs(want)) + 1e-30))
        except Exception as e:
            _acc_cache[key] = mprobe.refused(
                'spectrometer', 'pallas[%s,n=%d,r=%d,tile=8]'
                % (precision or 'default', nfft, rfactor), e)
    rel = _acc_cache[key]
    return rel if isinstance(rel, float) else 1e9


def kernel_usable(nfft, rfactor, tile, precision, transpose):
    """True when the kernel COMPILES AND RUNS on the current backend at
    the exact (tile, precision, transpose) that would be substituted.
    The accuracy gate probes a small tile; VMEM exhaustion only shows
    up at the substitution tile (scoped-vmem limit ~16 MB), so the
    matcher must probe the real configuration before committing — a
    mid-pipeline compile failure would otherwise kill the block thread.
    Probed once per configuration.  A refusal under the automatic
    selection is reported through ``mprobe.refused`` and returns
    False; under ``BF_SPEC_IMPL=pallas`` (force) it RAISES — a forced
    implementation that cannot be built must not quietly become the
    XLA chain."""
    from . import mprobe
    try:
        key = ((nfft, rfactor, tile, precision, transpose)
               + _choose_split(nfft, rfactor))
    except ValueError:
        return False     # no split for this shape: not a candidate
    if key not in _usable_cache:
        try:
            import jax.numpy as jnp
            volt = np.zeros((tile, 2, nfft, 2), np.int8)
            out = fused_spectrometer(jnp.asarray(volt), rfactor=rfactor,
                                     time_tile=tile, precision=precision,
                                     transpose=transpose)
            np.asarray(out)
            _usable_cache[key] = True
        except Exception as e:
            if spectrometer_mode() == 'pallas':
                raise
            _usable_cache[key] = mprobe.refused(
                'spectrometer', 'pallas[%s,%s,n=%d,r=%d,tile=%d]'
                % (precision or 'default', transpose, nfft, rfactor,
                   tile), e)
    return _usable_cache[key] is True


def choose_precision(nfft=4096, rfactor=4):
    """Precision for the fused kernel under the current BF_SPEC_IMPL
    mode, or the string 'off' when the XLA chain should run instead.

    'auto' only substitutes the kernel when it matches the float64
    oracle to f32 accuracy (same 1e-5 bar as chip_smoke.py's phase A
    on the chip) at the requested fft length, so enabling it can
    never change science output beyond FFT-algorithm noise.
    """
    import os
    import jax
    mode = spectrometer_mode()
    if mode == 'xla':
        return 'off'
    try:
        if jax.default_backend() != 'tpu':
            return 'off'
    except Exception:
        return 'off'
    if mode == 'pallas':
        prec = os.environ.get('BF_SPEC_PREC', '').strip().lower()
        return prec if prec in ('high', 'highest') else None
    # auto: correctness-gated substitution, cheapest passing precision
    for prec in (None, 'high', 'highest'):
        if spectrometer_accuracy(prec, nfft, rfactor) < 1e-5:
            return prec
    return 'off'
