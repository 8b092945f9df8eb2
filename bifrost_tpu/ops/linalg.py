"""Batched linear algebra on the MXU (reference: src/linalg.cu:877-904,
src/linalg_kernels.cu; python/bifrost/linalg.py).

Two operations, mirroring bfLinAlgMatMul:

- ``c = alpha * a @ b + beta * c``      (beamforming GEMM)
- ``c = alpha * a @ a^H + beta * c``    (correlation, when b is None)

The reference's identity here is hand-beating library kernels: a custom
cherk below n=896 and a dp4a int8 path (reference: src/linalg.cu:210-226,
src/linalg_kernels.cu:55).  The TPU equivalents implemented here:

- **Planar complex GEMM.**  XLA lowers an interleaved complex64 dot to
  real dots over de-interleaved copies; computing directly on separate
  re/im planes with the Karatsuba 3-multiply skips that materialization
  and one full real matmul: m1 = ar@br, m2 = ai@bi, m3 = (ar+ai)@(br+bi)
  -> (m1-m2) + i(m3-m1-m2).
- **bf16 hi-lo split.**  f32 operands split as x = hi + lo (two bf16
  planes); x@y ~= hi@yh + (hi@yl + lo@yh), three bf16 MXU passes with
  f32 accumulation — ~f32 result accuracy at the bf16 MXU rate,
  dropping only the lo@lo term (~2^-16 relative).  This is the MXU
  analogue of the reference's "compute in a cheaper type without losing
  the answer" Cherk3mEx trick.
- **Widened int8 gram.**  The ci8 a@a^H needs rr+ii and K-K^T
  (K = im@re^T).  Either three int8 matmuls (the Cherk3mEx 3-multiply),
  or ONE (2n, k)@(k, 2n) int8 matmul of the stacked [re; im] planes
  whose 4 blocks contain every term — 4/3 the MACs but a single big
  MXU-shaped kernel.  Which wins depends on XLA's lowering, so it is
  measured (ops.mprobe), never asserted.
- **cf16 plane operands.**  A cf16 ring array feeds the planar GEMMs
  as raw f16 planes — never promoted to complex64 — so the HBM read
  is half-width, the lever at bandwidth-bound beamform shapes.  The
  hi-lo split is EXACT for f16 planes (a f16 value splits exactly
  into two bf16 terms), so the traffic cut costs no accuracy.  A
  single-pass bf16 candidate (full MXU rate, ~2^-8 rounding) exists
  but fails the default accuracy gate by construction — it races only
  under an explicit BF_LINALG_GATE_RTOL widening or a forced impl.

Every implementation is exact-int (i8 paths) or accuracy-gated (float
paths: before the speed race, each candidate's on-device deviation
from the XLA baseline at the actual shape must stay inside the bf16
accuracy class — see LinAlg._GATE_RTOL).  BF_LINALG_AB_IMPL /
BF_LINALG_AAH_IMPL / BF_LINALG_I8_IMPL force a path.
"""

from __future__ import annotations

import os

import numpy as np

from ..dtype import DataType
from .common import as_jax, logical_dtype
from .fft import _writeback

__all__ = ['LinAlg', 'matmul', 'xcorr_int8', 'xcorr_prewarm',
           'XEngine', 'XCORR_CLASSES', 'xcorr_class_rtol']


def _reim_planes(x, kind, nbits, dev_dtype):
    """(re, im) planes of a bf ndarray of the given complex dtype, or
    None — never promoting to a wider complex type, so the device read
    stays at the narrow width."""
    from ..ndarray import ndarray as bf_ndarray
    import jax.numpy as jnp
    if isinstance(x, bf_ndarray) and x.dtype.kind == kind \
            and x.dtype.nbits == nbits:
        if x.space == 'tpu':
            arr = x.data  # trailing (re, im) axis of length 2
            if arr.shape[-1] == 2 and arr.dtype == dev_dtype:
                return arr[..., 0], arr[..., 1]
            return None
        buf = x.as_numpy()
        return jnp.asarray(buf['re']), jnp.asarray(buf['im'])
    return None


def _int8_reim(x):
    """ci8 planes — keeps the MXU int8 path honest."""
    import jax.numpy as jnp
    return _reim_planes(x, 'ci', 8, jnp.int8)


def _cf16_reim(x):
    """cf16 planes: half-width HBM reads straight into the planar
    GEMMs (the reference's Cherk3mEx cf16 design point,
    src/linalg.cu:210-226) — the lever at bandwidth-bound beamform
    shapes."""
    import jax.numpy as jnp
    return _reim_planes(x, 'cf', 16, jnp.float16)


# ---------------------------------------------------------------------------
# real-matmul building blocks
# ---------------------------------------------------------------------------

def _mm_f32(a, b):
    import jax.numpy as jnp
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _split_hilo(x):
    """f32 -> (hi, lo) bf16 planes with x == hi + lo up to bf16(lo)
    rounding (lo captures the next 8 mantissa bits)."""
    import jax.numpy as jnp
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mm_hilo(a, b):
    """f32-accuracy-class matmul as three bf16 MXU passes with f32
    accumulation (drops the lo@lo term, ~2^-16 relative)."""
    import jax.numpy as jnp
    ah, al = _split_hilo(a)
    bh, bl = _split_hilo(b)
    f32 = jnp.float32
    return (jnp.matmul(ah, bh, preferred_element_type=f32)
            + (jnp.matmul(ah, bl, preferred_element_type=f32)
               + jnp.matmul(al, bh, preferred_element_type=f32)))


def _mm_bf16(a, b):
    """ONE bf16 MXU pass with f32 accumulation: full MXU rate, bf16
    input rounding (~2^-8 relative — measured ~4e-3 even for f16
    planes, above the default accuracy gate).  Races only when the
    operator explicitly widens the gate (BF_LINALG_GATE_RTOL) or
    forces the impl; never admitted unchecked."""
    import jax.numpy as jnp
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _cmm_planar(ar, ai, br, bi, mm):
    """Complex matmul on planes, Karatsuba 3-multiply.  The m3 addends
    are widened to f32 first: for f16 planes, re+im can overflow the
    f16 range (max 65504) for values that are individually in range —
    the HBM read already happened, so the cast is free."""
    import jax.numpy as jnp

    def wide(x):
        return x.astype(jnp.float32) if x.dtype.itemsize < 4 else x

    m1 = mm(ar, br)
    m2 = mm(ai, bi)
    m3 = mm(wide(ar) + wide(ai), wide(br) + wide(bi))
    return m1 - m2, m3 - m1 - m2


def _planes(x):
    """(re, im) planes of an operand.  Operands arrive either as jax
    complex/real arrays or as an (re, im) plane tuple (the cf16 device
    rep — never promoted to complex64 so its HBM reads stay
    half-width)."""
    import jax.numpy as jnp
    if isinstance(x, tuple):
        return x
    if jnp.iscomplexobj(x):
        return jnp.real(x), jnp.imag(x)
    return x, None


def _as_complex(x):
    """Operand as a complex/real jax array (the XLA-baseline impls
    need the interleaved form; plane tuples are combined here)."""
    import jax.numpy as jnp
    if isinstance(x, tuple):
        return x[0].astype(jnp.float32) + 1j * x[1].astype(jnp.float32)
    return x


# ---------------------------------------------------------------------------
# a @ b implementations (complex-capable GEMM)
# ---------------------------------------------------------------------------

def _ab_xla(a, b, c, alpha, beta):
    import jax.numpy as jnp
    a, b = _as_complex(a), _as_complex(b)
    acc = jnp.complex64 if jnp.iscomplexobj(a) or jnp.iscomplexobj(b) \
        else jnp.float32
    y = alpha * jnp.matmul(a, b, preferred_element_type=acc)
    if beta != 0 and c is not None:
        y = y + beta * c
    return y


def _ab_planar_with(mm):
    def impl(a, b, c, alpha, beta):
        import jax.numpy as jnp
        ar, ai = _planes(a)
        br, bi = _planes(b)
        if ai is None and bi is None:
            y = alpha * mm(ar, br).astype(jnp.float32)
        else:
            if ai is None:
                yr, yi = mm(ar, br), mm(ar, bi)
            elif bi is None:
                yr, yi = mm(ar, br), mm(ai, br)
            else:
                yr, yi = _cmm_planar(ar, ai, br, bi, mm)
            y = alpha * (yr + 1j * yi)
        if beta != 0 and c is not None:
            y = y + beta * c
        return y
    return impl


_AB_IMPLS = {
    'xla': _ab_xla,
    'planar': _ab_planar_with(_mm_f32),
    'planar_hilo': _ab_planar_with(_mm_hilo),
    'planar_bf16': _ab_planar_with(_mm_bf16),
}


# ---------------------------------------------------------------------------
# a @ a^H implementations (complex float)
# ---------------------------------------------------------------------------

def _aah_xla(a, c, alpha, beta):
    import jax.numpy as jnp
    a = _as_complex(a)
    y = alpha * jnp.matmul(a, jnp.conj(jnp.swapaxes(a, -1, -2)),
                           preferred_element_type=jnp.complex64)
    if beta != 0 and c is not None:
        y = y + beta * c
    return y


def _aah_planar_with(mm):
    def impl(a, c, alpha, beta):
        import jax.numpy as jnp
        ar, ai = _planes(a)
        arT = jnp.swapaxes(ar, -1, -2)
        if ai is None:
            y = (alpha * mm(ar, arT)).astype(jnp.complex64)
        else:
            aiT = jnp.swapaxes(ai, -1, -2)
            rr = mm(ar, arT)
            ii = mm(ai, aiT)
            k = mm(ai, arT)
            y = alpha * ((rr + ii) +
                         1j * (k - jnp.swapaxes(k, -1, -2)))
        if beta != 0 and c is not None:
            y = y + beta * c
        return y
    return impl


_AAH_IMPLS = {
    'xla': _aah_xla,
    'planar': _aah_planar_with(_mm_f32),
    'planar_hilo': _aah_planar_with(_mm_hilo),
    'planar_bf16': _aah_planar_with(_mm_bf16),
}


# ---------------------------------------------------------------------------
# int8 a @ a^H implementations (ci8 correlation)
# ---------------------------------------------------------------------------

def _aah_i8_3mm(re, im, c, alpha, beta):
    """Three real int8 MXU matmuls, int32 accumulation:
    A A^H = (re.re^T + im.im^T) + i(K - K^T),  K = im.re^T
    (the Cherk3mEx reduction; reference: src/linalg.cu:130-148)."""
    import jax.numpy as jnp
    reT = jnp.swapaxes(re, -1, -2)
    imT = jnp.swapaxes(im, -1, -2)
    rr = jnp.matmul(re, reT, preferred_element_type=jnp.int32)
    ii = jnp.matmul(im, imT, preferred_element_type=jnp.int32)
    k = jnp.matmul(im, reT, preferred_element_type=jnp.int32)
    y = (rr + ii).astype(jnp.float32) + \
        1j * (k - jnp.swapaxes(k, -1, -2)).astype(jnp.float32)
    y = alpha * y
    if beta != 0 and c is not None:
        y = y + beta * c
    return y


def _aah_i8_gram(re, im, c, alpha, beta):
    """ONE widened int8 matmul: stack z = [re; im] on the row axis and
    take z @ z^T; its 4 blocks hold rr, ri, ir, ii.  4/3 the MACs of
    the 3-multiply but a single large MXU-shaped kernel; int32
    accumulation keeps it exact.  yi needs no transpose: the ri block
    IS K^T."""
    import jax.numpy as jnp
    n = re.shape[-2]
    z = jnp.concatenate([re, im], axis=-2)
    g = jnp.matmul(z, jnp.swapaxes(z, -1, -2),
                   preferred_element_type=jnp.int32)
    rr = g[..., :n, :n]
    ri = g[..., :n, n:]     # re.im^T == K^T
    ir = g[..., n:, :n]     # im.re^T == K
    ii = g[..., n:, n:]
    y = (rr + ii).astype(jnp.float32) + 1j * (ir - ri).astype(jnp.float32)
    y = alpha * y
    if beta != 0 and c is not None:
        y = y + beta * c
    return y


_I8_IMPLS = {
    'i8_3mm': _aah_i8_3mm,
    'i8_gram': _aah_i8_gram,
}

#: (family, shapes_key) -> fallback impl frozen after a probe where
#: every candidate errored — in-process only (see LinAlg._pick)
_NEG_PROBE_CACHE = {}


def _force_env(var, allowed):
    v = os.environ.get(var, '').strip().lower()
    return v if v in allowed else None


def _probe_wanted():
    """Single source of truth for BF_LINALG_PROBE semantics: probe on
    TPU unless '0', probe anywhere when '1'."""
    probe_env = os.environ.get('BF_LINALG_PROBE', '').strip()
    if probe_env == '1':
        return True
    if probe_env == '0':
        return False
    try:
        import jax
        return jax.default_backend() == 'tpu'
    except Exception:
        return False


class LinAlg(object):
    """Plan-style wrapper (reference: python/bifrost/linalg.py).

    Implementation selection per call family: an env override wins
    (BF_LINALG_AB_IMPL / BF_LINALG_AAH_IMPL / BF_LINALG_I8_IMPL);
    otherwise on TPU the candidates are measured at the actual shape
    and the winner cached (ops.mprobe policy); off-TPU the XLA path is
    used (CPU lowering has no interleaved-complex penalty to dodge).
    Float-path candidates are accuracy-gated before any timing: an
    impl deviating from the XLA baseline by more than _GATE_RTOL
    relative at the actual shape is excluded."""

    def __init__(self, ab_impl=None, aah_impl=None, i8_impl=None):
        self._force = {
            'ab': ab_impl or _force_env('BF_LINALG_AB_IMPL', _AB_IMPLS),
            'aah': aah_impl or _force_env('BF_LINALG_AAH_IMPL',
                                          _AAH_IMPLS),
            'i8': i8_impl or _force_env('BF_LINALG_I8_IMPL', _I8_IMPLS),
        }
        self.chosen = {}
        self.probe_ms = {}
        self._jits = {}

    def _jit(self, family, name):
        import jax
        key = (family, name)
        fn = self._jits.get(key)
        if fn is None:
            impls = {'ab': _AB_IMPLS, 'aah': _AAH_IMPLS,
                     'i8': _I8_IMPLS}[family]
            fn = jax.jit(impls[name], static_argnames=('alpha', 'beta'))
            self._jits[key] = fn
        return fn

    def _pick(self, family, shapes_key, candidates, make_args,
              gate=False):
        """Winner for this call family at this shape.  ``make_args``
        returns the positional operands WITHOUT alpha/beta/c — the
        probe times the alpha=1, beta=0 form of each candidate.

        With ``gate=True`` (complex float families) the candidates are
        accuracy-gated before timing.  Both the gate and the timing run
        at most once per (family, shape): a cached winner (in-process
        or on disk) is returned without executing any candidate, so the
        steady-state gulp loop pays only dict lookups.  When every
        candidate errors, the fallback default is remembered in-process
        (negative cache) so steady-state calls stop re-running the full
        gate+race every gulp."""
        if self._force[family]:
            self.chosen[family] = self._force[family]
            return self._force[family]
        default = {'ab': 'xla', 'aah': 'xla', 'i8': 'i8_3mm'}[family]
        if gate:
            # the gate width is part of the measurement's identity: a
            # winner admitted under a widened BF_LINALG_GATE_RTOL (e.g.
            # the ~2^-8 single-pass bf16 path) must never be served to
            # a default-gate session from the shared disk cache
            rtol = self._gate_rtol()
            if rtol != LinAlg._GATE_RTOL:
                shapes_key = '%s|gate_rtol=%g' % (shapes_key, rtol)
        if _probe_wanted() and len(candidates) > 1:
            neg = _NEG_PROBE_CACHE.get((family, shapes_key))
            if neg is not None:
                self.chosen[family] = neg
                return neg
            from . import mprobe
            cached = mprobe.peek('linalg_%s' % family, shapes_key)
            if cached is not None and cached[0] in candidates:
                self.chosen[family] = cached[0]
                self.probe_ms[family] = cached[1]
                return cached[0]
            probe_fns = {
                n: (lambda f: lambda *a: f(*a, None, alpha=1.0,
                                           beta=0.0))(
                    self._jit(family, n))
                for n in candidates}
            persist = True
            if gate:
                keep, had_errors = self._accuracy_gate(probe_fns,
                                                       make_args)
                probe_fns = {n: probe_fns[n] for n in keep}
                persist = not had_errors
            winner, ms, _err = mprobe.select(
                'linalg_%s' % family, shapes_key, probe_fns, make_args,
                persist=persist)
            if winner is not None:
                self.chosen[family] = winner
                self.probe_ms[family] = ms
                return winner
            # every candidate errored (or was gated out): freeze the
            # fallback for this shape in-process — not to disk, so a
            # transient failure is re-measured next session
            _NEG_PROBE_CACHE[(family, shapes_key)] = default
        self.chosen[family] = default
        return default

    # a candidate deviating from the XLA baseline by more than this
    # (relative, at the actual shape) is excluded from the speed race:
    # the bound admits the hi-lo split's legitimate ~2^-16 truncation
    # while catching a broken lowering outright.  The single-pass bf16
    # candidate (~2^-8) always fails this default — it only races
    # under an explicit widening (BF_LINALG_GATE_RTOL) or a force.
    _GATE_RTOL = 1e-3
    # candidates that are by construction below f32 accuracy class:
    # these must NEVER be admitted without a passing gate measurement
    _LOSSY = frozenset(['planar_bf16'])

    @staticmethod
    def _gate_rtol():
        try:
            return float(os.environ.get('BF_LINALG_GATE_RTOL', '')
                         or LinAlg._GATE_RTOL)
        except ValueError:
            return LinAlg._GATE_RTOL

    @staticmethod
    def _accuracy_gate(impls, make_args):
        """(keep, had_errors): candidates whose on-device deviation
        from the XLA baseline at the actual shape stays inside
        _gate_rtol() relative (mprobe.accuracy_gate).  Runs once per
        (family, shape) — only when no cached winner exists."""
        from . import mprobe
        return mprobe.accuracy_gate('linalg', impls, make_args,
                                    LinAlg._gate_rtol(),
                                    lossy=LinAlg._LOSSY)

    # -- public API ---------------------------------------------------------

    def matmul(self, alpha, a, b, beta, c):
        """c = alpha*a@b + beta*c, or a@a^H when b is None
        (reference: bfLinAlgMatMul, src/linalg.cu:877)."""
        import jax.numpy as jnp
        alpha = complex(alpha) if np.iscomplexobj(np.asarray(alpha)) \
            else float(alpha)
        beta = complex(beta) if np.iscomplexobj(np.asarray(beta)) \
            else float(beta)
        cj = as_jax(c) if (c is not None and beta != 0) else None

        def operand(x):
            """(jax array or (re, im) f16 plane tuple, key fragment).
            cf16 stays planar end-to-end — half-width HBM reads are
            the point (reference: Cherk3mEx cf16,
            src/linalg.cu:210-226); dtype is part of the key because a
            winner (and gate result) measured for f32 is invalid for
            c64 or cf16 at the same shape."""
            cf = _cf16_reim(x)
            if cf is not None:
                return cf, '%s cf16' % (cf[0].shape,)
            xj = as_jax(x)
            return xj, '%s %s' % (xj.shape, xj.dtype)

        if b is None:
            reim = _int8_reim(a)
            if reim is not None:
                re, im = reim
                name = self._pick('i8', 'shape=%s' % (re.shape,),
                                  _I8_IMPLS, lambda: (re, im))
                y = self._jit('i8', name)(re, im, cj,
                                          alpha=alpha, beta=beta)
            else:
                aj, akey = operand(a)
                # gate unconditionally: real-float races include the
                # lossy single-pass bf16 candidate too
                name = self._pick('aah', 'a=%s' % akey, _AAH_IMPLS,
                                  lambda: (aj,), gate=True)
                y = self._jit('aah', name)(aj, cj,
                                           alpha=alpha, beta=beta)
        else:
            aj, akey = operand(a)
            bj, bkey = operand(b)
            name = self._pick(
                'ab', 'a=%s b=%s' % (akey, bkey), _AB_IMPLS,
                lambda: (aj, bj), gate=True)
            y = self._jit('ab', name)(aj, bj, cj,
                                      alpha=alpha, beta=beta)
        if c is not None:
            odt = logical_dtype(c)
            tgt = jnp.dtype(odt.as_jax_dtype())
            if y.dtype != tgt:
                if not np.issubdtype(tgt, np.complexfloating) and \
                        np.issubdtype(y.dtype, np.complexfloating):
                    y = y.real
                y = y.astype(tgt)
            return _writeback(y, c)
        return y


# ---------------------------------------------------------------------------
# cross-correlation entry point (FX correlator X-step; blocks.correlate
# routes here)
# ---------------------------------------------------------------------------

def _xcorr_einsum(re_i, im_i, re_j, im_j):
    import jax.numpy as jnp
    rr = jnp.einsum('tfi,tfj->fij', re_i, re_j,
                    preferred_element_type=jnp.int32)
    ii = jnp.einsum('tfi,tfj->fij', im_i, im_j,
                    preferred_element_type=jnp.int32)
    ir = jnp.einsum('tfi,tfj->fij', im_i, re_j,
                    preferred_element_type=jnp.int32)
    ri = jnp.einsum('tfi,tfj->fij', re_i, im_j,
                    preferred_element_type=jnp.int32)
    return (rr + ii).astype(jnp.float32) + \
        1j * (ir - ri).astype(jnp.float32)


def _xcorr_fmt(re_i, im_i, re_j, im_j):
    """Pre-transpose to (F, n, T) / (F, T, n) so the contraction is a
    canonical batched GEMM — the relayout is paid once, explicitly,
    instead of inside XLA's dot lowering where it may not fuse."""
    import jax.numpy as jnp

    def t_in(x):                      # (T, F, n) -> (F, n, T)
        return jnp.transpose(x, (1, 2, 0))

    def t_jn(x):                      # (T, F, n) -> (F, T, n)
        return jnp.transpose(x, (1, 0, 2))

    a_re, a_im = t_in(re_i), t_in(im_i)
    b_re, b_im = t_jn(re_j), t_jn(im_j)
    mm = lambda x, y: jnp.matmul(x, y, preferred_element_type=jnp.int32)
    rr = mm(a_re, b_re)
    ii = mm(a_im, b_im)
    ir = mm(a_im, b_re)
    ri = mm(a_re, b_im)
    return (rr + ii).astype(jnp.float32) + \
        1j * (ir - ri).astype(jnp.float32)


def _xcorr_einsum3(re_i, im_i, re_j, im_j):
    """Auto-correlation only: the Hermitian structure makes the cross
    term one matmul (K - K^T), 3 einsums instead of 4."""
    import jax.numpy as jnp
    rr = jnp.einsum('tfi,tfj->fij', re_i, re_i,
                    preferred_element_type=jnp.int32)
    ii = jnp.einsum('tfi,tfj->fij', im_i, im_i,
                    preferred_element_type=jnp.int32)
    k = jnp.einsum('tfi,tfj->fij', im_i, re_i,
                   preferred_element_type=jnp.int32)
    return (rr + ii).astype(jnp.float32) + \
        1j * (k - jnp.swapaxes(k, -1, -2)).astype(jnp.float32)


def _xcorr_fmt3(re_i, im_i, re_j, im_j):
    """Auto-correlation only: pre-transposed batched GEMM form of the
    3-matmul reduction."""
    import jax.numpy as jnp
    a_re = jnp.transpose(re_i, (1, 2, 0))           # (F, n, T)
    a_im = jnp.transpose(im_i, (1, 2, 0))
    b_re = jnp.transpose(re_i, (1, 0, 2))           # (F, T, n)
    b_im = jnp.transpose(im_i, (1, 0, 2))
    mm = lambda x, y: jnp.matmul(x, y, preferred_element_type=jnp.int32)
    rr = mm(a_re, b_re)
    ii = mm(a_im, b_im)
    k = mm(a_im, b_re)
    return (rr + ii).astype(jnp.float32) + \
        1j * (k - jnp.swapaxes(k, -1, -2)).astype(jnp.float32)


def _xcorr_gram(re_i, im_i, re_j, im_j):
    """Auto-correlation only (i is j): one widened int8 gram matmul in
    the (F, 2n, T) layout."""
    import jax.numpy as jnp
    z = jnp.concatenate([re_i, im_i], axis=-1)      # (T, F, 2n)
    zt = jnp.transpose(z, (1, 2, 0))                # (F, 2n, T)
    g = jnp.matmul(zt, jnp.transpose(z, (1, 0, 2)),
                   preferred_element_type=jnp.int32)
    n = re_i.shape[-1]
    rr = g[..., :n, :n]
    ri = g[..., :n, n:]
    ir = g[..., n:, :n]
    ii = g[..., n:, n:]
    return (rr + ii).astype(jnp.float32) + 1j * (ir - ri).astype(jnp.float32)


def _xcorr_pallas(re_i, im_i, re_j, im_j):
    """Auto-correlation only: the fused Hermitian Pallas kernel — all
    three int8 MXU dots and the visibility epilogue stay in VMEM, one
    HBM write per channel (ops.pallas_kernels.xcorr_herm).  Races
    measured; auto-dropped where Mosaic rejects the shape."""
    from .pallas_kernels import xcorr_herm
    return xcorr_herm(re_i, im_i)


def _xcorr_pallas_cross(re_i, im_i, re_j, im_j):
    """Cross blocks (station-sharded mesh form): four fused int8 MXU
    dots per channel (ops.pallas_kernels.xcorr_cross)."""
    from .pallas_kernels import xcorr_cross
    return xcorr_cross(re_i, im_i, re_j, im_j)


_XCORR_IMPLS = {
    'einsum': _xcorr_einsum,
    'fmt': _xcorr_fmt,
    'pallas': _xcorr_pallas_cross,
}
_XCORR_AUTO_IMPLS = dict(_XCORR_IMPLS, einsum3=_xcorr_einsum3,
                         fmt3=_xcorr_fmt3, gram=_xcorr_gram,
                         pallas=_xcorr_pallas)

_xcorr_jits = {}
_xcorr_chosen = {}


def _xcorr_race_impls(impls):
    """Candidates eligible for the measured race on this backend.  The
    pallas kernel races only on TPU and only when the cheap Pallas
    availability probe passes: off-TPU its interpret-mode fallback is
    orders of magnitude too slow to time at production shapes, and on
    a backend where Pallas doesn't run, an ungated failure inside a
    live pipeline process could poison every subsequent op (the lesson
    bench._run_isolated documents).  A forced BF_LINALG_XCORR_IMPL or
    explicit impl= still dispatches it regardless."""
    if 'pallas' not in impls:
        return impls
    try:
        import jax
        on_tpu = jax.default_backend() == 'tpu'
    except Exception:
        on_tpu = False
    if on_tpu:
        from .pallas_kernels import available
        if available():
            return impls
    return {k: v for k, v in impls.items() if k != 'pallas'}


def xcorr_int8(re_i, im_i, re_j=None, im_j=None, impl=None):
    """FX-correlator cross-multiply on int8 planes.

    (T, F, n_i) x (T, F, n_j) -> (F, n_i, n_j) complex64 visibilities
    integrated over T (vis[f, i, j] = sum_t x_i x_j^*).  When re_j/im_j
    are omitted the auto-correlation gains the widened-gram candidate.
    Exact int32 accumulation on every path; the winning layout is
    measured per shape on TPU (BF_LINALG_XCORR_IMPL forces one).
    Reference: the xGPU-style cherk design point, src/linalg.cu:210-226.
    """
    import jax
    auto = re_j is None
    if auto:
        re_j, im_j = re_i, im_i
    impls = _XCORR_AUTO_IMPLS if auto else _XCORR_IMPLS
    # the Hermitian 3-einsum form is the exact auto-correlation
    # equivalent at 3/4 the MACs — the right default wherever no
    # measurement is available
    default = 'einsum3' if auto else 'einsum'
    name = impl or _force_env('BF_LINALG_XCORR_IMPL', impls)
    key = 'auto=%s i=%s j=%s' % (auto, re_i.shape, re_j.shape)
    if name is None and isinstance(re_i, jax.core.Tracer):
        # inside an outer jit trace (the block path): no measuring
        # possible here — reuse a winner probed eagerly at this shape
        # (blocks pre-warm via xcorr_prewarm at on_sequence), else
        # consult the probe cache from an earlier session, else the
        # default.  The cache peek is pure Python — trace-safe.  A
        # miss falls back WITHOUT recording: a later eager prewarm at
        # this shape must still be able to measure.
        name = _xcorr_chosen.get(key)
        if name is None:
            from . import mprobe
            cached = mprobe.peek('linalg_xcorr', key)
            if cached is not None and cached[0] in impls:
                _xcorr_chosen[key] = name = cached[0]
            else:
                name = default
        return impls[name](re_i, im_i, re_j, im_j)
    if name is None:
        want = _probe_wanted()
        if want and key not in _xcorr_chosen:
            from . import mprobe
            # jit cache keyed by family too: 'pallas' names different
            # kernels in the auto and cross families
            jitted = {n: _xcorr_jits.setdefault((auto, n), jax.jit(f))
                      for n, f in _xcorr_race_impls(impls).items()}
            winner, ms, _ = mprobe.select(
                'linalg_xcorr', key, jitted,
                lambda: (re_i, im_i, re_j, im_j))
            _xcorr_chosen[key] = winner or default
        name = _xcorr_chosen.get(key, default) if want else default
    fn = _xcorr_jits.setdefault((auto, name), jax.jit(impls[name]))
    return fn(re_i, im_i, re_j, im_j)


def xcorr_prewarm(t, f, n_i, n_j=None):
    """Eagerly probe the xcorr layout winner at (T, F, n) so a later
    jit-traced xcorr_int8 at the same shape picks it up.  Blocks call
    this at on_sequence — probe cost lands at sequence start, never as
    first-gulp latency (VERDICT r4 item 6 policy).  No-op when probing
    is off (the traced call will use the default impl anyway)."""
    if not _probe_wanted():
        return
    import jax.numpy as jnp
    z = jnp.zeros((t, f, n_i), jnp.int8)
    if n_j is None:
        xcorr_int8(z, z)
    else:
        zj = jnp.zeros((t, f, n_j), jnp.int8)
        xcorr_int8(z, z, zj, zj)


# ---------------------------------------------------------------------------
# XEngine: the raced, accuracy-classed X-engine (FX correlator X-step;
# blocks.correlate routes here).  The beamform-side
# twin is ops.beamform.Beamformer — same selection machinery, but the
# correlation has NO weight-quantization step: on ci8 voltage planes
# the int8 candidates are EXACT (pure int32 accumulation, bit-identical
# to the numpy int64 oracle — tests/test_correlate.py asserts this), so
# they are admitted under EVERY accuracy class, not just 'int8'.
# ---------------------------------------------------------------------------

#: accuracy class -> gate rtol vs the XLA complex64 baseline (the
#: Beamformer BEAM_CLASSES ladder).  For the X-engine the classes bound
#: only the FLOAT candidates: planar's hi-lo truncation (~2^-16) passes
#: 'f32'; the one-pass bf16 candidate (~2^-8) needs 'bf16' or wider.
XCORR_CLASSES = {'f32': 1e-3, 'bf16': 8e-3, 'int8': 4e-2}


def xcorr_class_rtol(accuracy):
    """Effective gate rtol for an accuracy class, honoring an explicit
    BF_XCORR_GATE_RTOL override (mirrors BF_BEAM_GATE_RTOL)."""
    try:
        env = os.environ.get('BF_XCORR_GATE_RTOL', '').strip()
        if env:
            return float(env)
    except ValueError:
        pass
    return XCORR_CLASSES[accuracy]


def _xe_xla(re, im):
    """The exactness baseline: interleaved complex64 einsum of
    x @ x^H over the time axis, (T, F, n) -> (F, n, n)."""
    import jax.numpy as jnp
    x = (re.astype(jnp.float32) +
         1j * im.astype(jnp.float32)).astype(jnp.complex64)
    return jnp.einsum('tfi,tfj->fij', x, jnp.conj(x),
                      preferred_element_type=jnp.complex64)


def _xe_planar_with(mm):
    """Hermitian 3-matmul on (re, im) planes in the pre-transposed
    (F, n, T) @ (F, T, n) batched-GEMM layout (the _xcorr_fmt3 shape),
    with ``mm`` setting the precision: hi-lo (f32 class at the bf16
    MXU rate) or one-pass bf16 (lossy)."""
    def fn(re, im):
        import jax.numpy as jnp
        ar = jnp.transpose(re.astype(jnp.float32), (1, 2, 0))
        ai = jnp.transpose(im.astype(jnp.float32), (1, 2, 0))
        br = jnp.swapaxes(ar, -1, -2)
        bi = jnp.swapaxes(ai, -1, -2)
        rr = mm(ar, br)
        ii = mm(ai, bi)
        k = mm(ai, br)
        return (rr + ii).astype(jnp.complex64) + \
            1j * (k - jnp.swapaxes(k, -1, -2)).astype(jnp.complex64)
    return fn


#: engine candidates over (T, F, n) voltage planes -> (F, n, n) c64.
#: The int candidates reuse the raced xcorr layouts verbatim: einsum3
#: is the Hermitian 3-einsum, gram the ONE widened (F, 2n, T) int8
#: matmul ("widened-int8 einsum"), pallas the fused VMEM kernel.
_XENGINE_IMPLS = {
    'xla': _xe_xla,
    'planar': _xe_planar_with(_mm_hilo),
    'planar_bf16': _xe_planar_with(_mm_bf16),
    'int8_3mm': lambda re, im: _xcorr_einsum3(re, im, re, im),
    'int8_wide': lambda re, im: _xcorr_gram(re, im, re, im),
    'pallas': lambda re, im: _xcorr_pallas(re, im, re, im),
}

#: candidates below the f32 accuracy class by construction — never
#: admitted without a passing gate measurement (Beamformer._LOSSY
#: policy).  The int candidates are NOT here: exact on int planes.
_XENGINE_LOSSY = frozenset(['planar_bf16'])

#: candidates that consume the int8 voltage planes directly (exact
#: int32 accumulation; the verifier's quantization check keys on this)
_XENGINE_INT_IMPLS = frozenset(['int8_3mm', 'int8_wide', 'pallas'])


class XEngine(object):
    """Plan-style raced X-engine (PR 9 engine pattern).

    ``accuracy``: 'f32' (default) | 'bf16' | 'int8' — the class float
    candidates must stay inside to race; int candidates are exact on
    ci8 planes and race under every class.  ``impl`` (or
    ``BF_XCORR_IMPL``) forces a candidate, bypassing gate and race;
    ``BF_XCORR_GATE_RTOL`` widens/narrows the class bound and becomes
    part of the probe-cache key (the LinAlg gate-key policy).

    Calls take (re, im) voltage planes shaped (T, F, n) — int8 (the
    ci8 ring device rep, n = station*pol flattened) or float — and
    return (F, n, n) complex64 visibilities integrated over T.
    """

    def __init__(self, accuracy='f32', impl=None):
        if accuracy not in XCORR_CLASSES:
            raise ValueError('accuracy must be one of %s, got %r'
                             % (sorted(XCORR_CLASSES), accuracy))
        self.accuracy = accuracy
        self._force = impl or _force_env('BF_XCORR_IMPL',
                                         set(_XENGINE_IMPLS))
        self.chosen = {}
        self.probe_ms = {}
        self._jits = {}

    # -- selection -------------------------------------------------------

    def _build(self, name):
        return _XENGINE_IMPLS[name]

    def _jit(self, name):
        import jax
        fn = self._jits.get(name)
        if fn is None:
            fn = self._jits[name] = jax.jit(self._build(name))
        return fn

    def _candidates(self, int_input):
        """Candidate names eligible at this input dtype + accuracy
        class.  Float voltages cannot feed the int8 kernels; on int
        planes the int candidates are exact and race at every class."""
        rtol = xcorr_class_rtol(self.accuracy)
        names = ['xla', 'planar']
        if rtol >= XCORR_CLASSES['bf16']:
            names.append('planar_bf16')
        if int_input:
            names += ['int8_3mm', 'int8_wide']
            if self._pallas_raceable():
                names.append('pallas')
        return names

    @staticmethod
    def _pallas_raceable():
        """The Pallas kernel races only where it compiles natively
        (the _xcorr_race_impls policy); a forced impl still
        dispatches it regardless."""
        try:
            import jax
            if jax.default_backend() != 'tpu':
                return False
        except Exception:
            return False
        from .pallas_kernels import available
        return available()

    def _default(self, int_input):
        """Winner when no measurement is available: on int planes the
        Hermitian 3-einsum — exact and the historical xcorr_int8
        default, so unprobed sessions keep byte-identical lowering;
        the XLA baseline otherwise."""
        return 'int8_3mm' if int_input else 'xla'

    def _key(self, shape, dtype, int_input):
        rtol = xcorr_class_rtol(self.accuracy)
        key = 'acc=%s v=%s %s' % (self.accuracy, tuple(shape), dtype)
        if rtol != XCORR_CLASSES[self.accuracy]:
            key += '|gate_rtol=%g' % rtol
        return key

    def _gate(self, names, make_args):
        """(keep, had_errors): candidates within the class rtol of the
        XLA baseline at the actual shape (mprobe.accuracy_gate)."""
        from . import mprobe
        return mprobe.accuracy_gate(
            'xengine', {n: self._jit(n) for n in names}, make_args,
            xcorr_class_rtol(self.accuracy), lossy=_XENGINE_LOSSY)

    def _select(self, shape, dtype, int_input, make_args):
        key = self._key(shape, dtype, int_input)
        if self._force:
            self.chosen[key] = self._force
            return self._force
        default = self._default(int_input)
        names = self._candidates(int_input)
        if key in self.chosen:
            return self.chosen[key]
        if not (_probe_wanted() and len(names) > 1):
            self.chosen[key] = default
            return default
        from . import mprobe
        cached = mprobe.peek('xengine', key)
        if cached is not None and cached[0] in names:
            self.chosen[key] = cached[0]
            self.probe_ms[key] = cached[1]
            return cached[0]
        keep, had_errors = self._gate(names, make_args)
        fns = {n: self._jit(n) for n in keep}
        winner, ms, _err = mprobe.select('xengine', key, fns,
                                         make_args,
                                         persist=not had_errors)
        self.chosen[key] = winner or default
        if winner is not None:
            self.probe_ms[key] = ms
        return self.chosen[key]

    # -- public API ------------------------------------------------------

    def prewarm(self, t, f, n, int_input=True, seed=11):
        """Eagerly gate + race the candidates at the actual shape so a
        later jit-traced __call__ finds the winner in the cache —
        probe cost lands at on_sequence, never as first-gulp latency
        (the xcorr_prewarm policy).  Returns the winner name."""
        import jax.numpy as jnp
        shape = (t, f, n)
        rng = np.random.RandomState(seed)
        if int_input:
            re = rng.randint(-64, 64, shape).astype(np.int8)
            im = rng.randint(-64, 64, shape).astype(np.int8)
            dtype = 'int8'
        else:
            re = rng.randn(*shape).astype(np.float32)
            im = rng.randn(*shape).astype(np.float32)
            dtype = 'float32'
        if not _probe_wanted() and not self._force:
            name = self._default(int_input)
            self.chosen[self._key(shape, dtype, int_input)] = name
            return name
        rej = jnp.asarray(re)
        imj = jnp.asarray(im)
        return self._select(shape, dtype, int_input,
                            lambda: (rej, imj))

    def __call__(self, re, im):
        """Correlate (T, F, n) voltage planes -> (F, n, n) complex64
        on the selected candidate.  Trace-safe: under an outer jit the
        winner comes from the in-process cache (a prewarm at this
        shape), the mprobe disk cache, or the class default — never a
        measurement."""
        import jax
        int_input = jax.numpy.issubdtype(re.dtype, jax.numpy.integer)
        shape = tuple(re.shape)
        key = self._key(shape, str(re.dtype), int_input)
        name = self._force or self.chosen.get(key)
        if name is None:
            if isinstance(re, jax.core.Tracer):
                from . import mprobe
                cached = mprobe.peek('xengine', key)
                names = self._candidates(int_input)
                if cached is not None and cached[0] in names:
                    self.chosen[key] = name = cached[0]
                else:
                    name = self._default(int_input)
            else:
                name = self._select(
                    shape, str(re.dtype), int_input,
                    lambda: (re, im)) if _probe_wanted() \
                    else self._default(int_input)
        if isinstance(re, jax.core.Tracer):
            return self._build(name)(re, im)
        return self._jit(name)(re, im)

    def ops_per_frame(self, nfreq, n):
        """Real ops per time frame of the correlation GEMM (one
        complex MAC = 8 real ops) — the bench ops-accounting unit."""
        return 8 * nfreq * n * n


_default = None


def matmul(alpha, a, b, beta, c):
    global _default
    if _default is None:
        _default = LinAlg()
    return _default.matmul(alpha, a, b, beta, c)
