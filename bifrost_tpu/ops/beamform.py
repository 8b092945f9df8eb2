"""Quantized coherent-beamformer engine (the beamform side of the
reference's hand-beaten GEMM identity, src/linalg.cu:210-226; recipe
papers: "The Tensor-Core Beamformer" arXiv:2505.03269 for the quantized
fused kernel shape, "GPU-Powered Coherent Beamforming" arXiv:1412.4907
for the workload geometry).

The hot product is y[t, f, p, b] = sum_s w[p, b, s] * x[t, f, p, s],
or, for a tied-array beamformer whose beams are delays and so a phase
per channel, sum_s w[f, p, b, s] * x[t, f, p, s] (weights
``(F, P, B, S)``: upstream hands bfLinAlgMatMul a (chan, beam, input)
batch):
a batched GEMM whose voltage operand is, in a capture pipeline, ci8
ring data — int8 (re, im) planes that the MXU multiplies at ~7x the
f32 rate on the bench host (docs/perf.md ceilings table) and more on
real MXUs.  Every candidate implementation is raced under the
ops.mprobe measured-selection policy and accuracy-gated against the
XLA complex64 baseline at the actual shape before any timing:

- ``xla``          — interleaved-complex einsum, the exactness baseline
- ``planar``       — 4 real hi-lo bf16 matmuls on (re, im) planes with
                     f32 accumulation (~2^-16: f32 accuracy class at
                     the bf16 MXU rate)
- ``planar_bf16``  — the same 4 products as ONE bf16 pass each (full
                     MXU rate, ~2^-8 input rounding — LOSSY, races only
                     under the 'bf16'/'int8' accuracy classes)
- ``int8_wide``    — ONE widened int8 einsum: z = [re | im] against a
                     stacked weight block whose 2B columns hold
                     (yr, yi); EXACT int32 accumulation of the
                     quantized weights, dequantized by the weight
                     scale (the dp4a cherk analogue)
- ``pallas``       — the fused Pallas kernel
                     (ops.pallas_kernels.beamform_int8): all four int8
                     MXU dots per channel stay in VMEM, one HBM write
                     per (re, im) output plane; TPU-only in races
- ``pallas_bf16``  — the bf16 Pallas kernel
                     (ops.pallas_kernels.beamform_bf16): the
                     planar_bf16 math with the pallas kernel's VMEM
                     locality, accepting int8 OR float voltage planes;
                     TPU-only in races, LOSSY like planar_bf16

The four einsum candidates take the frequency axis of per-channel
weights as a batch axis of the same contraction.  The two Pallas
complex-beam kernels hold one weight set in VMEM for every channel
and all T frames of a channel: they are not raced for per-channel
weights and say so (``mprobe.refused``); what runs such weights on
the chip is the fused chain below, not a complex-beam candidate
(ROADMAP D4).

Gates and probes run on a TILE OF TIME (:func:`probe_nframe`): the
candidates' cost is linear in the frames, and at a deployment's shape
(16384 frames x 64 channels x 2 pol x 864 beams) the baseline's
complex64 output would be 14.5 GB, which no chip holds.

The ci8 ring's device representation (int8 planes with a trailing
(re, im) axis) feeds the int8 candidates DIRECTLY — unpack is fused
into the kernel's load and no f32 voltage array ever materializes in
HBM.

Accuracy classes (the gate rtol each admits, vs the XLA baseline):

=========  ========  =====================================================
class      rtol      admits
=========  ========  =====================================================
``f32``    1e-3      xla, planar (the LinAlg production gate)
``bf16``   8e-3      \\+ planar_bf16 (~2^-8 input rounding)
``int8``   4e-2      \\+ int8_wide, pallas (weight quantization ~2^-7)
=========  ========  =====================================================

A candidate that is lossy by construction can never race under a class
that does not admit its error — the engine's answer to "lossy winners
stay opt-in".  ``BF_BEAM_IMPL`` forces any candidate unconditionally
(the operator's override); ``BF_BEAM_GATE_RTOL`` widens/narrows the
active class bound explicitly, and (as in LinAlg) a non-default bound
becomes part of the probe-cache key so a widened-gate winner is never
served to a default-gate session.
"""

from __future__ import annotations

import os

import numpy as np

from .linalg import (_force_env, _probe_wanted, _mm_hilo, _mm_bf16,
                     LinAlg)

__all__ = ['Beamformer', 'BEAM_CLASSES', 'beam_class_rtol',
           'quantize_weights', 'fused_mode', 'fused_usable',
           'fused_detect', 'fused_operands', 'probe_nframe',
           'GATE_BYTES']

#: the most a gate or a race lets one candidate's output take: the
#: candidates are probed on as many frames as stay under it
GATE_BYTES = 256 << 20

#: accuracy class -> gate rtol vs the XLA complex64 baseline.  'f32'
#: is the LinAlg production bound; 'bf16' admits one-pass bf16 input
#: rounding (~2^-8); 'int8' admits the ~2^-7 weight-quantization step.
BEAM_CLASSES = {'f32': LinAlg._GATE_RTOL, 'bf16': 8e-3, 'int8': 4e-2}

#: candidates below the f32 accuracy class, by construction: these only
#: race under a class (or explicit BF_BEAM_GATE_RTOL) admitting them,
#: or a forced BF_BEAM_IMPL.
_LOSSY = frozenset(['planar_bf16', 'pallas_bf16', 'int8_wide',
                    'pallas'])

#: candidates that consume the int8 voltage planes directly (quantized
#: weights, exact int32 accumulation)
_INT_IMPLS = frozenset(['int8_wide', 'pallas'])

_IMPL_NAMES = ('xla', 'planar', 'planar_bf16', 'pallas_bf16',
               'int8_wide', 'pallas')

_PER_CHANNEL = ('the kernel holds one weight set for every channel '
                'and all frames of a channel in VMEM; per-channel '
                'weights run through xla, planar, planar_bf16, '
                'int8_wide, or the fused chain '
                '(stages.match_beamformer)')


def beam_class_rtol(accuracy):
    """Effective gate rtol for an accuracy class, honoring an explicit
    BF_BEAM_GATE_RTOL override (mirrors BF_LINALG_GATE_RTOL)."""
    try:
        env = os.environ.get('BF_BEAM_GATE_RTOL', '').strip()
        if env:
            return float(env)
    except ValueError:
        pass
    return BEAM_CLASSES[accuracy]


def quantize_weights(wr, wi):
    """(wr8, wi8, scale): symmetric int8 quantization of f32 weight
    planes.  Clips at [-127, 127] — NOT -128 — so the widened-weight
    block's negated copy (-wi8) can never overflow int8."""
    amax = float(max(np.max(np.abs(wr)), np.max(np.abs(wi)), 1e-30))
    scale = amax / 127.0
    q = lambda m: np.clip(np.round(m / scale), -127, 127) \
        .astype(np.int8)
    return q(wr), q(wi), scale


def probe_nframe(ntime, nfreq, npol, nbeam):
    """Frames a gate or a race of the candidates runs on, of a gulp's
    ``ntime``: as many as keep one candidate's complex64 output under
    GATE_BYTES."""
    return max(1, min(int(ntime),
                      GATE_BYTES // (8 * nfreq * npol * nbeam)))


def _wide_weight_block(wr8, wi8):
    """([F,] P, 2S, 2B) int8 block W2 with z @ W2 = [yr | yi] for
    z = [re | im]: one widened int8 contraction carries the full
    complex product (the single-big-kernel trick of the widened gram,
    ops.linalg._aah_i8_gram, adapted to a@b)."""
    # wr8/wi8: ([F,] P, B, S)
    wrT = np.swapaxes(wr8, -1, -2)            # ([F,] P, S, B)
    wiT = np.swapaxes(wi8, -1, -2)
    top = np.concatenate([wrT, wiT], axis=-1)             # re rows
    bot = np.concatenate([-wiT, wrT], axis=-1)            # im rows
    return np.concatenate([top, bot], axis=-2)     # ([F,] P, 2S, 2B)


def _esum(a, b, acc):
    """The canonical contraction: (T, F, P, S) x ([F,] P, B, S)
    -> (T, F, P, B)."""
    import jax.numpy as jnp
    return jnp.einsum('tfps,fpbs->tfpb' if np.ndim(b) == 4
                      else 'tfps,pbs->tfpb', a, b,
                      preferred_element_type=acc)


class Beamformer(object):
    """Plan-style quantized beamformer for a fixed weight set.

    ``weights``: complex, one of

    - ``(B, N)`` — beams x flattened (station*pol) inputs; voltages'
      trailing non-time/freq axes are flattened to N and the output has
      a single 'beam' axis;
    - ``(B, S)`` with a distinct pol axis — the same weights applied
      per polarization; output keeps the pol axis;
    - ``(P, B, S)`` — per-polarization weight sets;
    - ``(F, P, B, S)`` — a set per frequency channel (a tied-array
      beam is a delay: a phase that differs from channel to channel),
      P one or the stream's.  One quantisation scale for all of it.

    ``accuracy``: 'f32' (default) | 'bf16' | 'int8' — the accuracy
    class candidates must stay inside to race (see module docstring).
    ``impl`` forces a candidate (overrides the race and the gate;
    ``BF_BEAM_IMPL`` does the same from the environment).

    Calls take (re, im) voltage planes shaped (T, F, P, S) — int8
    (the ci8 ring device rep, P possibly 1) or float — and return
    complex64 beams (T, F, P, B).
    """

    def __init__(self, weights, accuracy='f32', impl=None):
        if accuracy not in BEAM_CLASSES:
            raise ValueError('accuracy must be one of %s, got %r'
                             % (sorted(BEAM_CLASSES), accuracy))
        self.accuracy = accuracy
        w = np.asarray(weights)
        if w.ndim == 2:
            w = w[None]                       # (1, B, S)
        if w.ndim not in (3, 4):
            raise ValueError('weights must be (B, N), (P, B, S) or '
                             '(F, P, B, S), got %s' % (w.shape,))
        #: channels the weights are for, None where one set serves all
        self.nfreq_w = w.shape[0] if w.ndim == 4 else None
        self.npol_w, self.nbeam, self.nstand = w.shape[-3:]
        self.wr = np.ascontiguousarray(w.real, np.float32)
        self.wi = np.ascontiguousarray(w.imag, np.float32)
        self.wr8, self.wi8, self.wscale = quantize_weights(self.wr,
                                                           self.wi)
        self._force = impl or _force_env('BF_BEAM_IMPL',
                                         set(_IMPL_NAMES))
        self.chosen = {}
        self.probe_ms = {}
        self._jits = {}
        self._consts = {}
        self._operands = {}
        self._fused_runs = {}

    # -- candidate implementations --------------------------------------

    def _const(self, name, build):
        """Cached NUMPY weight constant.  Deliberately not a jax
        array: jnp.asarray under an outer jit trace would cache a
        tracer, leaking it into the next trace (the mesh path builds
        one plan per layout) — numpy constifies fresh per trace."""
        c = self._consts.get(name)
        if c is None:
            c = self._consts[name] = np.asarray(build())
        return c

    def _pol_weights(self, npol):
        """Weight planes broadcast to the voltage pol count."""
        if self.npol_w == npol:
            return self.wr, self.wi, self.wr8, self.wi8
        if self.npol_w == 1:
            rep = lambda m: np.repeat(m, npol, axis=-3)
            return (rep(self.wr), rep(self.wi), rep(self.wr8),
                    rep(self.wi8))
        raise ValueError('weights have %d pol sets but voltages %d'
                         % (self.npol_w, npol))

    def _impl_xla(self, npol):
        import jax.numpy as jnp
        wr, wi, _, _ = self._pol_weights(npol)
        wc = self._const('wc%d' % npol,
                         lambda: (wr + 1j * wi).astype(np.complex64))

        def fn(re, im):
            x = (re.astype(jnp.float32) +
                 1j * im.astype(jnp.float32)).astype(jnp.complex64)
            return _esum(x, wc, jnp.complex64)
        return fn

    def _impl_planar(self, npol, mm):
        """4 real plane contractions through ``mm``-style precision:
        mm is applied via a hi-lo (or single-pass bf16) einsum pair."""
        import jax.numpy as jnp
        wr, wi, _, _ = self._pol_weights(npol)
        wrj = self._const('wr%d' % npol, lambda: wr)
        wij = self._const('wi%d' % npol, lambda: wi)
        hilo = mm is _mm_hilo

        def split(x):
            h = x.astype(jnp.bfloat16)
            l = (x - h.astype(jnp.float32)).astype(jnp.bfloat16)
            return h, l

        def prod(a, b):
            if not hilo:
                return _esum(a.astype(jnp.bfloat16),
                             b.astype(jnp.bfloat16), jnp.float32)
            # int8 voltage planes are EXACT in bf16 — only the weight
            # side needs the hi-lo split then (2 passes, not 3)
            bh, bl = split(b)
            if jnp.issubdtype(a.dtype, jnp.integer):
                ab = a.astype(jnp.bfloat16)
                return _esum(ab, bh, jnp.float32) + \
                    _esum(ab, bl, jnp.float32)
            ah, al = split(a.astype(jnp.float32))
            return (_esum(ah, bh, jnp.float32) +
                    (_esum(ah, bl, jnp.float32) +
                     _esum(al, bh, jnp.float32)))

        def fn(re, im):
            yr = prod(re, wrj) - prod(im, wij)
            yi = prod(re, wij) + prod(im, wrj)
            return (yr + 1j * yi).astype(jnp.complex64)
        return fn

    def _impl_int8_wide(self, npol):
        import jax.numpy as jnp
        _, _, wr8, wi8 = self._pol_weights(npol)
        w2 = self._const('w2%d' % npol,
                         lambda: _wide_weight_block(wr8, wi8))
        scale = np.float32(self.wscale)
        nb = self.nbeam

        def fn(re, im):
            yr, yi = self.int8_planes(re, im, w2=w2, nbeam=nb)
            return ((yr.astype(jnp.float32) +
                     1j * yi.astype(jnp.float32)) *
                    scale).astype(jnp.complex64)
        return fn

    def _impl_pallas(self, npol):
        import jax.numpy as jnp
        from . import pallas_kernels as pk
        self._one_set('pallas')
        _, _, wr8, wi8 = self._pol_weights(npol)
        wr8j = self._const('wr8%d' % npol, lambda: wr8)
        wi8j = self._const('wi8%d' % npol, lambda: wi8)
        scale = np.float32(self.wscale)

        def fn(re, im):
            outs = []
            for p in range(re.shape[2]):
                yr, yi = pk.beamform_int8(wr8j[p], wi8j[p],
                                          re[:, :, p], im[:, :, p])
                outs.append((yr.astype(jnp.float32) +
                             1j * yi.astype(jnp.float32)) * scale)
            return jnp.stack(outs, axis=2).astype(jnp.complex64)
        return fn

    def _impl_pallas_bf16(self, npol):
        """The planar_bf16 math inside the Pallas kernel's VMEM
        locality (ops.pallas_kernels.beamform_bf16): full-precision
        f32 weight planes, voltages cast to bf16 in VMEM."""
        import jax.numpy as jnp
        from . import pallas_kernels as pk
        self._one_set('pallas_bf16')
        wr, wi, _, _ = self._pol_weights(npol)
        wrj = self._const('wr%d' % npol, lambda: wr)
        wij = self._const('wi%d' % npol, lambda: wi)

        def fn(re, im):
            outs = []
            for p in range(re.shape[2]):
                yr, yi = pk.beamform_bf16(wrj[p], wij[p],
                                          re[:, :, p], im[:, :, p])
                outs.append(yr + 1j * yi)
            return jnp.stack(outs, axis=2).astype(jnp.complex64)
        return fn

    def _one_set(self, name):
        """The Pallas complex-beam kernels hold ONE weight set in VMEM
        for every channel: refuse per-channel weights by name."""
        if self.nfreq_w is not None:
            raise ValueError(
                'beamform candidate %r takes one weight set for every '
                'channel, not %d channels\' own (%s)'
                % (name, self.nfreq_w, _PER_CHANNEL))

    @staticmethod
    def int8_planes(re, im, w2, nbeam):
        """EXACT integer core of the widened-int8 candidate: int8
        voltage planes (T, F, P, S) against the ([F,] P, 2S, 2B) widened
        weight block -> (yr, yi) int32 planes (T, F, P, B).  Pure
        int32 accumulation — bit-identical to the numpy int64 oracle
        (tests/test_beamform.py asserts this); the caller applies the
        dequantization scale."""
        import jax.numpy as jnp
        z = jnp.concatenate([re, im], axis=-1)        # (T, F, P, 2S)
        y = jnp.einsum('tfpz,fpzc->tfpc' if np.ndim(w2) == 4
                       else 'tfpz,pzc->tfpc', z, w2,
                       preferred_element_type=jnp.int32)
        return y[..., :nbeam], y[..., nbeam:]

    # -- selection -------------------------------------------------------

    def _build(self, name, npol):
        if name == 'xla':
            return self._impl_xla(npol)
        if name == 'planar':
            return self._impl_planar(npol, _mm_hilo)
        if name == 'planar_bf16':
            return self._impl_planar(npol, _mm_bf16)
        if name == 'int8_wide':
            return self._impl_int8_wide(npol)
        if name == 'pallas':
            return self._impl_pallas(npol)
        if name == 'pallas_bf16':
            return self._impl_pallas_bf16(npol)
        raise KeyError(name)

    def _jit(self, name, npol):
        import jax
        key = (name, npol)
        fn = self._jits.get(key)
        if fn is None:
            fn = self._jits[key] = jax.jit(self._build(name, npol))
        return fn

    def _candidates(self, int_input):
        """Candidate names eligible at this input dtype + accuracy
        class.  Float voltages cannot feed the int8 kernels; a class
        that does not admit a lossy candidate's error excludes it from
        the race outright (it could only mislead the gate run)."""
        rtol = beam_class_rtol(self.accuracy)
        names = ['xla', 'planar']
        pallas = []
        if rtol >= BEAM_CLASSES['bf16']:
            names.append('planar_bf16')
            pallas.append('pallas_bf16')
        if int_input and rtol >= BEAM_CLASSES['int8']:
            names.append('int8_wide')
            pallas.append('pallas')
        if pallas and self._pallas_raceable():
            if self.nfreq_w is None:
                names.extend(pallas)
            else:
                # not given the frequency axis (module docstring):
                # they would have raced here, so it is said
                from . import mprobe
                for name in pallas:
                    mprobe.refused('beamform', name,
                                   NotImplementedError(_PER_CHANNEL))
        return names

    @staticmethod
    def _pallas_raceable():
        """The Pallas kernel races only where it compiles natively:
        off-TPU its interpret mode is orders of magnitude too slow at
        production shapes (same policy as linalg._xcorr_race_impls).
        A forced impl still dispatches it regardless."""
        try:
            import jax
            if jax.default_backend() != 'tpu':
                return False
        except Exception:
            return False
        from .pallas_kernels import available
        return available()

    def _default(self, int_input):
        """Winner when no measurement is available: the XLA baseline,
        except under the 'int8' class on int input — the operator
        declared the quantized tolerance, so the quantized path (whose
        error is within the class by construction) engages even where
        probing is off; measurement refines the choice."""
        if int_input and self.accuracy == 'int8':
            return 'int8_wide'
        return 'xla'

    def _key(self, shape, dtype, int_input):
        rtol = beam_class_rtol(self.accuracy)
        key = ('acc=%s w=(%s%d,%d,%d) v=%s %s'
               % (self.accuracy, '' if self.nfreq_w is None
                  else '%d,' % self.nfreq_w, self.npol_w, self.nbeam,
                  self.nstand, tuple(shape), dtype))
        if rtol != BEAM_CLASSES[self.accuracy]:
            # an explicit BF_BEAM_GATE_RTOL is part of the
            # measurement's identity (LinAlg gate-key policy)
            key += '|gate_rtol=%g' % rtol
        return key

    def _gate(self, names, npol, make_args):
        """(keep, had_errors): candidates within the class rtol of the
        XLA baseline at the actual shape (mprobe.accuracy_gate); the
        forced path bypasses this."""
        from . import mprobe
        return mprobe.accuracy_gate(
            'beamform', {n: self._jit(n, npol) for n in names},
            make_args, beam_class_rtol(self.accuracy), lossy=_LOSSY,
            max_bytes=GATE_BYTES)

    def _select(self, shape, dtype, int_input, make_args):
        """Measured winner for voltage planes of this shape/dtype —
        gate first, race the survivors, cache per the mprobe policy.
        ``make_args`` gives the planes the candidates are run on: a
        tile of the shape's frames (:func:`probe_nframe`)."""
        npol = shape[2]
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
        cached = mprobe.peek('beamform', key)
        if cached is not None and cached[0] in names:
            self.chosen[key] = cached[0]
            self.probe_ms[key] = cached[1]
            return cached[0]
        keep, had_errors = self._gate(names, npol, make_args)
        fns = {n: self._jit(n, npol) for n in keep}
        winner, ms, _err = mprobe.select('beamform', key, fns,
                                         make_args,
                                         persist=not had_errors)
        self.chosen[key] = winner or default
        if winner is not None:
            self.probe_ms[key] = ms
        return self.chosen[key]

    # -- public API ------------------------------------------------------

    def prewarm(self, t, f, npol=None, int_input=True, seed=11):
        """Eagerly gate + race the candidates at the actual gulp shape
        (random voltages) so a later jit-traced __call__ finds the
        winner in the cache — probe cost lands at on_sequence, never as
        first-gulp latency (the xcorr_prewarm policy).  Returns the
        winner name (the default when probing is off)."""
        import jax.numpy as jnp
        npol = npol or self.npol_w
        shape = (t, f, npol, self.nstand)
        dtype = 'int8' if int_input else 'float32'
        if self._force or not _probe_wanted():
            name = self._force or self._default(int_input)
            self.chosen[self._key(shape, dtype, int_input)] = name
            return name
        # the winner of the gulp's shape, measured on a tile of it
        tile = (probe_nframe(t, f, npol, self.nbeam),) + shape[1:]
        rng = np.random.RandomState(seed)
        if int_input:
            re = rng.randint(-64, 64, tile).astype(np.int8)
            im = rng.randint(-64, 64, tile).astype(np.int8)
        else:
            re = rng.randn(*tile).astype(np.float32)
            im = rng.randn(*tile).astype(np.float32)
        rej = jnp.asarray(re)
        imj = jnp.asarray(im)
        return self._select(shape, dtype, int_input,
                            lambda: (rej, imj))

    def __call__(self, re, im):
        """Beamform (T, F, P, S) voltage planes -> (T, F, P, B)
        complex64 beams on the selected candidate.  Trace-safe: under
        an outer jit the winner comes from the in-process cache (a
        prewarm at this shape), the mprobe disk cache, or the class
        default — never a measurement."""
        import jax
        int_input = jax.numpy.issubdtype(re.dtype, jax.numpy.integer)
        shape = tuple(re.shape)
        key = self._key(shape, str(re.dtype), int_input)
        name = self._force or self.chosen.get(key)
        if name is None:
            if isinstance(re, jax.core.Tracer):
                from . import mprobe
                cached = mprobe.peek('beamform', key)
                names = self._candidates(int_input)
                if cached is not None and cached[0] in names:
                    self.chosen[key] = name = cached[0]
                else:
                    name = self._default(int_input)
            elif _probe_wanted():
                n = probe_nframe(shape[0], shape[1], shape[2],
                                 self.nbeam)
                name = self._select(shape, str(re.dtype), int_input,
                                    lambda: (re[:n], im[:n]))
            else:
                name = self._default(int_input)
        if isinstance(re, jax.core.Tracer):
            return self._build(name, shape[2])(re, im)
        return self._jit(name, shape[2])(re, im)

    def ops_per_frame(self, nfreq, npol=None):
        """Real ops per time frame of the beamform GEMM (one complex
        MAC = 8 real ops) — the like_top / bench ops-accounting unit."""
        npol = npol or self.npol_w
        return 8 * nfreq * npol * self.nbeam * self.nstand


# ---------------------------------------------------------------------------
# fused beamform -> detect -> integrate [-> requantise] (the whole-chain
# kernel substitution, stages.match_beamformer)
# ---------------------------------------------------------------------------

def fused_mode():
    """BF_BEAM_FUSED: 'auto' (default — substitute the fused Pallas
    kernel when the chain matches and the engine's accuracy class
    admits int8: compiled by Mosaic on a TPU, where a refusal is
    reported and the stages run; interpreted elsewhere, which is what
    tests and rehearsals run), 'force' (substitute whatever the
    accuracy class, and raise on a refusal — test hook), or 'off'
    (never substitute)."""
    v = os.environ.get('BF_BEAM_FUSED', 'auto').strip().lower()
    return v if v in ('auto', 'force', 'off') else 'auto'


def fused_operands(engine, bf16=False):
    """The fused kernel's weight operand ON THE DEVICE, made once an
    engine and kept: ``(F or 1, 4 S, 4 Bp)``
    (pallas_kernels.beam_wide_weights), int8 from the engine's
    quantised planes, or bfloat16 from its float planes for the forced
    one-pass candidate.  An ARGUMENT of the gulp's program, never a
    constant folded into it: at a deployment's 58.7 MB a folded
    constant is set-up time and program size (blocks/fused.py passes
    it; a caller that closes over it gets the constant)."""
    key = 'fz_bf16' if bf16 else 'fz_int8'
    w = engine._operands.get(key)
    if w is None:
        import jax
        import jax.numpy as jnp
        from . import pallas_kernels as pk
        wr, wi, wr8, wi8 = engine._pol_weights(2)
        planes = (wr, wi) if bf16 else (wr8, wi8)
        host = pk.beam_wide_weights(
            *(p if p.ndim == 4 else p[None] for p in planes),
            dtype=jnp.bfloat16 if bf16 else None)
        with jax.ensure_compile_time_eval():
            w = engine._operands[key] = jax.device_put(host)
    return (w,)


def fused_detect(engine, x, rfactor, stokes='stokes', scale=1.0,
                 quantize=None, time_tile=None, bf16=False,
                 operands=None, nfreq=None):
    """The fused chain on a dual-pol ci8 gulp: beamform both pols with
    ``engine``'s quantised weights (its own set for every channel, or
    one for all), detect (``stokes``: 'stokes' or 'stokes_i'),
    integrate ``rfactor`` frames, multiply by ``scale`` and, with
    ``quantize`` = (lo, hi, dtype), round and clip — one Pallas
    program, time in tiles, beam voltages never leaving VMEM
    (pallas_kernels.beamform_detect).

    ``x``: the gulp's device representation (T, F, S, 2, 2) int8, or
    its int16 words on one axis with ``nfreq`` beside them
    (devrep.ComplexWords.words: (freq, station, pol) are a frame's
    words in the host's own order, so the kernel reads them as they
    landed, folded to rows of frames).  ``operands``:
    :func:`fused_operands`, where the caller passes them through its
    own jit as arguments.  Returns (T // rfactor, F, 4 or 1, B)."""
    import jax
    import jax.numpy as jnp
    from . import pallas_kernels as pk
    nstand = engine.nstand
    if x.dtype == jnp.int16:
        if not nfreq or x.size % (nfreq * nstand * 2):
            raise ValueError('the words of a (T, %r, %d, 2) ci8 gulp '
                             'wanted, got %s' % (nfreq, nstand, x.shape))
        rows = x.reshape(-1, nfreq * nstand * 2)
    else:
        nfreq = x.shape[1]
        rows = jax.lax.bitcast_convert_type(x, jnp.int16) \
            .reshape(x.shape[0], nfreq * nstand * 2)
    (w,) = fused_operands(engine, bf16) if operands is None else operands
    wscale = 1.0 if bf16 else float(engine.wscale)
    return pk.beamform_detect(
        rows, w, nfreq, engine.nbeam, rfactor, stokes=stokes,
        scale=wscale * wscale * float(scale), quantize=quantize,
        time_tile=time_tile)


def how_key(how):
    """fused_detect's keywords as a dictionary key."""
    return tuple(sorted((k, str(v)) for k, v in how.items()))


#: probe key -> bool; the compile probe runs at the EXACT substitution
#: shape (the spectrometer lesson: VMEM limits bind at the real tile,
#: not a toy probe), memoized either way so a backend that persistently
#: rejects the config is not re-probed per plan rebuild
_fused_probe = {}


def fused_usable(engine, t, f, rfactor, **how):
    """True when the fused kernel (``how``: fused_detect's keywords)
    compiles on this backend at the exact shape match_beamformer would
    substitute, from the gulp's words.  On a TPU the program is
    compiled ahead of time by Mosaic and XLA and not run: nothing of
    a gulp's size is made for the probe.  Off the TPU the kernel is
    interpreted and there is nothing to refuse.  A refusal is reported
    through ``mprobe.refused`` under BF_BEAM_FUSED=auto and RAISES
    under ``force``."""
    key = (engine.nfreq_w, engine.nbeam, engine.nstand, t, f, rfactor,
           how_key(how))
    hit = _fused_probe.get(key)
    if hit is not None:
        return hit
    try:
        import jax
        import jax.numpy as jnp
        if jax.default_backend() == 'tpu':
            ops = fused_operands(engine, how.get('bf16', False))
            jax.jit(lambda w, *o: fused_detect(
                engine, w, rfactor, nfreq=f, operands=o, **how)) \
                .lower(jax.ShapeDtypeStruct(
                    (t * f * engine.nstand * 2,), jnp.int16), *ops) \
                .compile()
        _fused_probe[key] = True
    except Exception as e:
        if fused_mode() == 'force':
            raise
        from . import mprobe
        mprobe.refused('beamform_fused', 'pallas[t=%d,f=%d,r=%d]'
                       % (t, f, rfactor), e)
        _fused_probe[key] = False
    return _fused_probe[key]
