"""Fusable device-block stages.

A Stage is the pure core of a device TransformBlock, split into its two
halves:

- ``transform_header(hdr) -> ohdr`` — per-sequence metadata negotiation
- ``build(in_meta) -> fn`` — build the jax function for one gulp, where
  ``in_meta`` describes the device-representation input array

A Block wraps one stage; :class:`bifrost_tpu.blocks.fused.FusedBlock`
wraps a chain of stages and jits the composition, so an entire block
chain (e.g. FFT → detect → reduce) executes as ONE XLA computation per
gulp — one dispatch, fully fused, zero intermediate HBM round trips the
compiler can't elide.  This is the TPU-native answer to the reference's
per-op kernel launches (reference: each block launches its own CUDA
kernel(s) per gulp, pipeline.py:627-628) and is where the framework
overtakes the CUDA design.
"""

from __future__ import annotations

from copy import deepcopy

import numpy as np

from .dtype import DataType
from .units import convert_units, transform_units

__all__ = ['Stage', 'FftStage', 'DetectStage', 'ReduceStage',
           'FftShiftStage', 'ReverseStage', 'TransposeStage',
           'ScrunchStage', 'MapStage', 'BeamformStage',
           'QuantizeStage', 'CorrelateStage', 'AccumulateStage',
           'FdmtStage', 'MatchedFilterStage', 'ThresholdStage',
           'CoherentDedispersionStage', 'FoldStage',
           'chain_overlap_nframe']


class Stage(object):
    """Base class; stages are stateful per-sequence (transform_header is
    called once per sequence, before build)."""

    #: (num, den): output_nframe = input_nframe * num // den
    nframe_ratio = (1, 1)

    #: Time-concat equivariance: True when applying the stage to K
    #: gulps stacked along the time axis equals applying it per gulp
    #: and concatenating the results — the condition for macro-gulp
    #: 'block' mode to run the stacked span through ONE program
    #: (bifrost_tpu.macro).  Every built-in stage is equivariant (the
    #: frame axis is either untouched, reduced in whole per-gulp
    #: groups, or only permuted); user-defined stages default to False,
    #: which routes them through the per-gulp 'sliced' mode instead —
    #: never a semantic change, just less fusion.
    batch_safe = False

    #: Frames of FUTURE input (lookahead) each output frame may
    #: reference: output frame t depends on input frames
    #: [t, t + overlap_nframe], so the last overlap_nframe output
    #: frames of any span are invalid until the next span recomputes
    #: them.  A wrapping block advertises the chain total as its ring
    #: overlap (define_input_overlap_nframe); inside a compiled
    #: segment the halo carry slices the ghost frames from the macro
    #: span head once and keeps interior handoffs elided
    #: (docs/perf.md).  Only meaningful on nframe_ratio == (1, 1)
    #: stages today.
    overlap_nframe = 0

    #: Arguments of the gulp's program besides the gulp.  A stage whose
    #: ``build`` function takes more than the gulp, ``fn(x, *operands)``,
    #: says here how many (:meth:`gulp_operands` gives them for each
    #: gulp: a fold's phase, from where the gulp lies in its sequence,
    #: or a dedispersion's chirp, the same device arrays every gulp of
    #: a sequence).  They are arguments of the one program, not
    #: constants folded into it, so no gulp recompiles and no executable
    #: holds a table; such a chain runs one gulp a program on one device
    #: (no macro-gulp batch, no segment, no mesh).
    n_gulp_operands = 0

    def transform_header(self, hdr):
        return hdr

    def build(self, in_meta):
        """in_meta: dict(shape=list incl. frame axis, dtype=DataType,
        taxis=int, reim=bool).  Return fn(jax array) -> jax array in
        device representation."""
        raise NotImplementedError

    def gulp_operands(self, frame_offset, nframe):
        """The ``n_gulp_operands`` arrays, host or device, of the gulp of
        ``nframe`` frames from frame ``frame_offset`` of its sequence."""
        return ()

    def output_nframe(self, input_nframe):
        num, den = self.nframe_ratio
        if (input_nframe * num) % den:
            raise ValueError("%s: nframe %d not divisible by %d"
                             % (type(self).__name__, input_nframe, den))
        return input_nframe * num // den


def _complexify_fn(in_meta):
    """Stage-input helper: device-rep (int pairs) -> complex, inside jit."""
    reim = in_meta.get('reim', False)

    def fn(x):
        import jax.numpy as jnp
        if reim and not jnp.issubdtype(x.dtype, jnp.complexfloating):
            return (x[..., 0].astype(jnp.float32) +
                    1j * x[..., 1].astype(jnp.float32))
        return x
    return fn


def _resolve_axis(tensor, axis):
    if isinstance(axis, str):
        return tensor['labels'].index(axis)
    return axis


class FftStage(Stage):
    """(reference: blocks/fft.py:39-137; src/fft.cu)"""

    batch_safe = True

    def __init__(self, axes, inverse=False, real_output=False,
                 axis_labels=None, apply_fftshift=False):
        if not isinstance(axes, (list, tuple)):
            axes = [axes]
        if not isinstance(axis_labels, (list, tuple)):
            axis_labels = [axis_labels]
        self.specified_axes = list(axes)
        self.inverse = inverse
        self.real_output = real_output
        self.axis_labels = list(axis_labels)
        self.apply_fftshift = apply_fftshift

    def transform_header(self, hdr):
        itensor = hdr['_tensor']
        itype = DataType(itensor['dtype']).as_floating_point()
        self.axes = [_resolve_axis(itensor, ax)
                     for ax in self.specified_axes]
        axes = self.axes
        shape = [itensor['shape'][ax] for ax in axes]
        otype = itype.as_real() if self.real_output else itype.as_complex()
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        otensor['dtype'] = str(otype)
        self.itype, self.otype = itype, otype
        self.mode = ('r2c' if itype.is_real and otype.is_complex else
                     'c2r' if itype.is_complex and otype.is_real else 'c2c')
        frame_axis = itensor['shape'].index(-1)
        if frame_axis in axes:
            raise KeyError("Cannot transform the frame axis; reshape the "
                           "stream first (views.split_axis)")
        if self.mode == 'r2c':
            otensor['shape'][axes[-1]] = otensor['shape'][axes[-1]] // 2 + 1
        elif self.mode == 'c2r':
            otensor['shape'][axes[-1]] = (otensor['shape'][axes[-1]] - 1) * 2
            shape[-1] = (shape[-1] - 1) * 2
        for i, (ax, length) in enumerate(zip(axes, shape)):
            if 'units' in otensor:
                otensor['units'][ax] = transform_units(
                    otensor['units'][ax], -1)
            if 'scales' in otensor:
                scale = otensor['scales'][ax][1]
                step = 1. / (scale * length)
                # a shifted forward spectrum starts at its most
                # negative frequency, as FftShiftStage's header says
                shifted = self.apply_fftshift and not self.inverse \
                    and self.mode == 'c2c'
                otensor['scales'][ax] = [-(length // 2) * step if shifted
                                         else 0, step]
            if 'labels' in otensor and self.axis_labels != [None]:
                otensor['labels'][ax] = self.axis_labels[i]
        self._oshape_tpl = list(otensor['shape'])
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        pre = _complexify_fn(in_meta)
        axes = list(self.axes)
        mode, shift, inverse = self.mode, self.apply_fftshift, self.inverse
        odt = self.otype.as_jax_dtype()
        itype = self.itype
        oshape_tpl = self._oshape_tpl

        def fn(x):
            x = pre(x)
            if mode == 'r2c':
                x = jnp.real(x).astype(
                    jnp.float64 if itype.nbits > 32 else jnp.float32)
                y = jnp.fft.rfftn(x, axes=axes)
                if shift:
                    y = jnp.fft.fftshift(y, axes=axes)
            elif mode == 'c2r':
                if shift:
                    x = jnp.fft.ifftshift(x, axes=axes)
                sizes = [(oshape_tpl[a] if oshape_tpl[a] != -1
                          else x.shape[a]) for a in axes]
                y = jnp.fft.irfftn(x, s=sizes, axes=axes)
                y = y * np.prod(sizes)
            else:
                from .ops.fft import fftn_dispatch
                if inverse:
                    if shift:
                        x = jnp.fft.ifftshift(x, axes=axes)
                    y = fftn_dispatch(x, axes, inverse=True)
                else:
                    y = fftn_dispatch(x, axes)
                    if shift:
                        y = jnp.fft.fftshift(y, axes=axes)
            return y.astype(odt)
        if mode == 'c2c':
            # which implementation the transform takes at this shape,
            # for the block that runs it to publish (compose_stages)
            from .ops.fft import fft_path
            shape = list(in_meta['shape'])
            if in_meta.get('reim', False):
                shape = shape[:-1]
            fn.impl_info = dict(
                fft_path(shape, axes, inverse,
                         'complex128' if itype.nbits > 32
                         else 'complex64'),
                nfft=[int(shape[a]) for a in axes])
        return fn


class DetectStage(Stage):
    """(reference: blocks/detect.py:40-138)"""

    batch_safe = True

    def __init__(self, mode, axis=None):
        self.mode = mode.lower()
        self.axis = axis
        if self.mode not in ('scalar', 'jones', 'stokes', 'stokes_i',
                             'coherence'):
            raise ValueError("Invalid detect mode: %r" % mode)

    def transform_header(self, hdr):
        itensor = hdr['_tensor']
        itype = DataType(itensor['dtype'])
        if not itype.is_complex:
            raise TypeError("detect requires complex input")
        axis = self.axis
        if axis is None and self.mode != 'scalar':
            axis = 'pol'
        if isinstance(axis, str):
            axis = itensor['labels'].index(axis)
        self.axis_index = axis
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        if axis is not None:
            self.npol = otensor['shape'][axis]
            if self.npol not in (1, 2):
                raise ValueError("Polarization axis must have length 1 or 2")
            if self.mode in ('stokes', 'coherence') and self.npol == 2:
                otensor['shape'][axis] = 4
            if self.mode == 'stokes_i' and self.npol == 2:
                otensor['shape'][axis] = 1
            if 'labels' in otensor:
                otensor['labels'][axis] = 'pol'
        else:
            self.npol = 1
        otype = itype if (self.mode == 'jones' and self.npol == 2) \
            else itype.as_real()
        otensor['dtype'] = str(DataType(str(otype)).as_floating_point())
        self.otype = DataType(otensor['dtype'])
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        pre = _complexify_fn(in_meta)
        mode, axis, npol = self.mode, self.axis_index, self.npol
        odt = self.otype.as_jax_dtype()
        # logical rank: the trailing (re,im) pair axis of ci-dtype device
        # representations disappears after complexification
        ndim = len(in_meta['shape']) - \
            (1 if in_meta.get('reim', False) else 0)

        def mag2(v):
            return jnp.real(v) ** 2 + jnp.imag(v) ** 2

        def take(x, p):
            idx = [slice(None)] * ndim
            idx[axis] = p
            return x[tuple(idx)]

        def fn(x):
            x = pre(x)
            if npol == 1:
                return mag2(x).astype(odt)
            xp, yp = take(x, 0), take(x, 1)
            if mode == 'stokes' and axis == 1 and xp.ndim == 2 \
                    and odt == jnp.float32:
                from .ops import pallas_kernels as _pk
                if _pk.enabled():
                    return _pk.stokes_detect(
                        jnp.real(xp), jnp.imag(xp),
                        jnp.real(yp), jnp.imag(yp))
            xx, yy = mag2(xp), mag2(yp)
            if mode == 'stokes_i':
                out = (xx + yy)[None]
            elif mode == 'stokes':
                xy = xp * jnp.conj(yp)
                out = jnp.stack([xx + yy, xx - yy,
                                 2 * jnp.real(xy), -2 * jnp.imag(xy)])
            elif mode == 'coherence':
                xy = jnp.conj(xp) * yp
                out = jnp.stack([xx, yy, jnp.real(xy), jnp.imag(xy)])
            elif mode == 'jones':
                out = jnp.stack([xx + 1j * yy, xp * jnp.conj(yp)])
            else:
                raise ValueError(mode)
            return jnp.moveaxis(out, 0, axis).astype(odt)
        return fn


class ReduceStage(Stage):
    """(reference: blocks/reduce.py:39-91; src/reduce.cu)"""

    batch_safe = True

    def __init__(self, axis, factor=None, op='sum'):
        self.specified_axis = axis
        self.specified_factor = factor
        self.op = op

    def transform_header(self, hdr):
        itensor = hdr['_tensor']
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        otensor['dtype'] = 'f32'
        if itensor['dtype'] in ('cf32', 'cf64') and \
                not self.op.startswith('pwr'):
            otensor['dtype'] = 'cf32'
        if 'labels' in itensor and isinstance(self.specified_axis, str):
            self.axis = itensor['labels'].index(self.specified_axis)
        else:
            self.axis = self.specified_axis
        self.frame_axis = itensor['shape'].index(-1)
        self.factor = self.specified_factor
        if self.axis == self.frame_axis:
            if self.factor is None:
                raise ValueError(
                    "Reduce factor must be specified for frame axis")
            self.nframe_ratio = (1, self.factor)
        else:
            if self.factor is None:
                self.factor = otensor['shape'][self.axis]
            elif otensor['shape'][self.axis] % self.factor != 0:
                raise ValueError("Reduce factor does not divide axis length")
            otensor['shape'][self.axis] //= self.factor
        otensor['scales'][self.axis][1] *= self.factor
        self.otype = DataType(otensor['dtype'])
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        from .ops.reduce import _reduce_jax
        pre = _complexify_fn(in_meta)
        axis, factor, op = self.axis, self.factor, self.op
        tgt = self.otype.as_jax_dtype()

        def fn(x):
            x = pre(x)
            y = _reduce_jax(x, axis, factor, op)
            if jnp.issubdtype(y.dtype, jnp.complexfloating) and \
                    not jnp.issubdtype(jnp.dtype(tgt), jnp.complexfloating):
                y = jnp.real(y)
            return y.astype(tgt)
        return fn


class FftShiftStage(Stage):
    """(reference: blocks/fftshift.py:37-81)"""

    batch_safe = True

    def __init__(self, axes, inverse=False):
        if not isinstance(axes, (list, tuple)):
            axes = [axes]
        self.specified_axes = axes
        self.inverse = inverse

    def transform_header(self, hdr):
        itensor = hdr['_tensor']
        self.axes = [_resolve_axis(itensor, ax)
                     for ax in self.specified_axes]
        frame_axis = itensor['shape'].index(-1)
        if frame_axis in self.axes:
            raise KeyError("Cannot fftshift the frame axis")
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        if 'scales' in itensor:
            for ax in self.axes:
                sgn = +1 if self.inverse else -1
                step = otensor['scales'][ax][1]
                otensor['scales'][ax][0] += \
                    sgn * (otensor['shape'][ax] // 2) * step
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        axes, inverse = list(self.axes), self.inverse

        def fn(x):
            return (jnp.fft.ifftshift if inverse
                    else jnp.fft.fftshift)(x, axes=axes)
        return fn


class ReverseStage(Stage):
    """(reference: blocks/reverse.py:36-75)"""

    batch_safe = True

    def __init__(self, axes):
        if not isinstance(axes, (list, tuple)):
            axes = [axes]
        self.specified_axes = axes

    def transform_header(self, hdr):
        itensor = hdr['_tensor']
        self.axes = [_resolve_axis(itensor, ax)
                     for ax in self.specified_axes]
        frame_axis = itensor['shape'].index(-1)
        if frame_axis in self.axes:
            raise KeyError("Cannot reverse the frame axis")
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        if 'scales' in itensor:
            for ax in self.axes:
                step = otensor['scales'][ax][1]
                otensor['scales'][ax][0] += otensor['shape'][ax] * step
                otensor['scales'][ax][1] = -step
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        axes = list(self.axes)

        def fn(x):
            y = x
            for ax in axes:
                y = jnp.roll(jnp.flip(y, axis=ax), 1, axis=ax)
            return y
        return fn


class TransposeStage(Stage):
    """(reference: blocks/transpose.py:41-83)"""

    batch_safe = True

    def __init__(self, axes):
        self.specified_axes = axes

    def transform_header(self, hdr):
        itensor = hdr['_tensor']
        if 'labels' in itensor:
            self.axes = [_resolve_axis(itensor, ax)
                         for ax in self.specified_axes]
        else:
            self.axes = list(self.specified_axes)
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        for item in ('shape', 'labels', 'scales', 'units'):
            if item in itensor:
                otensor[item] = [itensor[item][ax] for ax in self.axes]
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        axes = list(self.axes)
        reim = in_meta.get('reim', False)

        def fn(x):
            a = axes + [len(axes)] if reim and x.ndim == len(axes) + 1 \
                else axes
            return jnp.transpose(x, a)
        return fn


class ScrunchStage(Stage):
    """(reference: blocks/scrunch.py:38-66)"""

    batch_safe = True

    def __init__(self, factor):
        self.factor = factor
        self.nframe_ratio = (1, factor)

    def transform_header(self, hdr):
        ohdr = deepcopy(hdr)
        t = ohdr['_tensor']
        self.taxis = t['shape'].index(-1)
        t['scales'][self.taxis][1] *= self.factor
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        f, taxis = self.factor, self.taxis

        def fn(x):
            nf = x.shape[taxis] // f
            shp = x.shape[:taxis] + (nf, f) + x.shape[taxis + 1:]
            acc = x.dtype if jnp.issubdtype(x.dtype, jnp.inexact) \
                else jnp.float32
            return jnp.mean(x.reshape(shp), axis=taxis + 1,
                            dtype=acc).astype(x.dtype)
        return fn


class BeamformStage(Stage):
    """Coherent beamform: contract the station(/pol) axes of the
    voltage stream against a fixed weight set through the quantized
    beamformer engine (:class:`bifrost_tpu.ops.beamform.Beamformer` —
    candidates raced + accuracy-gated per the declared ``accuracy``
    class; ``BF_BEAM_IMPL`` forces one).

    Input tensor: ``['time', 'freq', 'station']`` or
    ``['time', 'freq', 'station', 'pol']``, dtype ci8 (int planes ride
    the MXU int8 path directly) or complex float.  Weight shapes select
    the output form (see the engine docstring):

    - ``(B, S)`` on pol-less input, or ``(B, S*P)`` (pol folded into
      the contraction) -> output ``['time', 'freq', 'beam']``;
    - ``(B, S)`` / ``(P, B, S)`` with a pol axis -> per-pol beams,
      output ``['time', 'freq', 'pol', 'beam']`` (the dual-pol form
      the fused beamform->Stokes-detect->integrate substitution
      recognizes, :func:`match_beamformer`);
    - ``(F, P, B, S)``: a weight set per channel (a tied-array beam is
      a delay, so a phase per channel), P one or the stream's, F the
      stream's channels: per-pol beams as above.  The three forms
      above keep their meaning: one set for every channel.

    Time-concat equivariant (``batch_safe``): macro-gulp block mode
    and the mesh frame-local shard_map plan both apply unchanged.
    """

    batch_safe = True

    def __init__(self, weights, accuracy='f32', impl=None):
        from .ops.beamform import Beamformer
        self.engine = Beamformer(weights, accuracy=accuracy, impl=impl)
        self.accuracy = self.engine.accuracy

    def transform_header(self, hdr):
        itensor = hdr['_tensor']
        labels = itensor.get('labels')
        if not labels or labels[:2] != ['time', 'freq']:
            raise ValueError(
                "beamform requires ['time', 'freq', ...] input labels, "
                "got %r" % (labels,))
        itype = DataType(itensor['dtype'])
        if not itype.is_complex:
            raise TypeError('beamform requires complex voltages, got '
                            '%s' % itensor['dtype'])
        shape = itensor['shape']
        eng = self.engine
        if eng.nfreq_w is not None and eng.nfreq_w != shape[1]:
            raise ValueError(
                'weights are for %d frequency channels but the stream '
                'has %d' % (eng.nfreq_w, shape[1]))
        if labels[2:] == ['station', 'pol']:
            s, p = shape[2], shape[3]
            if eng.nfreq_w is None and eng.npol_w == 1 and \
                    eng.nstand == s * p:
                self.mode = 'fold'
            elif eng.nstand == s and eng.npol_w in (1, p):
                self.mode = 'perpol'
            else:
                raise ValueError(
                    'weights (%d pol sets, %d inputs) match neither '
                    'per-pol station count %d nor folded %d'
                    % (eng.npol_w, eng.nstand, s, s * p))
            self.npol = p
        elif labels[2:] == ['station']:
            if eng.npol_w != 1 or eng.nstand != shape[2]:
                raise ValueError(
                    'weights expect %d inputs but the stream has %d '
                    'stations' % (eng.nstand, shape[2]))
            self.mode = 'nopol'
            self.npol = 1
        else:
            raise ValueError(
                "beamform requires trailing ['station'[, 'pol']] "
                "axes, got %r" % (labels[2:],))
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        otensor['dtype'] = 'cf32'
        for key, fill in (('shape', eng.nbeam), ('labels', 'beam'),
                          ('scales', [0, 1]), ('units', None)):
            if key not in otensor:
                continue
            vals = otensor[key]
            if self.mode == 'perpol':
                # ['time', 'freq', 'pol', 'beam']: the pol entry moves
                # up from position 3
                vals = [deepcopy(vals[0]), deepcopy(vals[1]),
                        deepcopy(vals[3]), deepcopy(fill)]
            else:
                vals = [deepcopy(vals[0]), deepcopy(vals[1]),
                        deepcopy(fill)]
            otensor[key] = vals
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        reim = in_meta.get('reim', False)
        mode = self.mode
        engine = self.engine

        def fn(x):
            if reim and not jnp.issubdtype(x.dtype,
                                           jnp.complexfloating):
                re, im = x[..., 0], x[..., 1]
            else:
                re, im = jnp.real(x), jnp.imag(x)
            if mode == 'nopol':
                re, im = re[:, :, None, :], im[:, :, None, :]
            elif mode == 'fold':
                shp = (re.shape[0], re.shape[1], 1, -1)
                re, im = re.reshape(shp), im.reshape(shp)
            else:
                # (T, F, S, P) -> canonical (T, F, P, S)
                re = jnp.swapaxes(re, 2, 3)
                im = jnp.swapaxes(im, 2, 3)
            y = engine(re, im)
            return y if mode == 'perpol' else y[:, :, 0, :]
        return fn


class QuantizeStage(Stage):
    """Requantize float data to a narrower (possibly complex-int)
    dtype INSIDE a fused chain (the device math of
    blocks.quantize.QuantizeBlock as a stage).

    The FX-correlator use: the channelizer's cf32 output requantizes
    to ci8 between the F and X steps, so inside a fused segment the
    float spectra live only in registers/VMEM — no f32 voltage array
    ever lands in HBM — and the X-engine consumes int8 planes on its
    exact int32 path.
    """

    batch_safe = True

    def __init__(self, dtype, scale=1.):
        self.dtype = DataType(dtype)
        self.scale = scale

    def transform_header(self, hdr):
        ohdr = deepcopy(hdr)
        ohdr['_tensor']['dtype'] = str(self.dtype)
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        from .ops.quantize import _clip_limits
        pre = _complexify_fn(in_meta)
        dt, scale = self.dtype, self.scale
        lo, hi = _clip_limits(dt)

        def fn(x):
            y = pre(x) * scale
            if dt.kind == 'ci':
                re = jnp.clip(jnp.round(jnp.real(y)), lo, hi)
                im = jnp.clip(jnp.round(jnp.imag(y)), lo, hi)
                comp = jnp.int8 if dt.nbits <= 8 else (
                    jnp.int16 if dt.nbits == 16 else jnp.int32)
                return jnp.stack([re, im], axis=-1).astype(comp)
            if lo is not None:
                y = jnp.clip(jnp.round(jnp.real(y) if
                                       jnp.iscomplexobj(y) else y),
                             lo, hi)
            return y.astype(dt.as_jax_dtype())
        return fn


class CorrelateStage(Stage):
    """FX-correlator X step as a fusable stage: one visibility matrix
    per ``nframe_per_vis`` input frames, computed by the raced
    X-engine (:class:`bifrost_tpu.ops.linalg.XEngine` — candidates
    raced + accuracy-gated per the declared ``accuracy`` class;
    ``BF_XCORR_IMPL`` forces one).

    Input tensor: ``['time', 'freq', 'station', 'pol']``, dtype ci8
    (int planes ride the exact int32 MXU path directly) or complex
    float.  Output: ``['time', 'freq', 'station_i', 'pol_i',
    'station_j', 'pol_j']`` cf32, the full visibility matrix
    (``matrix_fill_mode='full'``), one output frame per integration.

    Unlike the stateful :class:`bifrost_tpu.blocks.correlate
    .CorrelateBlock` (which integrates ACROSS gulps), the stage
    integrates whole groups WITHIN each gulp — ``nframe_per_vis`` must
    divide the gulp — which is exactly what makes it time-concat
    equivariant (``batch_safe``): macro-gulp block mode and segment
    fusion (capture -> F -> X -> accumulate as ONE compiled program)
    both apply unchanged.
    """

    batch_safe = True

    def __init__(self, nframe_per_vis, accuracy='f32', impl=None):
        from .ops.linalg import XEngine
        self.nframe_per_vis = int(nframe_per_vis)
        if self.nframe_per_vis < 1:
            raise ValueError('nframe_per_vis must be >= 1')
        self.nframe_ratio = (1, self.nframe_per_vis)
        self.engine = XEngine(accuracy=accuracy, impl=impl)
        self.accuracy = self.engine.accuracy

    def transform_header(self, hdr):
        itensor = hdr['_tensor']
        labels = itensor.get('labels')
        if labels != ['time', 'freq', 'station', 'pol']:
            raise ValueError(
                "correlate requires ['time', 'freq', 'station', "
                "'pol'] input labels, got %r" % (labels,))
        itype = DataType(itensor['dtype'])
        if not itype.is_complex:
            raise TypeError('correlate requires complex voltages, '
                            'got %s' % itensor['dtype'])
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        otensor['dtype'] = 'cf32'
        for key in ('shape', 'labels', 'scales', 'units'):
            if key not in itensor:
                continue
            tv, fv, sv, pv = (deepcopy(v) for v in itensor[key])
            otensor[key] = [tv, fv, sv, pv,
                            deepcopy(sv) if key != 'labels'
                            else sv + '_j',
                            deepcopy(pv) if key != 'labels'
                            else pv + '_j']
        if 'labels' in otensor:
            otensor['labels'][2] += '_i'
            otensor['labels'][3] += '_i'
        if 'scales' in otensor:
            otensor['scales'][0][1] *= self.nframe_per_vis
        ohdr['matrix_fill_mode'] = 'full'
        return ohdr

    def build(self, in_meta):
        import jax
        import jax.numpy as jnp
        reim = in_meta.get('reim', False)
        r = self.nframe_per_vis
        t = in_meta['shape'][0]
        if t % r:
            raise ValueError(
                'CorrelateStage: gulp nframe %d not divisible by '
                'nframe_per_vis %d' % (t, r))
        engine = self.engine

        def fn(x):
            if reim and not jnp.issubdtype(x.dtype,
                                           jnp.complexfloating):
                re, im = x[..., 0], x[..., 1]
            else:
                re, im = jnp.real(x), jnp.imag(x)
            nt, f, s, p = re.shape
            re = re.reshape(nt // r, r, f, s * p)
            im = im.reshape(nt // r, r, f, s * p)
            # one engine call per integration group; vmap traces the
            # engine at the (r, f, n) per-group shape, so the winner
            # probed by an eager prewarm at that shape applies — and
            # the SAME program runs at every macro factor K, keeping
            # K>1 byte-identical to K=1
            vis = jax.vmap(engine)(re, im)          # (g, f, n, n)
            return vis.reshape(nt // r, f, s, p, s, p) \
                .astype(jnp.complex64)
        return fn


class AccumulateStage(ReduceStage):
    """Frame-axis integration as a fusable stage — the in-chain twin
    of :class:`bifrost_tpu.blocks.accumulate.AccumulateBlock` (which
    carries state across gulps): sums whole groups of ``nframe``
    frames within a gulp, so it composes into fused segments and
    macro-gulp batches.  The FX chain uses it to integrate visibility
    matrices after the X step."""

    def __init__(self, nframe, op='sum'):
        super(AccumulateStage, self).__init__('time', factor=int(nframe),
                                              op=op)


class MapStage(Stage):
    """User-defined elementwise stage via a bf.map expression operating on
    'a' (input) and 'b' (output); fusable with neighbors."""

    batch_safe = True

    def __init__(self, func_string, dtype=None, scalars=None):
        self.func_string = func_string
        self.dtype = dtype
        self.scalars = dict(scalars or {})

    def transform_header(self, hdr):
        ohdr = deepcopy(hdr)
        if self.dtype is not None:
            ohdr['_tensor']['dtype'] = str(DataType(self.dtype))
        self.otype = DataType(ohdr['_tensor']['dtype'])
        return ohdr

    def build(self, in_meta):
        from .ops.map import _Eval
        from .ops.map_lang import compile_map
        pre = _complexify_fn(in_meta)
        body = compile_map(self.func_string, ['a', 'b'] +
                           list(self.scalars))
        otype = self.otype
        idt = in_meta['dtype']
        # a_type reflects the array's logical dtype after complexification
        atype = idt.as_floating_point() if idt.kind == 'ci' else idt
        scalars = dict(self.scalars)
        lshape = tuple(in_meta['shape'][:len(in_meta['shape']) -
                                        (1 if in_meta.get('reim') else 0)])

        def fn(x):
            import jax.numpy as jnp
            x = pre(x)
            ev = _Eval(lshape, None, {},
                       scalars, {'a': atype, 'b': otype}, {})
            ev.arrays = {'a': x}
            ev.out = {'b': jnp.zeros(x.shape, otype.as_jax_dtype())}
            ev.run(body)
            return ev.out['b']
        return fn


def chain_overlap_nframe(stages):
    """Input-frame lookahead a stage chain needs, or None.

    Walks the chain BACK from the sink, converting each downstream
    halo through the stage's frame ratio and adding the stage's own
    declared ``overlap_nframe``.  Returns None when a downstream halo
    does not convert to a whole input-frame count — the caller must
    then treat the chain as carry-unsafe (fall back to the plain
    per-gulp overlap boundary)."""
    halo = 0
    for stage in reversed(stages):
        num, den = getattr(stage, 'nframe_ratio', (1, 1))
        if halo:
            if (halo * den) % num:
                return None
            halo = halo * den // num
        halo += int(getattr(stage, 'overlap_nframe', 0) or 0)
    return halo


class FdmtStage(Stage):
    """Incoherent dedispersion (FDMT) as a fusable stage — the pure
    core of :class:`bifrost_tpu.blocks.fdmt.FdmtBlock` with a STATIC
    ``max_delay``, so the lookahead requirement is known at chain
    construction (``overlap_nframe``) before any header flows.

    Input tensor ``[..., 'freq', 'time']`` (time is the frame axis and
    rides last, the ring's lane-contiguous layout); output replaces
    the freq axis with ``max_delay`` dispersion trials.  Output frame
    t is a fixed-order sum over input frames [t, t + max_delay]
    (positive delays only — the lookahead convention the ring overlap
    machinery implements), so committed frames are byte-identical
    whatever span they were computed in: time-concat equivariance
    holds for the non-ghost frames, which is what makes the chain
    macro-gulp 'block' eligible and halo-carriable inside a compiled
    segment.  The per-gulp core is the raced engine
    (:class:`bifrost_tpu.ops.fdmt.Fdmt`; ``BF_FDMT_IMPL`` forces one).
    """

    batch_safe = True

    def __init__(self, max_delay, exponent=-2.0):
        from .ops.fdmt import Fdmt
        self.max_delay = int(max_delay)
        if self.max_delay < 1:
            raise ValueError('max_delay must be >= 1')
        self.exponent = exponent
        self.overlap_nframe = self.max_delay
        self.engine = Fdmt()

    def transform_header(self, hdr):
        from .ops.fdmt import KDM
        itensor = hdr['_tensor']
        labels = itensor.get('labels')
        if not labels or labels[-1] != 'time' or labels[-2] != 'freq':
            raise KeyError("fdmt requires [..., 'freq', 'time'] input "
                           "labels, got %r" % (labels,))
        nchan = itensor['shape'][-2]
        f0_, df_ = itensor['scales'][-2]
        dt_ = itensor['scales'][-1][1]
        units = itensor.get('units')
        funit = units[-2] if units else 'MHz'
        tunit = units[-1] if units else 's'
        f0 = convert_units(f0_, funit, 'MHz')
        df = convert_units(df_, funit, 'MHz')
        dt = convert_units(dt_, tunit, 's')
        fac = f0 ** -2 - (f0 + nchan * df) ** -2
        max_dm = self.max_delay * dt / (KDM * abs(fac))
        self.dm_step = max_dm / self.max_delay
        self.engine.init(nchan, self.max_delay, f0, df, self.exponent,
                         space='tpu')
        ohdr = deepcopy(hdr)
        refdm = convert_units(hdr['refdm'], hdr['refdm_units'],
                              'pc cm^-3') if 'refdm' in hdr else 0.
        otensor = ohdr['_tensor']
        otensor['dtype'] = 'f32'
        otensor['shape'][-2] = self.max_delay
        otensor['labels'][-2] = 'dispersion'
        if 'scales' in otensor:
            otensor['scales'][-2] = [refdm, self.dm_step]
        if units:
            otensor['units'][-2] = 'pc cm^-3'
        ohdr['max_dm'] = max_dm
        ohdr['max_dm_units'] = 'pc cm^-3'
        ohdr['cfreq'] = f0_ + 0.5 * (nchan - 1) * df_
        ohdr['cfreq_units'] = funit
        ohdr['bw'] = nchan * df_
        ohdr['bw_units'] = funit
        return ohdr

    def build(self, in_meta):
        import jax
        import jax.numpy as jnp
        shape = in_meta['shape']
        # probe/lock the measured core at the ACTUAL (nchan, T) the
        # chain will trace — no jit here, the enclosing chain jit owns
        # compilation
        core = self.engine._pick_core(False, shape=(int(shape[-2]),
                                                    int(shape[-1])))

        def fn(x):
            xs = x.astype(jnp.float32) if not jnp.issubdtype(
                x.dtype, jnp.floating) else x
            if xs.ndim == 2:
                return core(xs)
            flat = xs.reshape((-1,) + xs.shape[-2:])
            out = jax.vmap(core)(flat)
            return out.reshape(xs.shape[:-2] + out.shape[-2:])
        return fn


class MatchedFilterStage(Stage):
    """Boxcar matched filter along the frame (time) axis: output frame
    t = sum of input frames [t, t + ntap - 1], summed in a FIXED order
    (ntap shifted adds — never a cumsum difference, whose float
    cancellation would break byte-identity across span positions).
    Declares ``ntap - 1`` frames of lookahead; the trailing invalid
    frames are recomputed by the next span exactly like the FDMT
    ghost region, so the stage composes into halo-carried segments."""

    batch_safe = True

    def __init__(self, ntap):
        self.ntap = int(ntap)
        if self.ntap < 1:
            raise ValueError('ntap must be >= 1')
        self.overlap_nframe = self.ntap - 1

    def transform_header(self, hdr):
        ohdr = deepcopy(hdr)
        t = ohdr['_tensor']
        self.taxis = t['shape'].index(-1)
        self.otype = DataType(t['dtype']).as_floating_point()
        if self.otype.is_complex:
            raise TypeError('matched filter requires real input, got '
                            '%s' % t['dtype'])
        t['dtype'] = str(self.otype)
        return ohdr

    def build(self, in_meta):
        import jax.numpy as jnp
        from jax import lax
        W, taxis = self.ntap, self.taxis
        odt = self.otype.as_jax_dtype()

        def fn(x):
            x = x.astype(odt)
            if W == 1:
                return x
            T = x.shape[taxis]
            pads = [(0, 0)] * x.ndim
            pads[taxis] = (0, W - 1)
            xp = jnp.pad(x, pads)
            y = lax.slice_in_dim(xp, 0, T, axis=taxis)
            for i in range(1, W):
                y = y + lax.slice_in_dim(xp, i, i + T, axis=taxis)
            return y
        return fn


class ThresholdStage(Stage):
    """Peak detect: zero every sample below ``threshold`` (elementwise
    and frame-local, so trivially batch-safe).  The candidate sink
    counts the surviving nonzero samples — keeping the zeroed shape
    instead of emitting a ragged candidate list is what keeps the
    whole search chain static-shaped and segment-fusable."""

    batch_safe = True

    def __init__(self, threshold):
        self.threshold = float(threshold)

    def transform_header(self, hdr):
        return deepcopy(hdr)

    def build(self, in_meta):
        import jax.numpy as jnp
        thr = self.threshold

        def fn(x):
            return jnp.where(x >= thr, x, jnp.zeros((), x.dtype))
        return fn


class CoherentDedispersionStage(Stage):
    """Coherent dedispersion by overlap-save (ops.dedisp), over the last
    axis of complex voltages whose axes include 'freq': a frame is a
    block of ``overlap + nwin * (nfft - overlap)`` samples of every
    channel, its first ``overlap`` history only (GUPPI's coherent mode
    writes every RAW block with its predecessor's tail in front), and
    becomes the ``nwin * (nfft - overlap)`` new samples, dedispersed at
    ``dm`` (pc cm^-3), from its sample ``overlap // 2`` on: the chirp's
    response reaches both ways in time, so half the overlap is dropped
    at each end of a window (ops.dedisp), and the output's time scale
    starts there.  Each frame stands alone, so the stage is frame-local
    (no lookahead, batch-safe).

    The channels' centres and width come from the header's 'freq'
    scale ([centre of channel 0, channel width], in its units, MHz by
    default); the chirp (ops.dedisp.chirp) is made once a sequence on
    the host in float64 and is an argument of the gulp's program
    (:meth:`gulp_operands`: two float32 planes, (nchan, nfft), on the
    device once a sequence, in the order the transforms hold the
    spectrum in, ops.dedisp.in_transform_order).  Output
    complex64.  Fused with DetectStage('stokes') and a FoldStage on
    ci8 input, the chain runs as one program with no spectrum in HBM
    (:func:`match_coherent_fold`)."""

    batch_safe = True
    n_gulp_operands = 2

    def __init__(self, dm, nfft, overlap):
        self.dm = float(dm)
        self.nfft = int(nfft)
        self.overlap = int(overlap)
        if not 0 < self.overlap < self.nfft:
            raise ValueError('overlap must lie in (0, nfft)')

    def transform_header(self, hdr):
        from .ops.dedisp import chirp, head, window_count
        itensor = hdr['_tensor']
        itype = DataType(itensor['dtype'])
        if not itype.is_complex:
            raise TypeError('coherent dedispersion requires complex '
                            'voltages, got %s' % itensor['dtype'])
        labels = itensor.get('labels') or []
        if 'freq' not in labels:
            raise KeyError("coherent dedispersion requires a 'freq' axis, "
                           "got labels %r" % (labels,))
        shape = itensor['shape']
        if shape[-1] == -1:
            raise KeyError('the last axis is the frame axis; reshape the '
                           'stream first (views.split_axis)')
        self.chan_axis = labels.index('freq')
        self.nout = window_count(int(shape[-1]), self.nfft, self.overlap) \
            * (self.nfft - self.overlap)
        nchan = int(shape[self.chan_axis])
        f0, df = itensor['scales'][self.chan_axis]
        units = itensor.get('units')
        funit = (units[self.chan_axis] if units else None) or 'MHz'
        f0 = convert_units(f0, funit, 'MHz')
        df = convert_units(df, funit, 'MHz')
        self.chirp = chirp(self.dm, f0 + df * np.arange(nchan), df,
                           self.nfft)
        self._operands = None
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        otensor['dtype'] = 'cf32'
        otensor['shape'][-1] = self.nout
        if 'scales' in otensor:
            # the first output is the block's sample head(overlap)
            t0, dt = otensor['scales'][-1]
            otensor['scales'][-1] = [t0 + head(self.overlap) * dt, dt]
        # the new samples a frame makes, every channel and polarisation
        self.frame_samples = int(np.prod([n for n in otensor['shape']
                                          if n != -1]))
        return ohdr

    def gulp_operands(self, frame_offset, nframe):
        if self._operands is None:
            import jax.numpy as jnp
            from .ops.dedisp import in_transform_order
            h = in_transform_order(self.chirp, self.nfft)
            self._operands = (jnp.asarray(h.real.astype(np.float32)),
                              jnp.asarray(h.imag.astype(np.float32)))
        return self._operands

    def build(self, in_meta):
        from .ops.dedisp import dedisperse, transform_paths
        pre = _complexify_fn(in_meta)
        nfft, overlap, axis = self.nfft, self.overlap, self.chan_axis

        def fn(x, chirp_re, chirp_im):
            return dedisperse(pre(x), chirp_re, chirp_im, nfft, overlap,
                              axis)
        fn.impl_info = transform_paths(nfft)
        return fn


class FoldStage(Stage):
    """Fold the last axis of a real time series (detected voltages:
    Stokes I, Q, U, V) into ``nbin`` phase bins of a pulsar's period,
    ``period_num / period_den`` samples, by the integer rule of
    ops.fold: absolute sample n falls in bin floor(nbin * ((period_den
    * n) mod period_num) / period_num).  Sample j of frame f is n = f *
    nsamp + j + origin, the origin the header's time of the frame's
    first sample in samples (the last axis's scale, [t0, dt]: t0 / dt,
    0 without a scale), so that n counts from the stream's time zero
    (guppi-fold's header puts it at the first new sample of its RAW
    blocks, so the first dedispersed sample, half an overlap before it,
    is n = -4096).  Output: the frame's profiles, the last axis
    'phase' of ``nbin``, float32, to be summed over sub-integrations
    downstream (``accumulate``).

    Each gulp's start residues are computed on the host, exactly
    (:meth:`gulp_operands`, in a ``fold.phase`` span), and are an
    argument of the gulp's program, so gulps at every phase run one
    compiled program.  A frame's profile depends on where the frame
    lies, so the stage is not time-concat equivariant."""

    n_gulp_operands = 1

    def __init__(self, nbin, period_num, period_den):
        self.nbin = int(nbin)
        self.period_num = int(period_num)
        self.period_den = int(period_den)

    def transform_header(self, hdr):
        from .ops.fold import FoldRule
        itensor = hdr['_tensor']
        itype = DataType(itensor['dtype'])
        if itype.is_complex:
            raise TypeError('fold requires a detected (real) series, '
                            'got %s' % itensor['dtype'])
        shape = itensor['shape']
        if shape.index(-1) != 0 or shape[-1] == -1:
            raise KeyError('fold requires the frame axis first and the '
                           'samples folded last')
        self.rule = FoldRule(shape[-1], self.nbin, self.period_num,
                             self.period_den)
        t0, dt = (itensor.get('scales') or [[0, 0]])[-1]
        self.origin = int(round(t0 / dt)) if dt else 0
        ohdr = deepcopy(hdr)
        otensor = ohdr['_tensor']
        otensor['dtype'] = 'f32'
        otensor['shape'][-1] = self.nbin
        if 'labels' in otensor:
            otensor['labels'][-1] = 'phase'
        if 'scales' in otensor:
            otensor['scales'][-1] = [0, 1. / self.nbin]
        if 'units' in otensor:
            otensor['units'][-1] = None
        return ohdr

    def gulp_operands(self, frame_offset, nframe):
        from .ops.fold import residues
        from .telemetry import spans
        with spans.timed('fold.phase', 'compute', frame=int(frame_offset)):
            return (residues(frame_offset, nframe, self.rule.nsamp,
                             self.period_num, self.period_den,
                             self.origin),)

    def build(self, in_meta):
        import jax
        import jax.numpy as jnp
        from .ops.fold import fold
        rule = self.rule

        def fn(x, starts):
            # a loop over the frames: the kernel's cut is not batched
            return jax.lax.map(lambda a: fold(a[0], a[1], rule),
                               (x.astype(jnp.float32), starts))
        return fn


def match_beamformer(stages, headers, shape, dtype):
    """Recognize the quantized beamform-and-detect pattern —
    BeamformStage (per-pol, dual pol; one weight set or a set per
    channel) -> DetectStage('stokes' | 'stokes_i', pol) -> ReduceStage
    over the frame axis [-> QuantizeStage to an 8-bit real type], on
    ci8 input — and return the fused Pallas kernel
    (ops.pallas_kernels.beamform_detect) as a callable plan when the
    engine's accuracy class and the backend admit it, else None.

    The fused kernel takes a channel and a tile of time a program:
    the gulp's words are split in VMEM, both polarizations are
    beamformed (int8 MXU dots against the channel's own weights, int32
    accumulation), the sums are squared in float32, R frames are
    integrated and, with the QuantizeStage, scaled, rounded and
    clipped — beam voltages never round-trip HBM (the Tensor-Core
    Beamformer's fused pipeline, arXiv:2505.03269), which at a
    deployment's shape is the difference between 57 MB a gulp and
    14.5 GB.  The plan starts from the gulp's int16 words
    (``plan.words``) and takes the weights as an argument of the
    program (``plan.bound``).  Substitution requires the 'int8'
    accuracy class (the kernel's weights are quantized by
    construction) — see ops.beamform.fused_mode for the BF_BEAM_FUSED
    override; a forced ``pallas_bf16`` candidate (BF_BEAM_IMPL) runs
    the same kernel with one bfloat16 pass of the float weights
    (lossy: the control of an int8 deployment), any other forced
    candidate runs unfused.
    """
    if len(stages) not in (3, 4):
        return None
    b, d, r = stages[:3]
    q = stages[3] if len(stages) == 4 else None
    if not (isinstance(b, BeamformStage) and isinstance(d, DetectStage)
            and isinstance(r, ReduceStage)
            and (q is None or isinstance(q, QuantizeStage))):
        return None
    if headers[0]['_tensor']['dtype'] != 'ci8':
        return None
    if str(dtype) != 'int8' or len(shape) != 5:
        return None
    ntime, nfreq, nstand, npol, two = shape
    if npol != 2 or two != 2:
        return None
    if getattr(b, 'mode', None) != 'perpol':
        return None
    if d.mode not in ('stokes', 'stokes_i') or d.axis_index != 2 \
            or d.npol != 2:
        return None
    if r.op != 'sum' or r.axis != r.frame_axis or not r.factor:
        return None
    if ntime % r.factor:
        return None
    quantize = None
    if q is not None:
        if q.dtype.kind not in ('i', 'u') or q.dtype.nbits != 8:
            return None
        from .ops.quantize import _clip_limits
        quantize = _clip_limits(q.dtype) + (q.dtype.as_jax_dtype(),)
    from .ops import beamform as _beam
    from .ops import pallas_kernels as _pk
    mode = _beam.fused_mode()
    if mode == 'off':
        return None
    eng = b.engine
    if eng._force not in (None, 'pallas', 'pallas_bf16'):
        return None
    bf16 = eng._force == 'pallas_bf16'
    if mode != 'force' and eng._force is None and \
            _beam.beam_class_rtol(eng.accuracy) < \
            _beam.BEAM_CLASSES['int8']:
        return None
    how = dict(stokes=d.mode, quantize=quantize, bf16=bf16,
               scale=1.0 if q is None else float(q.scale))
    if not _beam.fused_usable(eng, ntime, nfreq, r.factor, **how):
        return None
    factor = r.factor
    # one function an engine and form, so that a later sequence of
    # the same shapes finds its program compiled
    key = (nfreq, factor, _beam.how_key(how))
    run = eng._fused_runs.get(key)
    if run is None:
        def run(x, *operands):
            return _beam.fused_detect(eng, x, factor, nfreq=nfreq,
                                      operands=operands or None, **how)
        eng._fused_runs[key] = run

    def bound():
        # (fn(x, *operands), operands): the weights as arguments of
        # the caller's program; x the pairs or the gulp's int16 words
        return run, _beam.fused_operands(eng, bf16)
    return SpectrometerPlan(run, {
        'impl': 'pallas-beamform-detect',
        'stokes': d.mode,
        'rfactor': factor,
        'time_tile': _pk.beam_time_tile(
            ntime, factor, 4 if quantize is None else 1),
        'nbeam': eng.nbeam,
        'weights': 'one set' if eng.nfreq_w is None else 'per channel',
        'dot': 'bf16' if bf16 else 'int8',
        'quantize': None if q is None else str(q.dtype),
        'accuracy': eng.accuracy,
        'wscale': float(eng.wscale),
    }, words=run, bound=bound)


def walk_headers(stages, hdr):
    """Run ``hdr`` through every stage's transform_header; returns the
    full header list (input + one per stage output)."""
    headers = [hdr]
    for stage in stages:
        hdr = stage.transform_header(hdr)
        headers.append(hdr)
    return headers


def compose_stages(stages, headers, shape, dtype, substitute=True,
                   mesh=None):
    """Build the one-gulp device function for a stage chain.

    This is the SINGLE chain constructor: FusedBlock compiles exactly
    this function per gulp, and the driver entry (__graft_entry__)
    builds its flagship step through it too, so what the driver
    measures is what users run (VERDICT r3 item 6).

    Returns ``(fn, info)`` where info records the path fn executes
    ({'impl': 'pallas-spectrometer', ...} when the whole-chain kernel
    substitution applies and ``substitute`` is True, else
    {'impl': 'xla-fused'}).  ``mesh``: the mesh the chain runs
    across, ``shape`` then a shard's; the plans that take a gulp on one
    device alone do not match there.
    """
    import jax
    if substitute:
        # check the whole-chain substitutions first: when one matches,
        # the per-stage functions below would be built only to be
        # discarded
        plan = match_spectrometer(stages, headers, shape, dtype)
        if plan is None:
            plan = match_long_spectrometer(stages, headers, shape, dtype)
        if plan is None:
            plan = match_beamformer(stages, headers, shape, dtype)
        if plan is None:
            plan = match_coherent_fold(stages, headers, shape, dtype)
        if plan is None:
            plan = match_channeliser(stages, headers, shape, dtype, mesh)
        if plan is not None:
            return plan, plan.info
    fns = []
    info = {'impl': 'xla-fused'}
    cur = jax.ShapeDtypeStruct(tuple(shape), dtype)
    taxis = headers[0]['_tensor']['shape'].index(-1)
    for stage, ihdr in zip(stages, headers[:-1]):
        idt = DataType(ihdr['_tensor']['dtype'])
        meta = {'shape': list(cur.shape), 'dtype': idt,
                'reim': idt.kind == 'ci'}
        fn = stage.build(meta)
        fns.append(fn)
        if getattr(fn, 'impl_info', None):
            info['fft'] = fn.impl_info
        cur = jax.eval_shape(fn, cur, *stage.gulp_operands(
            0, cur.shape[taxis]))
    counts = [stage.n_gulp_operands for stage in stages]

    def composed(x, *operands):
        # ``operands``: every stage's gulp operands in the chain's order
        for fn, n in zip(fns, counts):
            x, operands = fn(x, *operands[:n]), operands[n:]
        return x
    return composed, info


def match_coherent_fold(stages, headers, shape, dtype):
    """Recognize a pulsar-timing backend's coherent mode --
    CoherentDedispersionStage -> DetectStage('stokes', pol) ->
    FoldStage on ci8 dual-pol input (time, freq, pol, fine_time) --
    and return ops.spectrometer.coherent_fold as a
    :class:`SpectrometerPlan`: one program a gulp that takes a few
    channels of a frame at a time through the forward transform, the
    chirp, the inverse and the detection, so that neither the spectra
    nor the dedispersed voltages reach HBM whole, then folds the
    Stokes of all channels at once (the record's 'fold' says which cut,
    ops.fold.cut_path).  Its transforms take
    the path their length selects (ops.fft.fft_path: three levels of
    DFT matrices past 16384 points, forward and inverse).  The plan
    starts from the gulp's int16 words or its pairs and takes the
    stages' gulp operands, the chirp's planes and the gulp's start
    residues, as arguments."""
    if len(stages) != 3:
        return None
    c, d, f = stages
    if not (isinstance(c, CoherentDedispersionStage)
            and isinstance(d, DetectStage) and isinstance(f, FoldStage)):
        return None
    if headers[0]['_tensor']['dtype'] != 'ci8' or str(dtype) != 'int8':
        return None
    if len(shape) != 5 or shape[2] != 2 or shape[-1] != 2 or \
            c.chan_axis != 1:
        return None
    if d.mode != 'stokes' or d.axis_index != 2 or d.npol != 2:
        return None
    if f.rule.nsamp != c.nout:
        return None
    from .ops.dedisp import transform_paths
    from .ops.fold import cut_path
    from .ops import spectrometer as spec
    path = transform_paths(c.nfft)
    if path['path'] == 'dftmm' or path['inverse']['path'] != path['path']:
        return None
    factors = tuple(path['factors']) if path['path'] == 'long' else None
    logical = tuple(int(n) for n in shape[:-1])
    nfft, overlap, rule = c.nfft, c.overlap, f.rule
    precision = path.get('precision', 'highest')

    def run(x, chirp_re, chirp_im, starts):
        return spec.coherent_fold(x, starts, chirp_re, chirp_im, logical,
                                  nfft, overlap, rule, factors=factors,
                                  precision=precision)
    return SpectrometerPlan(run, {
        'impl': 'coherent-fold',
        'fft': path,
        'dm': c.dm,
        'overlap': overlap,
        'nbin': f.nbin,
        'period': [f.period_num, f.period_den],
        'fold': cut_path(logical[1] * 4, rule),
    }, words=run)


class SpectrometerPlan(object):
    """Callable wrapper around the substituted fused kernel that also
    RECORDS its configuration, so the block that executes it can
    publish what actually ran (ProcLog ``<block>/impl``) instead of
    benchmarks re-deriving the decision (VERDICT r3 item 4)."""

    def __init__(self, fn, info, words=None, bound=None):
        self.fn = fn
        self.info = dict(info)
        #: the same plan as a function of the gulp's int16 words
        #: (devrep.ComplexWords.words), where the kernel can start
        #: from them (:func:`from_words`)
        self.words = words
        #: ``bound() -> (fn(x, *operands), operands)`` where the
        #: plan has operands that live on the device (a beamformer's
        #: weights) and belong among the program's arguments, not
        #: its constants (blocks/fused.py)
        self.bound = bound

    def __call__(self, x, *operands):
        return self.fn(x, *operands)


def from_words(composed, shape):
    """The one-gulp function ``composed`` (:func:`compose_stages`, for
    a ci8 gulp of ``shape``, (re, im) last) as a function of the
    gulp's int16 words, one a complex sample, as a device ring holds
    them (devrep.ComplexWords): the plan's own where its kernel starts
    from words, else the pairs are made first, inside the program."""
    fn = getattr(composed, 'words', None)
    if fn is not None:
        return fn
    from .words import pairs_of
    shape = tuple(shape)
    return lambda w, *operands: composed(pairs_of(w, shape), *operands)


def match_spectrometer(stages, headers, shape, dtype):
    """Recognize the Guppi spectrometer pattern — FftStage(c2c forward,
    no shift, last axis) -> DetectStage('stokes', pol) ->
    ReduceStage('freq', r, 'sum') on ci8 dual-pol input — and return
    the fused Pallas kernel (ops/spectrometer.py) as a callable
    :class:`SpectrometerPlan` when the active BF_SPEC_IMPL mode admits
    it, else None.  Under ``auto`` a kernel the backend refuses is
    reported (ops.mprobe.refused: one warning, an entry in the
    published impl record) and the XLA chain runs; under the forced
    ``pallas`` mode the refusal raises.

    This is the TPU equivalent of the reference wiring cuFFT load/store
    callbacks into the transform (reference: src/fft_kernels.cu
    CallbackData): the whole chain becomes one kernel with no HBM
    round-trips between steps.
    """
    import os
    if len(stages) != 3:
        return None
    f, d, r = stages
    if not (isinstance(f, FftStage) and isinstance(d, DetectStage)
            and isinstance(r, ReduceStage)):
        return None
    if headers[0]['_tensor']['dtype'] != 'ci8':
        return None
    if str(dtype) != 'int8' or len(shape) != 4:
        return None
    ntime, npol, nfft, two = shape
    if npol != 2 or two != 2 or nfft < 4 or (nfft & (nfft - 1)):
        return None
    if f.mode != 'c2c' or f.inverse or f.apply_fftshift \
            or f.axes != [2]:
        return None
    if d.mode != 'stokes' or d.axis_index != 1 or d.npol != 2:
        return None
    if r.op != 'sum' or r.axis != 2 or not r.factor:
        return None
    from .ops import spectrometer as spec
    try:
        n1, _ = spec._choose_split(nfft, r.factor)
    except ValueError:
        return None
    prec = spec.choose_precision(nfft, r.factor)
    if prec == 'off':
        return None
    # default tile 16: the 4096-pt kernel fits the ~16 MB scoped-VMEM
    # limit at 16 but not 32 (measured on chip)
    try:
        tile = int(os.environ.get('BF_SPEC_TILE', '16'))
    except ValueError:
        tile = 16
    if tile < 1:
        tile = 16
    trans = spec.resolve_transpose('auto', nfft, r.factor)
    # the EFFECTIVE tile after fused_spectrometer's shrink-to-divisor
    # (shape[0] is the frame count the kernel will actually see — the
    # per-shard count under a mesh)
    tile = min(tile, shape[0])
    while shape[0] % tile:
        tile -= 1
    # compile-probe the EXACT substitution configuration (VMEM limits
    # bind at the real tile, not the accuracy gate's small one)
    if not spec.kernel_usable(nfft, r.factor, tile, prec, trans):
        return None
    factor = r.factor

    def fn(x):
        # the pairs, or the gulp's words (fused_spectrometer)
        return spec.fused_spectrometer(x, nfft=nfft, rfactor=factor,
                                       time_tile=tile, precision=prec,
                                       transpose=trans)
    return SpectrometerPlan(fn, {
        'impl': 'pallas-spectrometer',
        'precision': prec or 'default',
        'tile': tile,
        'transpose': trans,
        'nfft': nfft,
        'rfactor': factor,
    }, words=fn)


def match_long_spectrometer(stages, headers, shape, dtype):
    """Recognize the Guppi spectrometer's production form —
    FftStage(c2c forward, no shift, last axis) -> DetectStage('stokes')
    on ci8 dual-pol input (..., pol, fine_time) whose transform is
    past two levels (ops.fft.fft_path says 'long': chosen from the
    length alone, on every backend) — and return
    ops.spectrometer.long_spectrometer as a :class:`SpectrometerPlan`:
    the three-level transform with the detection inside its loop over
    chunks, so the complex spectra never reach HBM.  Any other chain
    with such a transform runs it through FftStage (the same
    long_fft, its spectra written whole)."""
    if len(stages) != 2:
        return None
    f, d = stages
    if not (isinstance(f, FftStage) and isinstance(d, DetectStage)):
        return None
    if headers[0]['_tensor']['dtype'] != 'ci8' or str(dtype) != 'int8':
        return None
    nd = len(shape) - 1                 # logical rank: (re, im) is last
    if nd < 3 or shape[-1] != 2 or shape[-3] != 2:
        return None
    if f.mode != 'c2c' or f.inverse or f.apply_fftshift \
            or f.axes != [nd - 1]:
        return None
    if d.mode != 'stokes' or d.axis_index != nd - 2 or d.npol != 2:
        return None
    from .ops.fft import fft_path
    from .ops import spectrometer as spec
    path = fft_path(shape[:-1], f.axes)
    if path['path'] != 'long':
        return None
    factors, prec = tuple(path['factors']), path['precision']

    def fn(x):
        return spec.long_spectrometer(x, factors, precision=prec)

    def words(w):
        # the words on one axis: (rows / 2, 4, nfft) comes back
        return fn(w).reshape(tuple(shape[:-3]) + (4, shape[-2]))
    return SpectrometerPlan(fn, {
        'impl': 'long-spectrometer',
        'fft': dict(path, nfft=[int(shape[-2])]),
    }, words=words)


def opens_with_short_transform(stages, hdr):
    """True where the chain's first stage is a transform of a ci8
    gulp's last axis (``hdr``, the chain's input header, after the
    stages' transform_header) whose length divides 128: a row of a
    gulp's words on one device holds whole ones (ops.channelise)."""
    if not stages or not isinstance(stages[0], FftStage) or \
            hdr['_tensor']['dtype'] != 'ci8':
        return False
    logical = hdr['_tensor']['shape']
    f = stages[0]
    return f.axes == [len(logical) - 1] and 128 % logical[-1] == 0


def match_channeliser(stages, headers, shape, dtype, mesh=None):
    """Recognize a short channeliser -- FftStage(c2c forward, the last
    axis, a length that divides 128, shifted or not) ->
    DetectStage('stokes_i', pol) -> ReduceStage(frame axis, r, 'sum')
    [-> TransposeStage] on ci8 dual-pol input, the frame axis first,
    on one device (``mesh`` None) -- and return
    ops.channelise.row_channeliser as a :class:`SpectrometerPlan`: the
    gulp's words taken as the rows of 128 they lie in, every transform
    of a row one block-diagonal real product on the MXU, the shift in
    the matrix's columns, the squares summed over the r spectra, the
    polarisations added on the sums, the product laid out as the
    chain's last header says.  No array with the transform's points
    minor, and no relayout of the gulp.  Its precision is float32's
    (the matrix as three bf16 parts against the voltages, which bf16
    holds exactly: three passes), one pass under BF_FFT_DFT_DTYPE=bf16
    as the other matrix forms (ops.fft.dft_bf16).  The plan starts
    from the gulp's int16 words or its pairs."""
    if mesh is not None or len(stages) not in (3, 4):
        return None
    f, d, r = stages[:3]
    t = stages[3] if len(stages) == 4 else None
    if not (isinstance(d, DetectStage) and isinstance(r, ReduceStage)
            and (t is None or isinstance(t, TransposeStage))):
        return None
    if not opens_with_short_transform(stages, headers[0]) or \
            str(dtype) != 'int8':
        return None
    nd = len(shape) - 1                 # logical rank: (re, im) is last
    if shape[-1] != 2 or headers[0]['_tensor']['shape'].index(-1) != 0:
        return None
    if f.mode != 'c2c' or f.inverse:
        return None
    if d.mode != 'stokes_i' or d.npol != 2 or \
            not 0 < d.axis_index < nd - 1:
        return None
    if r.op != 'sum' or r.axis != r.frame_axis or not r.factor or \
            shape[0] % r.factor:
        return None
    from .ops import channelise
    from .ops.fft import dft_bf16
    logical = tuple(int(n) for n in shape[:-1])
    nspec, pol, shift = r.factor, d.axis_index, bool(f.apply_fftshift)
    precision = 'default' if dft_bf16() else 'split'
    axes = None if t is None else list(t.axes)

    def fn(x):
        y = channelise.row_channeliser(x, logical, nspec, pol, shift,
                                       precision)
        return y if axes is None else y.transpose(axes)
    return SpectrometerPlan(fn, {
        'impl': 'row-channeliser',
        'nfft': logical[-1],
        'shift': shift,
        'precision': precision,
    }, words=fn)
