"""Integrate gulps: b = beta*b + a, committing every ``nframe`` inputs
(reference: python/bifrost/blocks/accumulate.py:41-74).

On TPU the accumulator is one device array the block owns for the
length of an integration: the first gulp's program makes it, every
other is given it donated and adds into it in place, and the commit
gulp hands it to the output ring and keeps nothing (as
blocks/correlate.py does with its planes), so a 1 GiB spectrum
integrates in 1 GiB however many gulps it takes.

:class:`AccumulateStageBlock` (``accumulate(..., fusable=True)``) is
the stateless form: it sums ``nframe``-frame groups WITHIN each gulp
(stages.AccumulateStage), so it is macro-gulp eligible and
segment-fusable — the FX-correlator chain's visibility integrator.
"""

from __future__ import annotations

from copy import deepcopy

from ..pipeline import TransformBlock
from ..dtype import DataType
from ..ops.common import complexify
from ..stages import AccumulateStage
from .fft import _StageBlock

__all__ = ['AccumulateBlock', 'AccumulateStageBlock', 'accumulate',
           'gulp_program']


def gulp_program(otype, idtype, first):
    """The device program that takes one gulp of ``idtype`` into an
    integration of ``otype``: the ``first`` of an integration,
    ``fn(x) -> acc``, makes an accumulator (the last integration's is
    the ring's by then); every other, ``fn(acc, x) -> acc``, is given
    it donated and adds where it lies, so one gulp's worth of device
    memory holds the sum however long the integration."""
    import jax
    from ..ops.common import donating_jit
    odt = DataType(otype).as_jax_dtype()

    def term(x):
        return complexify(x, idtype).astype(odt)
    if first:
        return jax.jit(term)
    return donating_jit(lambda acc, x: acc + term(x), donate_argnums=(0,))


class AccumulateBlock(TransformBlock):
    def __init__(self, iring, nframe, dtype=None, gulp_nframe=1,
                 *args, **kwargs):
        assert gulp_nframe == 1
        super(AccumulateBlock, self).__init__(iring, gulp_nframe=1,
                                              *args, **kwargs)
        self.nframe = nframe
        self.dtype = dtype

    def define_valid_input_spaces(self):
        return ('tpu', 'system')

    @property
    def impl_info(self):
        """What ran, for monitors and a benchmark's window line: what
        the block that fills this one's ring published about itself
        (a fused chain's transform path), and the integration."""
        info = dict(getattr(getattr(self.iring, 'owner', None),
                            'impl_info', None) or {})
        info['accumulate'] = self.nframe
        return info

    def on_sequence(self, iseq):
        ihdr = iseq.header
        ohdr = deepcopy(ihdr)
        otensor = ohdr['_tensor']
        if 'scales' in otensor:
            frame_axis = otensor['shape'].index(-1)
            otensor['scales'][frame_axis][1] *= self.nframe
        if self.dtype is not None:
            otensor['dtype'] = str(self.dtype)
        self.frame_count = 0
        self._acc = None
        self._fn = {}
        self.otype = DataType(otensor['dtype'])
        return ohdr

    def _program(self, x, idtype, first):
        """The device program of one gulp (:func:`gulp_program`),
        built once a shape."""
        key = (tuple(x.shape), str(x.dtype), first)
        fn = self._fn.get(key)
        if fn is None:
            fn = self._fn[key] = gulp_program(self.otype, idtype, first)
        return fn

    def on_data(self, ispan, ospan):
        from ..telemetry import counters
        on_device = ispan.ring.space == 'tpu'
        first = self.frame_count == 0 or self._acc is None
        if on_device:
            fn = self._program(ispan.data, ispan.dtype, first)
            if first:
                self._acc = fn(ispan.data)
            else:
                self._acc = fn(self._acc, ispan.data)
                counters.inc('accumulate.acc_in_place')
        else:
            import numpy as np
            x = ispan.data.as_numpy()
            odt = self.otype.as_numpy_dtype()
            if first:
                self._acc = x.astype(odt) if odt.names is None else x.copy()
            else:
                self._acc = self._acc + x
        counters.inc('accumulate.gulps')
        self.frame_count += 1
        if self.frame_count == self.nframe:
            if on_device:
                # the sum is the ring's from here on
                ospan.set(self._acc, owned=True)
                self._acc = None
            else:
                ospan.data.as_numpy()[...] = self._acc
            self.frame_count = 0
            counters.inc('accumulate.integrations')
            return 1
        return 0


class AccumulateStageBlock(_StageBlock):
    """Stage-backed integrator: sums ``nframe``-frame groups WITHIN
    each gulp (requires nframe | gulp) — macro-gulp eligible and
    segment-fusable, unlike the stateful AccumulateBlock whose
    cross-gulp carry pins gulp_nframe=1."""

    def __init__(self, iring, nframe, op='sum', *args, **kwargs):
        super(AccumulateStageBlock, self).__init__(
            iring, AccumulateStage(nframe, op=op), *args, **kwargs)


def accumulate(iring, nframe, dtype=None, fusable=False, *args,
               **kwargs):
    """Block: accumulate ``nframe`` frames before outputting one.
    ``fusable=True`` returns the stage-backed in-gulp integrator
    (:class:`AccumulateStageBlock`; ``dtype`` must be None — the
    stage keeps the input dtype)."""
    if fusable:
        assert dtype is None, 'fusable accumulate keeps the input dtype'
        return AccumulateStageBlock(iring, nframe, *args, **kwargs)
    return AccumulateBlock(iring, nframe, dtype, *args, **kwargs)
