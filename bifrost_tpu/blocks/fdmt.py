"""FDMT dedispersion block (reference: python/bifrost/blocks/fdmt.py:38-140).

Input layout [..., 'freq', 'time'] — time is the frame axis and is last,
so 'freq' rides the ring's ringlet dimension and each frequency lane is
time-contiguous (reference uses the same ringlet trick).  Positive
delays look ahead: the block overlaps successive gulps by max_delay
frames (define_input_overlap_nframe), exactly like the reference.
Negative delays look back: the block holds the max_delay frames before
each gulp on the device itself and hands the core [history ‖ gulp] in
one program on one device, so every gulp's product leaves with that
gulp, the last one included, and nothing upstream runs twice.
"""

from __future__ import annotations

import math
from copy import deepcopy

from ..pipeline import TransformBlock
from ..units import convert_units
from ..ops.fdmt import Fdmt, KDM
from ..stages import FdmtStage, MatchedFilterStage, ThresholdStage
from .fft import _StageBlock

__all__ = ['FdmtBlock', 'fdmt', 'history_program', 'FdmtStageBlock',
           'fdmt_stage', 'MatchedFilterBlock', 'matched_filter',
           'ThresholdBlock', 'threshold']


def history_program(core, max_delay):
    """``fdmt_history(history, x) -> (dm_time, history')``, the program
    of a gulp with negative delays (the module a device trace names
    ``jit_fdmt_history``): ``core``, a one-gulp FDMT core with negative
    delays (``Fdmt._pick_core``), over [history ‖ x] along time, the
    last axis, batched over any leading axes; of its output the gulp's
    own frames, and of its input the last ``max_delay`` frames, the
    next gulp's history."""
    import jax
    import jax.numpy as jnp

    def fdmt_history(history, x):
        x = jnp.concatenate([history, x.astype(jnp.float32)], axis=-1)
        flat = x.reshape((-1,) + x.shape[-2:])
        y = jax.vmap(core)(flat)[..., max_delay:]
        return y.reshape(x.shape[:-2] + y.shape[-2:]), x[..., -max_delay:]
    return fdmt_history


class FdmtBlock(TransformBlock):
    def __init__(self, iring, max_dm=None, max_delay=None,
                 max_diagonal=None, exponent=-2.0, negative_delays=False,
                 *args, **kwargs):
        super(FdmtBlock, self).__init__(iring, *args, **kwargs)
        if sum(m is not None
               for m in (max_dm, max_delay, max_diagonal)) != 1:
            raise ValueError("Must specify exactly one of: max_dm, "
                             "max_delay, max_diagonal")
        self.max_value = max_dm or max_delay or max_diagonal or 0.
        self.max_mode = ('dm' if max_dm is not None else
                         'delay' if max_delay is not None else 'diagonal')
        self.dm_units = 'pc cm^-3'
        self.exponent = exponent
        self.negative_delays = negative_delays
        self.fdmt = Fdmt()
        self._mesh_fns = {}
        self._history_fns = {}
        #: negative delays: the last max_delay input frames, on the
        #: device (zeros before a sequence's first frame)
        self._history = None
        #: what ran: ``{'fdmt': {'core', 'history', 'max_delay'}}``
        self.impl_info = None

    def define_valid_input_spaces(self):
        return ('tpu',)

    def on_sequence(self, iseq):
        ihdr = iseq.header
        itensor = ihdr['_tensor']
        labels = itensor['labels']
        if labels[-1] != 'time' or labels[-2] != 'freq':
            raise KeyError("Expected axes [..., 'freq', 'time'], got %s"
                           % labels)
        nchan = itensor['shape'][-2]
        f0_, df_ = itensor['scales'][-2]
        t0_, dt_ = itensor['scales'][-1]
        f0 = convert_units(f0_, itensor['units'][-2], 'MHz')
        df = convert_units(df_, itensor['units'][-2], 'MHz')
        dt = convert_units(dt_, itensor['units'][-1], 's')
        max_mode, max_value = self.max_mode, self.max_value
        if max_mode == 'diagonal':
            max_mode, max_value = 'delay', int(
                math.ceil(nchan * self.max_value))
        if max_mode == 'dm':
            max_dm = max_value
            rel_delay = (KDM / dt * max_dm *
                         (f0 ** -2 - (f0 + nchan * df) ** -2))
            self.max_delay = int(math.ceil(abs(rel_delay)))
        else:
            self.max_delay = int(max_value)
            fac = f0 ** -2 - (f0 + nchan * df) ** -2
            max_dm = self.max_delay * dt / (KDM * abs(fac))
        # a positive DM reaches the higher frequencies first: along an
        # ascending band that is the sweep of negative delays
        max_dm = abs(max_dm) if (df > 0) == self.negative_delays \
            else -abs(max_dm)
        self.dm_step = max_dm / self.max_delay
        self.fdmt.init(nchan, self.max_delay, f0, df, self.exponent,
                       space='tpu')
        # cached programs close over the previous sequence's plan
        self._mesh_fns = {}
        self._history_fns = {}
        self._history = None
        # Pre-warm at sequence start, before any gulp flows: the
        # measured core probe + XLA compile otherwise land inside the
        # first on_data — and in the reference's world a first-gulp
        # latency spike in a capture pipeline is a dropped packet
        # (VERDICT r4 item 6).  The expected gulp is stride + overlap
        # frames on the time axis; a shrunk final gulp still recompiles
        # lazily as before.  Negative delays read the gulp alone.
        gulp = self.gulp_nframe or ihdr.get('gulp_nframe')
        if gulp:
            try:
                import jax
                import jax.numpy as jnp
                from ..dtype import DataType
                dtype = DataType(itensor['dtype']).as_jax_dtype()
                ov = 0 if self.negative_delays else self.max_delay
                shape = tuple(int(s) if s != -1 else int(gulp) + ov
                              for s in itensor['shape'])
                if self.negative_delays:
                    jax.block_until_ready(self._history_fn(shape, dtype)(
                        jnp.zeros(shape[:-1] + (self.max_delay,),
                                  jnp.float32), jnp.zeros(shape, dtype)))
                elif self._mesh_fn(shape) is not None:
                    # the mesh path serves every full gulp: warm ITS
                    # compile (the single-device warmup would build a
                    # fn the steady state never executes)
                    jax.block_until_ready(self._mesh_fn(shape)(
                        jnp.zeros(shape, jnp.float32)))
                else:
                    self.fdmt.warmup(shape, dtype)
            except Exception:
                pass    # fall back to lazy build at first gulp
        ohdr = deepcopy(ihdr)
        refdm = convert_units(ihdr['refdm'], ihdr['refdm_units'],
                              self.dm_units) if 'refdm' in ihdr else 0.
        ohdr['_tensor']['dtype'] = 'f32'
        ohdr['_tensor']['shape'][-2] = self.max_delay
        ohdr['_tensor']['labels'][-2] = 'dispersion'
        ohdr['_tensor']['scales'][-2] = [refdm, self.dm_step]
        ohdr['_tensor']['units'][-2] = self.dm_units
        ohdr['max_dm'] = max_dm
        ohdr['max_dm_units'] = self.dm_units
        ohdr['cfreq'] = f0_ + 0.5 * (nchan - 1) * df_
        ohdr['cfreq_units'] = itensor['units'][-2]
        ohdr['bw'] = nchan * df_
        ohdr['bw_units'] = itensor['units'][-2]
        # output frame t is the sweep that crosses the band's first
        # edge, f0, at time t: channel c it crosses delay(c) frames
        # later (positive delays) or earlier (negative)
        ohdr['reffreq'] = f0_
        ohdr['reffreq_units'] = itensor['units'][-2]
        return ohdr

    def define_input_overlap_nframe(self, iseq):
        """Positive delays need max_delay frames of lookahead
        (reference: blocks/fdmt.py define_input_overlap_nframe);
        negative delays look back, at frames the block holds itself."""
        return 0 if self.negative_delays else self.max_delay

    def _history_fn(self, shape, dtype):
        """The program of a gulp of ``shape`` with negative delays,
        ``fdmt_history(history, x) -> (dm_time, history')``: the core
        over [history ‖ gulp], the gulp's own frames of its output, and
        the last max_delay frames of the input, held for the next gulp.
        The core is raced at that width (Fdmt._pick_core)."""
        key = (tuple(shape), str(dtype))
        fn = self._history_fns.get(key)
        if fn is None:
            import jax
            md = self.max_delay
            core = self.fdmt._pick_core(
                True, shape=(int(shape[-2]), md + int(shape[-1])))
            fn = self._history_fns[key] = jax.jit(
                history_program(core, md))
        return fn

    def _count(self, nframe, held):
        """``fdmt.gulps``; ``fdmt.device_history_gulps``, of them those
        whose history came from frames the block held on the device;
        ``fdmt.front_frames``, the input frames it was handed, overlap
        included: what the blocks upstream computed for it."""
        from ..telemetry import counters
        counters.inc('fdmt.gulps')
        counters.inc('fdmt.device_history_gulps', 1 if held else 0)
        counters.inc('fdmt.front_frames', int(nframe))
        self.impl_info = {'fdmt': {
            'core': self.fdmt.chosen_core,
            'history': 'device' if held else 'ring overlap',
            'max_delay': self.max_delay}}

    def _mesh_fn(self, shape):
        """Time-sharded transform over the scope mesh when the gulp
        admits it (2-D (nchan, T) data, time divisible by the mesh's
        time axis, per-shard window >= max_delay for the adjacent-
        neighbor halo).  Bit-compatible with the single-device core —
        parallel.ops.sharded_fdmt exchanges a max_delay halo via
        ppermute, so a shrunk final gulp simply falls back.  Built
        once per shape; None caches negative decisions too."""
        key = tuple(shape)
        if key in self._mesh_fns:
            return self._mesh_fns[key]
        fn = None
        mesh = self.mesh
        if mesh is not None and len(shape) == 2:
            from ..parallel.scope import time_axis_name
            tname = time_axis_name(mesh)
            if tname is not None:
                n = int(mesh.shape[tname])
                T = int(shape[-1])
                if n > 1 and T % n == 0 and T // n >= self.max_delay:
                    import jax
                    import jax.numpy as jnp
                    from jax.sharding import (NamedSharding,
                                              PartitionSpec as P)
                    from ..parallel.ops import sharded_fdmt
                    # per-shard windows are (nchan, T/n + halo): probe
                    # the measured core winner at that width rather
                    # than running the mesh path on the unmeasured
                    # gather core (the probe is cached/locked, so a
                    # ragged later shape reuses it)
                    core = self.fdmt._pick_core(
                        self.negative_delays,
                        shape=(int(shape[0]),
                               T // n + self.max_delay))
                    sharded = jax.jit(sharded_fdmt(
                        mesh, self.fdmt, tname,
                        negative_delays=self.negative_delays,
                        core=core))
                    in_sh = NamedSharding(mesh, P(None, tname))

                    def fn(x, _sh=sharded, _in=in_sh):
                        # mirror Fdmt._get_fn's wrapper: integer input
                        # dtypes must compute (and publish) as f32
                        x = x.astype(jnp.float32)
                        return _sh(jax.device_put(x, _in))
        self._mesh_fns[key] = fn
        return fn

    def on_data(self, ispan, ospan):
        if self.negative_delays:
            import jax.numpy as jnp
            from ..telemetry import spans
            x = ispan.data
            if self._history is None:
                self._history = jnp.zeros(
                    tuple(x.shape[:-1]) + (self.max_delay,), jnp.float32)
            fn = self._history_fn(x.shape, x.dtype)
            with spans.timed('fdmt.dispatch', 'compute',
                             frame=int(ispan.frame_offset)):
                y, self._history = fn(self._history, x)
            ospan.set(y, owned=True)
            self._count(ispan.nframe, True)
            return
        if ispan.nframe <= self.max_delay:
            return 0
        self._count(ispan.nframe, False)
        x = ispan.data
        fn = self._mesh_fn(x.shape)
        if fn is not None:
            ospan.set(fn(getattr(x, 'data', x)))
            return
        ospan.set(self.fdmt.execute(x,
                                    negative_delays=self.negative_delays))


def fdmt(iring, max_dm=None, max_delay=None, max_diagonal=None,
         exponent=-2.0, negative_delays=False, *args, **kwargs):
    """Block: Fast Dispersion Measure Transform (incoherent dedispersion
    for pulsar/FRB searches; reference docstring: blocks/fdmt.py:129-178)."""
    return FdmtBlock(iring, max_dm, max_delay, max_diagonal, exponent,
                     negative_delays, *args, **kwargs)


class FdmtStageBlock(_StageBlock):
    """Stage-backed FDMT: the same transform as :class:`FdmtBlock`,
    but driven by :class:`bifrost_tpu.stages.FdmtStage` so the whole
    FRB-search chain (channelize -> fdmt -> matched_filter ->
    threshold) is segment-fusable AND macro-gulp eligible with the
    in-program halo carry (docs/perf.md): the compiled segment reads
    K*G + max_delay frames per span, the ghost history rides the span
    head once, and the interior overlap handoffs never touch a ring.
    The legacy :class:`FdmtBlock` keeps the mesh halo-exchange path
    and the max_dm/max_diagonal sizing modes."""

    def __init__(self, iring, max_delay, exponent=-2.0,
                 *args, **kwargs):
        super(FdmtStageBlock, self).__init__(
            iring, FdmtStage(max_delay, exponent), *args, **kwargs)


def fdmt_stage(iring, max_delay, exponent=-2.0, *args, **kwargs):
    """Block: stage-backed, segment-fusable FDMT (fixed ``max_delay``
    sizing; see :class:`FdmtStageBlock`)."""
    return FdmtStageBlock(iring, max_delay, exponent, *args, **kwargs)


class MatchedFilterBlock(_StageBlock):
    """Boxcar matched filter along the time axis: output frame t is
    the fixed-order sum of input frames [t, t + ntap), the standard
    width-matched detection filter for dispersed-pulse searches.
    Declares ``ntap - 1`` frames of lookahead, carried in-program when
    fused (halo carry)."""

    def __init__(self, iring, ntap, *args, **kwargs):
        super(MatchedFilterBlock, self).__init__(
            iring, MatchedFilterStage(ntap), *args, **kwargs)


def matched_filter(iring, ntap, *args, **kwargs):
    """Block: boxcar matched filter over ``ntap`` time frames (see
    :class:`MatchedFilterBlock`)."""
    return MatchedFilterBlock(iring, ntap, *args, **kwargs)


class ThresholdBlock(_StageBlock):
    """Peak detect: zero every sample below ``threshold``, keep the
    rest — the candidate sink then reads survivors off the ring
    (frame-local, so trivially fusable and macro-gulp safe)."""

    def __init__(self, iring, threshold, *args, **kwargs):
        super(ThresholdBlock, self).__init__(
            iring, ThresholdStage(threshold), *args, **kwargs)


def threshold(iring, threshold, *args, **kwargs):
    """Block: peak detect against a fixed ``threshold`` (see
    :class:`ThresholdBlock`)."""
    return ThresholdBlock(iring, threshold, *args, **kwargs)
