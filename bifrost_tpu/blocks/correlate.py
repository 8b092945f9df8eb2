"""FX-correlator X step: cross-multiply stations, integrate in time
(reference: python/bifrost/blocks/correlate.py:36-108, backed by the
xGPU-style cherk kernel in src/linalg.cu:210-226).

On TPU the per-channel a·a^H rides the MXU through the raced X-engine
(:class:`bifrost_tpu.ops.linalg.XEngine`): ci8 voltages stay int8 on
exact-int32 candidates, float voltages race planar layouts against the
XLA complex64 baseline, all accuracy-gated per the declared class.  The
output matrix is fully filled (header ``matrix_fill_mode='full'``; the
reference fills the lower triangle only, a CUDA-kernel economy that a
systolic matmul does not need).

Two block forms:

- :class:`CorrelateBlock` — stateful: integrates ``nframe_per_integration``
  frames ACROSS gulps, one output frame per integration.  On one
  device it integrates IN PLACE: the accumulator is a pair of float32
  (re, im) planes, shaped as the output span's frame, which the first
  gulp of an integration makes and every later one is given, donated,
  by a program that takes the channels through the engine a chunk at
  a time (``_VIS_CHUNK_BYTES``), so that a gulp's visibilities never
  exist whole beside the accumulator.  The finished planes ARE the
  product: the block hands them to its ring as they are
  (``devrep.ComplexPlanes``) and keeps nothing, so no complex64
  program runs between the integration and the host (the TPU computes
  complex64 on separate planes: a complex accumulator is copied by
  every add, donated or not, and a program with a complex argument
  splits all of it first).  Under a mesh
  it runs one of two measured plans: time-parallel partial visibilities
  met in a ``psum``, or the CORNER TURN — redistribute the voltages
  from time-sharded to channel-sharded with an on-chip collective
  (``jax.lax.all_to_all``, or the Pallas ring-permute kernel on TPU)
  and correlate each channel shard over the full gulp with zero
  further collectives (``BF_XCORR_CORNER_TURN`` forces a plan; by
  default the plans race under ops.mprobe at prewarm).
- :class:`CorrelateStageBlock` — stage-backed
  (:class:`bifrost_tpu.stages.CorrelateStage`): integrates whole
  groups WITHIN each gulp, which makes it macro-gulp eligible and
  segment-fusable (capture -> F -> X -> accumulate as ONE compiled
  program, bifrost_tpu.segments).
"""

from __future__ import annotations

import os

from copy import deepcopy

from ..pipeline import TransformBlock
from ..stages import CorrelateStage
from ..words import ComplexWords
from .fft import _StageBlock

__all__ = ['CorrelateBlock', 'CorrelateStageBlock', 'correlate']


def _cross_block(x, xg, reim):
    """Cross-multiply a local station-row block against the full
    (gathered) station axis: x (T, F, Sr, P[,2]), xg (T, F, S, P[,2])
    -> (F, Sr, P, S, P)."""
    import jax.numpy as jnp
    if reim:
        from ..ops.linalg import xcorr_int8
        t, f, sr, p = x.shape[:4]
        s = xg.shape[2]
        re_i = x[..., 0].reshape(t, f, sr * p)
        im_i = x[..., 1].reshape(t, f, sr * p)
        re_j = xg[..., 0].reshape(t, f, s * p)
        im_j = xg[..., 1].reshape(t, f, s * p)
        vis = xcorr_int8(re_i, im_i, re_j, im_j)
        return vis.reshape(f, sr, p, s, p)
    t, f, sr, p = x.shape
    s = xg.shape[2]
    xi = x.reshape(t, f, sr * p)
    xj = xg.reshape(t, f, s * p)
    vis = jnp.einsum('tfi,tfj->fij', xi, jnp.conj(xj),
                     preferred_element_type=jnp.complex64)
    return vis.reshape(f, sr, p, s, p)


#: a gulp's channels go through the engine in the fewest equal chunks
#: whose complex64 visibilities hold at most this many bytes: the
#: temporaries of the in-place program are a chunk's, not a product's
#: (0.5-0.7 GB against 3.2 GB at 1024 channels of 512 inputs, by
#: libtpu's own memory analysis; PERF.md section 6, PR 28)
_VIS_CHUNK_BYTES = 1 << 28


def _chunk_nchan(f, n):
    """Channels a chunk: the largest divisor of ``f`` whose (n, n)
    complex64 matrices fit ``_VIS_CHUNK_BYTES`` (one channel at
    least)."""
    fit = max(_VIS_CHUNK_BYTES // (8 * n * n), 1)
    return next(c for c in range(min(fit, f), 0, -1) if f % c == 0)


def _corner_turn_mode():
    """BF_XCORR_CORNER_TURN: 'auto' (default — race the psum and
    corner-turn mesh plans at prewarm where probing is on), 'off'
    (always the psum plan), 'xla' / 'pallas' (force the corner-turn
    plan with that redistribution primitive)."""
    v = os.environ.get('BF_XCORR_CORNER_TURN', 'auto').strip().lower()
    return v if v in ('auto', 'off', 'xla', 'pallas') else 'auto'


class CorrelateBlock(TransformBlock):
    def __init__(self, iring, nframe_per_integration, accuracy='f32',
                 impl=None, *args, **kwargs):
        super(CorrelateBlock, self).__init__(iring, *args, **kwargs)
        from ..ops.linalg import XEngine
        self.nframe_per_integration = nframe_per_integration
        self.engine = XEngine(accuracy=accuracy, impl=impl)
        self.accuracy = self.engine.accuracy
        self._fn = {}
        self.impl_info = None
        #: mesh plan the measured prewarm selected ('psum' or
        #: 'corner:xla' / 'corner:pallas'); published to ProcLog via
        #: impl_info so monitors read what ran
        self._mesh_plan = 'psum'

    def define_valid_input_spaces(self):
        return ('tpu',)

    @property
    def _collective_boundary(self):
        """Segment-planner protocol (bifrost_tpu.segments): under a
        mesh this block schedules its own cross-device collective
        (the corner turn or the psum meeting point), so its ring
        boundaries report reason 'collective' (BF-I191) instead of
        fusing."""
        return self.mesh is not None

    def define_output_nframes(self, input_nframe):
        return 1

    def on_sequence(self, iseq):
        self.nframe_integrated = 0
        self._acc = None
        self._fn = {}
        ihdr = iseq.header
        itensor = ihdr['_tensor']
        assert itensor['labels'] == ['time', 'freq', 'station', 'pol']
        ohdr = deepcopy(ihdr)
        otensor = ohdr['_tensor']
        otensor['dtype'] = 'cf32'
        for key in ('shape', 'labels', 'scales', 'units'):
            # deep-copy the per-axis entries so the doubled station/pol
            # axes don't alias each other or the input header
            tv, fv, sv, pv = (deepcopy(v) for v in itensor[key])
            otensor[key] = [tv, fv, sv, pv,
                            deepcopy(sv) if key != 'labels' else sv + '_j',
                            deepcopy(pv) if key != 'labels' else pv + '_j']
        otensor['labels'][2] += '_i'
        otensor['labels'][3] += '_i'
        otensor['scales'][0][1] *= self.nframe_per_integration
        ohdr['matrix_fill_mode'] = 'full'
        # The engine reads gulps of the *input* header's gulp_nframe (or
        # this block's override); that is what must divide the integration.
        gulp_actual = self.gulp_nframe or ihdr['gulp_nframe']
        if self.nframe_per_integration % gulp_actual != 0:
            raise ValueError(
                "gulp_nframe (%d) does not divide nframe_per_integration "
                "(%d)" % (gulp_actual, self.nframe_per_integration))
        ohdr['gulp_nframe'] = min(ihdr['gulp_nframe'],
                                  self.nframe_per_integration)
        self._prewarm_xcorr(itensor, gulp_actual)
        # GEMM-class ops accounting (like_top's GOP/s column): the full
        # visibility matrix costs F * (S*P)^2 complex MACs per frame
        # (8 real ops each)
        _, f, s, p = itensor['shape'][:4]
        self._gemm_ops = 8 * gulp_actual * f * (s * p) ** 2
        return ohdr

    # -- mesh plan selection --------------------------------------------

    def _corner_eligible(self, shape, ndev):
        """The corner-turn plan applies to a purely time-sharded mesh
        whose device count divides BOTH the frame axis and the channel
        axis (the all_to_all swaps one for the other)."""
        return (shape[0] % ndev == 0 and shape[1] % ndev == 0
                and ndev > 1)

    def _mesh_geometry(self, shape):
        """(tname, ndev, shard_stations, sname) for this gulp shape, or
        None when the mesh cannot shard it."""
        from ..parallel.scope import (time_axis_name, station_axis_name,
                                      shardable_nframe)
        mesh = self.mesh
        if mesh is None or not shardable_nframe(mesh, shape[0]):
            return None
        sname = station_axis_name(mesh)
        shard_stations = (sname is not None and mesh.shape[sname] > 1
                          and shape[2] % mesh.shape[sname] == 0)
        tname = time_axis_name(mesh)
        return tname, mesh.shape[tname], shard_stations, sname

    def _select_mesh_plan(self, shape, dtype, reim):
        """Choose between the psum and corner-turn mesh plans for this
        sequence: an explicit BF_XCORR_CORNER_TURN wins; otherwise the
        two plans race on synthetic data under the mprobe policy (the
        measurement runs at prewarm, never as first-gulp latency).
        The psum plan is the unmeasured default."""
        import numpy as np
        geo = self._mesh_geometry(shape)
        if geo is None:
            return 'psum'
        tname, ndev, shard_stations, _ = geo
        if shard_stations or not self._corner_eligible(shape, ndev):
            return 'psum'
        mode = _corner_turn_mode()
        if mode == 'off':
            return 'psum'
        from ..ops.beamform import Beamformer
        pallas_ok = Beamformer._pallas_raceable()
        if mode in ('xla', 'pallas'):
            return 'corner:%s' % mode
        from ..ops.linalg import _probe_wanted
        if not _probe_wanted():
            return 'psum'
        from ..ops import mprobe
        key = 'v=%s %s ndev=%d acc=%s' % (tuple(shape), dtype, ndev,
                                          self.accuracy)
        cached = mprobe.peek('corner_turn', key)
        names = ['psum', 'corner:xla'] + \
            (['corner:pallas'] if pallas_ok else [])
        if cached is not None and cached[0] in names:
            return cached[0]
        rng = np.random.RandomState(17)
        if reim:
            x = rng.randint(-64, 64, shape).astype(np.int8)
        else:
            x = (rng.randn(*shape) +
                 1j * rng.randn(*shape)).astype(np.complex64)
        fns = {}
        for name in names:
            try:
                fns[name] = self._build_mesh(tuple(shape), dtype, reim,
                                             acc_is_none=True, plan=name)
            except Exception as e:
                mprobe.refused('corner_turn', name, e)
        if len(fns) < 2:
            return 'psum'
        winner, _ms, _err = mprobe.select(
            'corner_turn', key, {n: (lambda f: lambda a: f(a, None))(f)
                                 for n, f in fns.items()},
            lambda: (x,))
        return winner or 'psum'

    def _prewarm_xcorr(self, itensor, gulp_nframe):
        """Probe the X-engine winner (and, under a mesh, the mesh-plan
        winner) for this sequence's gulp shape now, so on_data's jit
        trace (where measuring is impossible) finds them in the cache —
        probe cost must not land as first-gulp latency in a capture
        pipeline."""
        from ..dtype import DataType
        dt = DataType(itensor['dtype'])
        int_input = dt.kind == 'ci' and dt.nbits == 8
        _, f, s, p = itensor['shape'][:4]
        n = s * p
        shape = tuple([gulp_nframe] + list(itensor['shape'][1:4]) +
                      ([2] if int_input else []))
        dtype = 'int8' if int_input else 'complex64'
        try:
            mesh = self.mesh
            t_eff, f_eff = gulp_nframe, f
            if mesh is not None:
                self._mesh_plan = self._select_mesh_plan(shape, dtype,
                                                         int_input)
                geo = self._mesh_geometry(shape)
                if geo is not None:
                    tname, ndev, shard_stations, sname = geo
                    if self._mesh_plan.startswith('corner'):
                        # channel-sharded: full gulp, F/ndev channels
                        f_eff = f // ndev
                    else:
                        t_eff = gulp_nframe // ndev
                    if shard_stations:
                        # station-ROW block against the gathered
                        # column axis rides the 4-operand xcorr race
                        from ..ops.linalg import xcorr_prewarm
                        sr = s // mesh.shape[sname]
                        xcorr_prewarm(t_eff, f, sr * p, n)
                        return
            if mesh is None:
                # the in-place program calls the engine a chunk of
                # channels at a time: that is the shape it is raced at
                f_eff = _chunk_nchan(f, n)
            winner = self.engine.prewarm(t_eff, f_eff, n,
                                         int_input=int_input)
            #: what ran, for monitors and the benchmark's window line
            self.impl_info = {'engine': winner, 'mesh_plan':
                              self._mesh_plan if mesh is not None else None,
                              'nchan_chunk': f_eff}
        except Exception as e:
            # probing is best-effort — the traced default works — but
            # a refusal is never dropped without a word
            from ..ops import mprobe
            mprobe.refused('xengine', 'prewarm', e)

    def _local_vis_fn(self, reim):
        engine = self.engine

        def local_vis(x):
            import jax.numpy as jnp
            if reim:
                t, f, s, p = x.shape[:4]
                re = x[..., 0].reshape(t, f, s * p)
                im = x[..., 1].reshape(t, f, s * p)
            else:
                t, f, s, p = x.shape
                xm = x.reshape(t, f, s * p)
                re, im = jnp.real(xm), jnp.imag(xm)
            vis = engine(re, im)
            return vis.reshape(f, s, p, s, p)
        return local_vis

    def _build_mesh(self, shape, dtype, reim, acc_is_none, plan):
        """One sharded mesh plan: 'psum' (time-parallel partial
        visibilities met in a psum; stations shard too on a 2-D mesh)
        or 'corner:<impl>' (corner-turn the voltages time-sharded ->
        channel-sharded, correlate each channel shard over the full
        gulp, gather the channel axis once).  Returns
        mesh_fn(x, acc) -> vis, or raises when the plan cannot be
        built at this geometry."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        local_vis = self._local_vis_fn(reim)
        mesh = self.mesh
        geo = self._mesh_geometry(shape)
        if geo is None:
            raise ValueError('mesh cannot shard gulp %r' % (shape,))
        tname, ndev, shard_stations, sname = geo
        spec = [None] * len(shape)
        spec[0] = tname
        if plan.startswith('corner'):
            if shard_stations or not self._corner_eligible(shape, ndev):
                raise ValueError('corner-turn plan ineligible at %r'
                                 % (shape,))
            ct_impl = plan.split(':', 1)[1]
            from ..parallel.corner_turn import corner_turn_local

            def local_fn(x, acc):
                # (T/D, F, ...) -> (T, F/D, ...): the on-chip
                # collective; then a channel-local correlation over
                # the FULL gulp with no further collectives, and one
                # gather of the finished channel rows
                xc = corner_turn_local(x, tname, impl=ct_impl)
                vis = local_vis(xc)
                vis = jax.lax.all_gather(vis, tname, axis=0,
                                         tiled=True)
                return vis if acc is None else acc + vis
            out_spec = P()
        else:
            if shard_stations:
                spec[2] = sname

            def local_fn(x, acc):
                if shard_stations:
                    # gather the antenna COLUMN axis; rows stay local
                    xg = jax.lax.all_gather(x, sname, axis=2,
                                            tiled=True)
                    vis = _cross_block(x, xg, reim)
                else:
                    vis = local_vis(x)
                vis = jax.lax.psum(vis, tname)
                return vis if acc is None else acc + vis
            # output (F, S_row, P, S, P): rows sharded over sname
            out_spec = P(None, sname, None, None, None) \
                if shard_stations else P()
        in_spec = P(*spec)
        in_sharding = NamedSharding(mesh, in_spec)
        acc_spec = out_spec
        from jax import shard_map
        kw = {}
        if plan.startswith('corner'):
            # replication of the all_gathered rows can't be statically
            # inferred through the corner-turn collective
            kw['check_vma'] = False
        if acc_is_none:
            sharded = jax.jit(shard_map(
                lambda x: local_fn(x, None), mesh=mesh,
                in_specs=in_spec, out_specs=out_spec, **kw))

            def mesh_fn(x, acc):
                return sharded(jax.device_put(x, in_sharding))
        else:
            sharded = jax.jit(shard_map(
                local_fn, mesh=mesh,
                in_specs=(in_spec, acc_spec),
                out_specs=out_spec, **kw))
            acc_sharding = NamedSharding(mesh, acc_spec)

            def mesh_fn(x, acc):
                acc = jax.device_put(acc, acc_sharding)
                return sharded(jax.device_put(x, in_sharding),
                               acc)
        return mesh_fn

    def _build(self, shape, dtype, reim, acc_is_none):
        """The program of a gulp under a mesh (one device integrates
        in place: :meth:`_build_in_place`)."""
        import jax
        local_vis = self._local_vis_fn(reim)

        def fn(x, acc):
            vis = local_vis(x)
            return vis if acc is None else acc + vis

        if self._mesh_geometry(shape) is not None:
            plan = self._mesh_plan
            try:
                return self._build_mesh(shape, dtype, reim,
                                        acc_is_none, plan)
            except Exception as e:
                if plan != 'psum':      # measured plan failed to
                    from ..ops import mprobe    # build: fall back
                    mprobe.refused('corner_turn', plan, e)
                    self._mesh_plan = 'psum'
                    return self._build_mesh(shape, dtype, reim,
                                            acc_is_none, 'psum')
                raise

        jfn = jax.jit(fn)

        # mesh fallback (e.g. indivisible partial gulp): carried state
        # may be mesh-committed — reconcile device sets first
        def plain_fn(x, acc):
            from ..parallel.scope import gather_local
            x = gather_local(x)
            if acc is not None:
                acc = gather_local(acc)
            return jfn(x, acc)
        return plain_fn

    def _build_in_place(self, shape, reim, first, words=False):
        """The one-device program of a gulp, with the float32
        accumulator planes shaped as the output span's frame
        (1, F, S, P, S, P): ``fn(x, ar, ai) -> (ar, ai)`` takes them
        donated and adds where they lie; the ``first`` of an
        integration, ``fn(x) -> (ar, ai)``, makes planes of its own
        (the last integration's belong to the ring by then).  With
        ``words`` ``x`` is the ci8 gulp's int16 words as the ring
        holds them (devrep.ComplexWords: one axis), folded here to
        (time, freq, station x pol), which is the engine's own order
        of axes, so a chunk's int8 planes are two sign-extending
        shifts of its slice and the pairs of ``shape`` are never
        made."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from ..ops.common import donating_jit
        local_vis = self._local_vis_fn(reim)
        _, f, s, p = shape[:4]
        fc = _chunk_nchan(f, s * p)

        engine = self.engine

        def chunk_vis(x, k):
            x = lax.dynamic_slice_in_dim(x, k * fc, fc, axis=1)
            if not words:
                return local_vis(x)
            # low byte re, high byte im (little-endian)
            re = ((x << 8) >> 8).astype(jnp.int8)
            im = (x >> 8).astype(jnp.int8)
            return engine(re, im).reshape(fc, s, p, s, p)

        def chunk(k, acc, x):
            # real() and imag() of the engine's complex64 are its own
            # two planes again once XLA has simplified the program
            vis = chunk_vis(x, k)
            out = []
            for plane, a in zip((jnp.real(vis), jnp.imag(vis)), acc):
                plane = plane[None]
                if not first:
                    plane = plane + lax.dynamic_slice_in_dim(
                        a, k * fc, fc, axis=1)
                out.append(lax.dynamic_update_slice_in_dim(
                    a, plane, k * fc, axis=1))
            return tuple(out)

        def fn(x, ar, ai):
            if words:
                x = x.reshape(shape[0], f, s * p)   # one pass
            if fc == f:
                return chunk(0, (ar, ai), x)
            return lax.fori_loop(0, f // fc,
                                 lambda k, acc: chunk(k, acc, x),
                                 (ar, ai))
        if not first:
            return donating_jit(fn, donate_argnums=(1, 2))
        # every chunk is written, so what the planes start as is
        # never read
        return jax.jit(lambda x: fn(
            x, *(jnp.empty((1, f, s, p, s, p), jnp.float32),) * 2))

    def _integrate_in_place(self, x, reim):
        """One gulp into the integration's planes: the first makes
        them, on the gulp's device, the others add into them.  A gulp
        that comes as words (devrep.ComplexWords) goes to the program
        as them."""
        from ..telemetry import counters
        first = self._acc is None
        words = isinstance(x, ComplexWords)
        key = (tuple(x.shape), 'words' if words else str(x.dtype), first)
        fn = self._fn.get(key)
        if fn is None:
            fn = self._fn[key] = self._build_in_place(x.shape, reim,
                                                       first, words)
        if words:
            x = x.words
        self._acc = fn(x) if first else fn(x, *self._acc)
        counters.inc('correlate.acc_in_place')

    def on_data(self, ispan, ospan):
        import jax.numpy as jnp
        from ..telemetry import counters
        # a ci8 gulp on one device: the program starts from its words
        x = ispan.words if self.mesh is None else None
        if x is None:
            x = ispan.data
        reim = ispan.tensor['dtype'].kind == 'ci' and \
            not jnp.issubdtype(x.dtype, jnp.complexfloating)
        if self.mesh is None:
            self._integrate_in_place(x, reim)
            self.nframe_integrated += ispan.nframe
            assert self.nframe_integrated <= self.nframe_per_integration
            if self.nframe_integrated < self.nframe_per_integration:
                return 0
            self.nframe_integrated = 0
            # the planes are the ring's from here on
            from ..planes import ComplexPlanes
            ospan.set(ComplexPlanes(*self._acc))
            self._acc = None
            counters.inc('correlate.integrations')
            return 1
        acc_is_none = self._acc is None
        key = (tuple(x.shape), str(x.dtype), acc_is_none)
        fn = self._fn.get(key)
        if fn is None:
            fn = self._build(x.shape, x.dtype, reim, acc_is_none)
            self._fn[key] = fn
        self._acc = fn(x, self._acc)
        self.nframe_integrated += ispan.nframe
        assert self.nframe_integrated <= self.nframe_per_integration
        if self.nframe_integrated == self.nframe_per_integration:
            self.nframe_integrated = 0
            out = self._acc[None]    # add the time axis
            self._acc = None
            ospan.set(out.astype(jnp.complex64))
            counters.inc('correlate.integrations')
            return 1
        return 0


class CorrelateStageBlock(_StageBlock):
    """Stage-backed X step (:class:`bifrost_tpu.stages.CorrelateStage`):
    one visibility per ``nframe_per_vis`` frames WITHIN each gulp.
    Macro-gulp eligible and segment-fusable — the FX flagship chain
    (capture -> F -> X -> accumulate) compiles to ONE program through
    the segment compiler when the verifier proves every boundary safe.
    """

    def __init__(self, iring, nframe_per_vis, accuracy='f32',
                 impl=None, *args, **kwargs):
        super(CorrelateStageBlock, self).__init__(
            iring, CorrelateStage(nframe_per_vis, accuracy=accuracy,
                                  impl=impl), *args, **kwargs)

    @property
    def engine(self):
        return self._stage.engine

    def on_sequence(self, iseq):
        ohdr = super(CorrelateStageBlock, self).on_sequence(iseq)
        # eager engine prewarm at the per-group shape (r, f, n): the
        # vmapped trace inside the stage sees exactly this shape at
        # EVERY macro factor K, so one probe covers all gulp modes
        from ..dtype import DataType
        itensor = iseq.header['_tensor']
        dt = DataType(itensor['dtype'])
        _, f, s, p = itensor['shape'][:4]
        try:
            self._stage.engine.prewarm(
                self._stage.nframe_per_vis, f, s * p,
                int_input=(dt.kind == 'ci' and dt.nbits == 8))
        except Exception as e:
            # probing is best-effort — the traced default works — but
            # a refusal is never dropped without a word
            from ..ops import mprobe
            mprobe.refused('xengine', 'prewarm', e)
        gulp_actual = self.gulp_nframe or iseq.header['gulp_nframe']
        self._gemm_ops = 8 * gulp_actual * f * (s * p) ** 2
        return ohdr


def correlate(iring, nframe_per_integration, accuracy='f32', impl=None,
              fusable=False, *args, **kwargs):
    """Block: the X step of an FX correlator (reference docstring:
    blocks/correlate.py:106-136; xGPU reference arXiv:1107.4264).

    ``accuracy`` / ``impl`` configure the raced X-engine
    (ops.linalg.XEngine).  ``fusable=True`` returns the stage-backed
    :class:`CorrelateStageBlock` (integration within each gulp —
    macro-gulp eligible, segment-fusable); the default is the
    stateful :class:`CorrelateBlock` (integration across gulps)."""
    if fusable:
        return CorrelateStageBlock(iring, nframe_per_integration,
                                   accuracy, impl, *args, **kwargs)
    return CorrelateBlock(iring, nframe_per_integration, accuracy,
                          impl, *args, **kwargs)
