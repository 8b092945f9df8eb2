"""FusedBlock: run a chain of device stages as ONE jitted computation.

Where the reference executes one CUDA kernel (or cuFFT/cuBLAS call) per
block per gulp (reference: pipeline.py:627-628), a FusedBlock composes
the stage functions and jits the composition — XLA fuses elementwise
stages into the FFT/GEMM epilogues and the whole chain costs one
dispatch and no intermediate ring traffic.  This is the intended
operating mode for hot paths (the Guppi spectroscopy chain runs
FFT→detect→reduce fused).
"""

from __future__ import annotations

from ..pipeline import TransformBlock
from ..words import ComplexWords

__all__ = ['FusedBlock', 'fused', 'device_stages']


def device_stages(block):
    """The jit-backed Stage chain ``block`` executes as pure device
    math, or None when the block is not stage-backed (host blocks,
    space movers, sources/sinks, bridges).  This is the segment
    compiler's eligibility primitive (bifrost_tpu.segments): any two
    adjacent blocks with stage chains compose into ONE traced body —
    a FusedBlock contributes its whole chain, a jitted stage block
    its single stage."""
    from .fft import _StageBlock
    if isinstance(block, FusedBlock):
        return list(block.stages)
    if isinstance(block, _StageBlock):
        return [block._stage]
    return None


class FusedBlock(TransformBlock):
    def __init__(self, iring, stages, *args, **kwargs):
        super(FusedBlock, self).__init__(iring, *args, **kwargs)
        self.stages = list(stages)
        #: compiled plans keyed by (shape, dtype, donate) for the
        #: per-gulp path and by ('macro', part_shapes, dtype, donate,
        #: G, mode) for macro-gulp batches — the donating and
        #: non-donating variants are distinct XLA programs (input
        #: aliasing differs), cached side by side
        self._plans = {}
        self._plan_impls = {}   # same key -> impl info recorded at build
        #: warm-start plan depot (bifrost_tpu.service; docs/service.md):
        #: a dict shared ACROSS job instances with the same structural
        #: topology + plan signature — builds deposit into it, and a
        #: warm-started job's blocks replay deposits instead of
        #: re-tracing/re-compiling (fused.plan_depot_hits).  None (the
        #: default) disables the seam entirely.
        self._plan_depot = None
        self._donate_on = None
        #: configuration of the path the LAST EXECUTED plan runs
        #: (published to ProcLog ``<name>/impl`` so benchmarks and
        #: monitors read what ran instead of re-deriving it)
        self.impl_info = None
        self._published_impl = None
        self._published_key = None
        self._last_built_impl = None
        from ..proclog import ProcLog
        self._impl_proclog = ProcLog(self.name + '/impl')

    def define_valid_input_spaces(self):
        return ('tpu',)

    # -- warm-start plan sharing (bifrost_tpu.service) --------------------
    def plan_signature(self):
        """Stable identity of the math this block's compiled plans
        implement: the stage chain's types + scalar construction
        parameters.  Two FusedBlocks with equal signatures compile
        byte-identical programs for equal plan keys, so their plans
        may be shared through a depot.  Returns None when any stage
        carries non-scalar state (e.g. a weights array) — such plans
        are never shared (the service counts the resulting warm-start
        rejection on ``service.warm.rejected_stale``)."""
        chain = []
        for s in self.stages:
            items = []
            for k, v in sorted(vars(s).items()):
                if isinstance(v, (int, float, str, bool, bytes,
                                  type(None))):
                    items.append((k, v))
                elif isinstance(v, (tuple, list)) and all(
                        isinstance(x, (int, float, str, bool,
                                       type(None))) for x in v):
                    items.append((k, tuple(v)))
                else:
                    return None
            chain.append((type(s).__name__, tuple(items)))
        return (type(self).__name__, tuple(chain))

    def _depot_fetch(self, key):
        """A previously deposited compiled plan for ``key``, installed
        into this block's plan cache, or None."""
        depot = self._plan_depot
        if depot is None:
            return None
        got = depot.get(key)
        if got is None:
            return None
        plan, info = got
        self._plans[key] = plan
        self._plan_impls[key] = info
        from ..telemetry import counters
        counters.inc('fused.plan_depot_hits')
        return plan

    def _depot_store(self, key):
        if self._plan_depot is not None:
            self._plan_depot[key] = (self._plans[key],
                                     self._plan_impls.get(key))

    def verify_header(self, ihdr):
        """Static-verification protocol (bifrost_tpu.analysis.verify):
        the output header this chain will advertise for ``ihdr``,
        derived by running each stage's pure ``transform_header`` half.
        A stage that rejects the stream contract (wrong dtype, missing
        axis label, non-divisible shape) raises HERE at submit time
        instead of in on_sequence at gulp 0."""
        hdr = ihdr
        for stage in self.stages:
            hdr = stage.transform_header(hdr)
        return hdr

    def macro_gulp_safe(self):
        """Macro-gulp eligible — including under a mesh: the K-gulp
        span shards over the mesh time axis exactly like a single gulp
        (K·G frames instead of G), so batched dispatch composes with
        sharded plans.  This is where dispatch amortization actually
        pays on TPU: one program K gulps wide AND N chips wide."""
        return True

    def macro_overlap_safe(self):
        """In-segment halo carry (docs/perf.md): a 'block'-mode chain
        with a derivable lookahead batches WITH its declared overlap —
        the K-gulp span arrives as K·G + overlap frames (ghost history
        sliced from the span head once) and the SAME composed program
        computes it, the trailing ghost frames going uncommitted.
        Correct because every member stage's committed output frame is
        a fixed-order function of a bounded input lookahead window
        (Stage.overlap_nframe), independent of span position."""
        from ..macro import chain_batch_mode
        from ..stages import chain_overlap_nframe
        return chain_batch_mode(self.stages) == 'block' and \
            chain_overlap_nframe(self.stages) is not None

    def define_input_overlap_nframe(self, iseq):
        from ..stages import chain_overlap_nframe
        ov = chain_overlap_nframe(self.stages)
        if ov is None:
            raise ValueError(
                '%s: stage-chain lookahead does not convert to a '
                'whole input-frame count' % self.name)
        return ov

    def on_sequence(self, iseq):
        hdr = iseq.header
        self._headers = [hdr]
        for stage in self.stages:
            hdr = stage.transform_header(hdr)
            self._headers.append(hdr)
        self._plans = {}
        self._plan_impls = {}
        self._published_impl = None
        self._published_key = None
        self._donate_on = None
        from ..stages import BeamformStage
        self._beam_stage = next((st for st in self.stages
                                 if isinstance(st, BeamformStage)), None)
        # ring-resident sharding advertisement: under a mesh this block
        # commits output spans sharded over the OUTPUT frame axis; a
        # stale input descriptor must never survive a layout change
        hdr.pop('_sharding', None)
        if self.mesh is not None:
            from ..parallel.scope import (sharding_descriptor,
                                          check_descriptor)
            try:
                # a producer advertising a layout this scope's mesh
                # would relayout is a per-sequence misconfiguration —
                # flag it once (mesh.layout_mismatch) up front
                check_descriptor(iseq.header,
                                 self.mesh,
                                 self._headers[0]['_tensor']
                                 ['shape'].index(-1))
                taxis_out = hdr['_tensor']['shape'].index(-1)
                hdr['_sharding'] = sharding_descriptor(self.mesh,
                                                       taxis_out)
            except (KeyError, ValueError):
                pass
        self._prewarm(iseq.header)
        return hdr

    def _prewarm(self, ihdr):
        """Build + compile + run the fused plan once on zeros of the
        expected gulp shape, at sequence start — so the kernel
        accuracy/compile probes and the XLA compile are not paid as
        first-gulp latency inside a live capture pipeline (VERDICT r4
        item 6).  Runs the SAME _execute_plan path on_data uses, so
        the cached plan key cannot drift from the hot path.  With
        donation active, the donating plan is the hot path — prewarm
        that variant too (the zeros gulp is exclusively ours to
        donate).  With a macro-gulp batch configured, the K-gulp macro
        plan is prewarmed as well (a full batch is the steady-state
        shape; the tail still compiles lazily).  Any failure falls
        back to the lazy build in on_data."""
        t = ihdr.get('_tensor', {})
        gulp = self.gulp_nframe or ihdr.get('gulp_nframe')
        if not gulp or -1 not in t.get('shape', []):
            return
        from ..stages import chain_overlap_nframe
        ov = chain_overlap_nframe(self.stages) or 0
        try:
            import jax
            from ..devrep import device_rep_zeros, whole
            # overlapped chains read gulp + lookahead frames per span
            shape = tuple(int(s) if s != -1 else int(gulp) + ov
                          for s in t['shape'])

            def zeros(shape):
                # the form a gulp will come in: a ci8 gulp on one
                # device as words (on_data), pairs under a mesh
                z = device_rep_zeros(shape, t['dtype'])
                return z if self.mesh is None else whole(z)
            jax.block_until_ready(self._execute_plan(zeros(shape)))
            if self._donation_on():
                jax.block_until_ready(self._execute_plan(
                    zeros(shape), donate=True))
        except Exception:
            self._plans = {}
            return
        try:
            from ..macro import resolve_gulp_batch
            k = resolve_gulp_batch(self)
            # skip the K-gulp compile when a static fallback (host
            # topology, ...) would discard it — only the
            # sequence-dependent conditions (overlap / dynamic gulp)
            # can still fall back after this.  Mesh scopes prewarm the
            # macro plan too (macro × mesh composes since PR 6).
            if k > 1 and self._macro_static_reason() is None and \
                    (not ov or self.macro_overlap_safe()):
                import jax
                taxis = t['shape'].index(-1)
                mshape = list(shape)
                # halo carry: K logical gulps + ONE overlap history
                mshape[taxis] = int(gulp) * k + ov
                # a macro span is read whole (``.data``): pairs
                jax.block_until_ready(self._execute_macro(
                    [whole(zeros(tuple(mshape)))],
                    donate=False, gulp_nframe=int(gulp)))
                if self._donation_on():
                    jax.block_until_ready(self._execute_macro(
                        [whole(zeros(tuple(mshape)))],
                        donate=True, gulp_nframe=int(gulp)))
        except Exception:
            # keep the per-gulp plans warmed above; the macro plan
            # builds lazily on the first batch instead
            self._plans = {key: p for key, p in self._plans.items()
                           if key and key[0] != 'macro'}

    def define_output_nframes(self, input_nframe):
        n = input_nframe
        for stage in self.stages:
            n = stage.output_nframe(n)
        return n

    def _build_plan(self, shape, dtype, donate=False, words=False):
        """The plan of a gulp of ``shape`` and ``dtype``; with
        ``words`` (one device only) the same plan as a function of the
        gulp's int16 words (stages.from_words): the whole-chain
        kernel folds them to its rows, one pass where the pairs take
        four, any other chain makes the pairs first, inside the one
        program."""
        import jax
        from ..stages import compose_stages, from_words
        from ..ops.common import donating_jit
        from ..telemetry import counters as _counters
        # every plan build (trace + compile) is counted: the service
        # tier's warm-start gate asserts a warm job's delta is ZERO
        _counters.inc('fused.plan_builds')
        mesh = self.mesh
        if mesh is None:
            # compose_stages applies the whole-chain kernel
            # substitution (e.g. the fused Pallas spectrometer) when
            # the stage pattern + accuracy gate admit
            composed, info = compose_stages(
                self.stages, self._headers, shape, dtype)
            bound = getattr(composed, 'bound', None)
            if words:
                info = dict(info, input='words')
            if bound is not None:
                # operands that live on the device (a beamformer's
                # weights) are arguments of the program: closed over,
                # they would be folded into it as constants
                composed, operands = bound()
            elif words:
                composed = from_words(composed, shape)
            if donate:
                # the donated gulp's HBM buffer is reusable in place
                # for any matching intermediate of the chain
                info = dict(info, donate_argnums=[0])
                plan = donating_jit(composed, donate_argnums=(0,))
            else:
                plan = jax.jit(composed)
            self._set_impl(info)
            if bound is not None:
                return (lambda x, _plan=plan: _plan(x, *operands)), None
            return plan, None
        composed, _ = compose_stages(self.stages, self._headers,
                                     shape, dtype, substitute=False)
        # Scale the whole fused chain over the scope's mesh: shard the
        # gulp's frame axis, let GSPMD partition every stage and insert
        # any collectives (the TPU generalization of the reference's
        # per-block gpu=N placement, reference: pipeline.py:365-366).
        # Plans carry BOTH in_shardings and out_shardings matching the
        # ring-resident layout: a sharded-H2D producer commits spans in
        # exactly the in_sharding, and this block commits its output in
        # exactly the out_sharding the next mesh block expects — chained
        # mesh blocks then exchange spans with ZERO reshards (only the
        # genuine collectives of the math remain; docs/parallel.md).
        from ..parallel.scope import (shardable_nframe,
                                      time_sharding,
                                      time_axis_name,
                                      time_axis_size)
        taxis = self._headers[0]['_tensor']['shape'].index(-1)
        if shardable_nframe(mesh, shape[taxis]):
            nsh = time_axis_size(mesh)
            taxis_out = self._headers[-1]['_tensor']['shape'].index(-1)
            sharding = time_sharding(mesh, len(shape), taxis)
            dargs = (0,) if donate else ()
            # FRAME-LOCAL first: a time-concat-equivariant chain (every
            # stage batch_safe — includes the whole-chain spectrometer
            # substitution, matched at the PER-SHARD shape each device
            # actually compiles) runs inside shard_map on the frame
            # axis, so the compiled program provably contains zero
            # collectives — nothing for the partitioner to get wrong
            # (the CPU partitioner all-gathers FFT batch dims under
            # plain GSPMD).
            from ..macro import chain_batch_mode
            from ..parallel.scope import frame_local_plan
            from ..stages import chain_overlap_nframe as _chain_ov
            # frame-local shard_map splits the frame axis with NO halo
            # exchange — lookahead chains would lose their history at
            # shard boundaries; GSPMD below stays correct (XLA inserts
            # the halo collectives)
            if chain_batch_mode(self.stages) == 'block' and \
                    _chain_ov(self.stages) == 0:
                def build_local(local_shape):
                    fn, info = compose_stages(self.stages,
                                              self._headers,
                                              local_shape, dtype)
                    self._local_info = info
                    return fn
                self._local_info = {}
                got = frame_local_plan(mesh, build_local, shape, dtype,
                                       taxis, taxis_out,
                                       donate_argnums=dargs)
                if got is not None:
                    plan, in_sh, _out_sh = got
                    info = dict(self._local_info,
                                mesh='shard_map[%d]' % nsh,
                                shards=nsh)
                    if donate:
                        info['donate_argnums'] = [0]
                    self._set_impl(info)
                    self._analyze_plan(plan, shape, dtype, in_sh)
                    return plan, taxis
            # GSPMD: non-equivariant chains (or a failed local build)
            # — XLA partitions the whole composition and inserts the
            # genuine collectives; in/out shardings still pin the
            # ring-resident layout at the boundaries
            info = {'impl': 'xla-fused', 'mesh': 'gspmd', 'shards': nsh}
            if donate:
                info['donate_argnums'] = [0]
            self._set_impl(info)
            out_sh = self._out_sharding(composed, shape, dtype, mesh,
                                        taxis_out)
            from ..ops.common import donating_jit
            plan = donating_jit(composed, donate_argnums=dargs,
                                in_shardings=sharding,
                                out_shardings=out_sh)
            self._analyze_plan(plan, shape, dtype, sharding)
            return plan, taxis
        # mesh present but the gulp's frame count is not shardable:
        # run unsharded (partial tail gulps; the producer committed
        # them single-device for the same reason)
        self._set_impl({'impl': 'xla-fused'})
        if donate:
            from ..ops.common import donating_jit
            return donating_jit(composed, donate_argnums=(0,)), None
        return jax.jit(composed), None

    @staticmethod
    def _out_sharding(fn, shape, dtype, mesh, taxis_out):
        """out_shardings for a mesh plan: the output frame axis over
        the mesh time axis when it divides (the ring-resident layout
        the NEXT mesh block's in_shardings expects), else None (XLA
        decides; the consumer falls back like any unshardable gulp)."""
        import jax
        from ..parallel.scope import time_sharding, time_axis_size
        try:
            out = jax.eval_shape(fn, jax.ShapeDtypeStruct(tuple(shape),
                                                          dtype))
        except Exception:
            return None
        if taxis_out >= out.ndim or \
                out.shape[taxis_out] % time_axis_size(mesh):
            return None
        return time_sharding(mesh, out.ndim, taxis_out)

    def _analyze_plan(self, plan, shapes, dtype, in_sharding):
        """BF_MESH_HLO_STATS=1: compile an analysis copy of the plan at
        the ring-resident input layout and count the collectives XLA
        inserted (``mesh.collectives.<kind>``); the count lands in the
        published impl info so monitors can see the plan is
        reshard-free.  ``shapes`` is one shape per plan argument (a
        multi-part macro plan takes one array per donated chunk — the
        analysis must match its arity or it silently fails)."""
        from ..parallel.scope import hlo_stats_enabled, record_collectives
        if not hlo_stats_enabled():
            return
        import jax
        if shapes and not isinstance(shapes[0], (tuple, list)):
            shapes = [shapes]
        args = tuple(jax.ShapeDtypeStruct(tuple(s), dtype,
                                          sharding=in_sharding)
                     for s in shapes)
        counts = record_collectives(plan, args, self.name)
        if counts is not None and self._last_built_impl is not None:
            self._last_built_impl['collectives'] = counts or {}

    def _set_impl(self, info):
        """Record the configuration of the plan being BUILT; publishing
        waits until the plan actually executes (_execute_plan) — with
        donation's per-gulp fallback, two variants coexist and only the
        executed one may claim the ProcLog record."""
        self._last_built_impl = dict(info)

    def _record_impl(self, key, info):
        """Remember the impl record of the plan just built for
        ``key``, together with every candidate the automatic selection
        tried and the backend refused (ops.mprobe.refused): a kernel
        the compiler turned down shows in the published record, not
        only in a warning."""
        if info is not None:
            from ..ops import mprobe
            refused = mprobe.refusals()
            if refused:
                info = dict(info, refused=refused)
        self._plan_impls[key] = info

    def _publish_impl(self, info, key=None):
        """Publish the EXECUTED plan's configuration.  Republishes
        whenever the executed PATH differs from the last published one
        — plan-key change (donate toggling mid-sequence, a macro batch
        engaging, a new shape) or info change — so monitors never read
        a stale impl while a different program is running."""
        self.impl_info = dict(info)
        # like_top's Shd column (docs/parallel.md): how many chips the
        # executing plan spans (1 = single-device)
        self._shards_active = int(info.get('shards', 1) or 1)
        if info == self._published_impl and \
                (key is None or key == self._published_key):
            return
        self._published_impl = dict(info)
        self._published_key = key
        try:
            # force: plan switches are rare, event-driven records — the
            # per-gulp rate limit must not drop one (the published
            # record would then describe a superseded plan)
            self._impl_proclog.update(self.impl_info, force=True)
        except OSError:
            pass

    def _execute_plan(self, x, donate=False):
        """Plan-cache dispatch + execution shared by on_data and
        _prewarm (one copy of the key/shard logic, so the pre-warmed
        key can never drift from the hot path's).  ``donate=True``
        requires an exclusively-owned ``x`` (it is deleted by the
        call) — mesh plans donate too: the sharded input's per-device
        buffers alias same-layout intermediates/outputs shard by
        shard (donation-under-sharding, docs/parallel.md)."""
        words = isinstance(x, ComplexWords)
        if words and self.mesh is not None:
            x, words = x.pairs(), False
        key = (tuple(x.shape), 'words' if words else str(x.dtype),
               bool(donate))
        plan = self._plans.get(key)
        if plan is None:
            plan = self._depot_fetch(key)
        if plan is None:
            self._last_built_impl = None
            plan = self._build_plan(x.shape, x.dtype, donate=donate,
                                    words=words)
            self._plans[key] = plan
            self._record_impl(key, self._last_built_impl)
            self._depot_store(key)
        info = self._plan_impls.get(key)
        if info is not None:
            self._publish_impl(info, key)
        fn, taxis = plan
        if taxis is not None:
            from ..parallel.scope import shard_gulp
            x = shard_gulp(x, self.mesh, taxis)
        return self._dispatch_device(fn, (x.words if words else x,))

    def _execute_macro(self, parts, donate, gulp_nframe):
        """Macro-gulp execution: run ONE compiled program over a
        K-gulp span (bifrost_tpu.macro; docs/perf.md).  ``parts`` is
        the span's input as one array or several exclusively-owned
        chunks exactly tiling it (multi-chunk donation); the plan
        concatenates parts inside the (donating) jit.  Plans are
        cached by (part shapes, dtype, donate, G, mode): the stacked
        'block' mode feeds the whole span through the composed chain
        (every built-in stage is time-concat equivariant, so the
        spectrometer substitution still matches at the macro shape);
        'sliced' mode maps the per-gulp body over G-frame slices
        inside one program when a stage is not provably batch-safe."""
        import jax
        from ..macro import build_batched_fn, chain_batch_mode
        from ..ops.common import donating_jit
        from ..stages import compose_stages, chain_overlap_nframe
        mode = chain_batch_mode(self.stages)
        overlap = chain_overlap_nframe(self.stages) or 0
        part_shapes = tuple(tuple(p.shape) for p in parts)
        dtype = parts[0].dtype
        key = ('macro', part_shapes, str(dtype), bool(donate),
               int(gulp_nframe), mode)
        plan = self._plans.get(key)
        if plan is None:
            plan = self._depot_fetch(key)
        if plan is None:
            from ..telemetry import counters as _counters
            _counters.inc('fused.plan_builds')
            taxis_in = self._headers[0]['_tensor']['shape'].index(-1)
            taxis_out = self._headers[-1]['_tensor']['shape'].index(-1)
            info_box = {}

            def per_shape(shape):
                fn, info = compose_stages(self.stages, self._headers,
                                          shape, dtype)
                info_box.update(info)
                return fn

            fn = build_batched_fn(per_shape, taxis_in, taxis_out,
                                  int(gulp_nframe), part_shapes, mode)
            nframe = sum(s[taxis_in] for s in part_shapes)
            info = dict(info_box,
                        batch=-(-max(nframe - overlap, 1) //
                                int(gulp_nframe)),
                        batch_mode=mode)
            dargs = tuple(range(len(parts))) if donate else ()
            if donate:
                info['donate_argnums'] = list(dargs)
            # macro × mesh: the K-gulp span shards over the mesh time
            # axis exactly like a single gulp (K·G frames instead of
            # G).  A single-part 'block'-mode span takes the same
            # frame-local shard_map shape as the per-gulp mesh plan —
            # zero collectives by construction; multi-part spans (a
            # K=1 producer feeding this macro consumer) and 'sliced'
            # chains jit GSPMD with in_shardings per part instead —
            # the in-program concat/slice is then the partitioner's
            # to place.
            built = None
            if self.mesh is not None:
                from ..parallel.scope import (frame_local_plan,
                                              time_sharding,
                                              time_axis_size)
                nsh = time_axis_size(self.mesh)
                ndim = len(part_shapes[0])
                if all(s[taxis_in] % nsh == 0 for s in part_shapes):
                    # frame-local is halo-blind: overlap chains take
                    # the GSPMD path (XLA inserts halo collectives)
                    if mode == 'block' and len(parts) == 1 and \
                            not overlap:
                        got = frame_local_plan(
                            self.mesh, per_shape, part_shapes[0],
                            dtype, taxis_in, taxis_out,
                            donate_argnums=dargs)
                        if got is not None:
                            built, in_sh, _o = got
                            info = dict(info, **info_box)
                            info['mesh'] = 'shard_map[%d]' % nsh
                            info['shards'] = nsh
                    if built is None:
                        in_sh = time_sharding(self.mesh, ndim,
                                              taxis_in)
                        shard_kw = {'in_shardings':
                                    tuple(in_sh for _ in parts)
                                    if len(parts) > 1 else in_sh}
                        if len(parts) == 1:
                            out_sh = self._out_sharding(
                                fn, part_shapes[0], dtype, self.mesh,
                                taxis_out)
                            if out_sh is not None:
                                shard_kw['out_shardings'] = out_sh
                        info = dict(info, mesh='gspmd', shards=nsh)
                        built = donating_jit(fn, donate_argnums=dargs,
                                             **shard_kw)
                    self._last_built_impl = info
                    self._analyze_plan(built, list(part_shapes), dtype,
                                       in_sh)
                    info = self._last_built_impl
            if built is not None:
                # mesh-sharded plan: remember the shard axis so
                # execution can relayout stray single-device parts
                # (mirroring _execute_plan's shard_gulp step — a jit
                # with explicit in_shardings REJECTS committed
                # mismatched inputs rather than moving them)
                fn, shard_taxis = built, taxis_in
            else:
                fn, shard_taxis = donating_jit(
                    fn, donate_argnums=dargs), None
            plan = (fn, shard_taxis)
            self._plans[key] = plan
            self._record_impl(key, info)
            self._depot_store(key)
        info = self._plan_impls.get(key)
        if info is not None:
            self._publish_impl(info, key)
        fn, shard_taxis = plan
        if shard_taxis is not None:
            from ..parallel.scope import shard_gulp
            parts = [shard_gulp(p, self.mesh, shard_taxis)
                     for p in parts]
        return self._dispatch_device(fn, parts)

    def _count_transformed(self, ngulps):
        """``spectrometer.gulps``: gulps that went through a chain
        with a transform in it (the whole-chain kernel, or an FftStage
        whose path the executed plan recorded);
        ``spectrometer.long_gulps``: those whose transform took the
        three-level path (ops.fft.long_fft);
        ``spectrometer.word_gulps``: those whose program started from
        the gulp's int16 words (devrep.ComplexWords), counted at 0
        too so that a reader finds the counter."""
        info = self.impl_info or {}
        fft = info.get('fft')
        if fft is None and info.get('impl') != 'pallas-spectrometer':
            return
        from ..telemetry import counters
        counters.inc('spectrometer.gulps', ngulps)
        counters.inc('spectrometer.word_gulps',
                     ngulps if info.get('input') == 'words' else 0)
        if fft is not None and fft.get('path') == 'long':
            counters.inc('spectrometer.long_gulps', ngulps)

    def _count_beamformed(self, ngulps, nframe):
        """``beamform.gulps``: gulps that went through a chain with a
        beamformer in it; ``beamform.fused_gulps``: of them, through
        the one kernel (stages.match_beamformer: no beam voltage in
        HBM); ``beamform.word_gulps``: of them, those whose program
        started from the gulp's int16 words; ``beamform.int8_ops``:
        8 x beams x samples of their ``nframe`` frames, from the
        shapes whatever implements them.  All counted at 0 too, so
        that a reader finds the counters."""
        stage = self._beam_stage
        if stage is None:
            return
        info = self.impl_info or {}
        from ..telemetry import counters
        shape = self._headers[0]['_tensor']['shape']
        counters.inc('beamform.gulps', ngulps)
        counters.inc('beamform.fused_gulps', ngulps if info.get('impl')
                     == 'pallas-beamform-detect' else 0)
        counters.inc('beamform.word_gulps',
                     ngulps if info.get('input') == 'words' else 0)
        counters.inc('beamform.int8_ops', int(nframe) *
                     stage.engine.ops_per_frame(
                         shape[1], stage.npol if stage.mode == 'perpol'
                         else 1))

    def on_data(self, ispan, ospan):
        if self._gulp_batch_active > 1 and self._macro_gulp_in:
            x = self._take_donatable(ispan, allow_parts=True)
            if x is None:
                parts, donate = [ispan.data], False
            elif isinstance(x, list):
                parts, donate = x, True
            else:
                parts, donate = [x], True
            ospan.set(self._execute_macro(parts, donate,
                                          self._macro_gulp_in),
                      owned=True)
            ngulps = max(1, -(-ispan.nframe // self._macro_gulp_in))
            self._count_transformed(ngulps)
            self._count_beamformed(ngulps, ispan.nframe)
            return
        # a ci8 gulp on one device: the plan starts from its words
        words = self.mesh is None
        x = self._take_donatable(ispan, words=words)
        if x is not None:
            ospan.set(self._execute_plan(x, donate=True), owned=True)
        else:
            x = ispan.words if words else None
            ospan.set(self._execute_plan(ispan.data if x is None else x),
                      owned=True)
        self._count_transformed(1)
        self._count_beamformed(1, ispan.nframe)


def fused(iring, stages, *args, **kwargs):
    """Block: run ``stages`` (see bifrost_tpu.stages) as one fused jitted
    computation per gulp."""
    return FusedBlock(iring, stages, *args, **kwargs)
