"""Compiled pipeline segments: fuse device-block chains into ONE XLA
program and elide the intermediate rings (docs/perf.md, "Compiled
pipeline segments").

Macro-gulp execution (PR 4, :mod:`bifrost_tpu.macro`) amortized the
Python dispatch *per block*: an eligible device block runs one
compiled program over K gulps.  But every block BOUNDARY still costs a
Python dispatch plus a full ring handoff (reserve/commit/acquire/
release and the device array parked in HBM between programs) — even
when both sides are jit-backed device blocks whose composition XLA
would happily fuse.  The TPU-side precedent is the large-scale DFT
work (arXiv:2002.03260): compile the whole multi-stage numerical chain
into a single XLA program scanning over the batch.

The segment compiler closes that last gap.  A pass over the pipeline
graph (run from ``Pipeline.run()``, gated by ``BF_SEGMENTS`` /
``Pipeline(segments=...)``) identifies maximal linear chains of
eligible device blocks — jit-backed ``FusedBlock``/``_StageBlock``
nodes whose intermediate rings have exactly one reader, no taps, no
overlap/ghost history, and no host/bridge/mesh-reshard/supervision
boundary — and replaces each chain with ONE :class:`SegmentBlock`: a
single compiled program that scans the K-gulp macro span (reusing
``macro.build_batched_fn`` slicing) from the segment's head ring
straight to its tail ring.  The interior rings are ELIDED entirely:
no thread writes them, no span is reserved on them, and donation is
threaded straight through the interior buffers (they become jit
temporaries XLA reuses in place).  Rings survive only at supervision,
tap, multi-reader, mesh-reshard, and host boundaries.

Inside a segment: **0 Python dispatches and 0 ring handoffs per
gulp** (tests/test_segments.py: ``test_segment_fuses_and_elides``).

Eligibility is decided by ONE planner (:func:`plan`) shared with the
static verifier: ``analysis.verify`` reports a ``BF-I190`` diagnostic
with this module's reason slug for every boundary that did NOT fuse,
so segments can never form across a boundary the verifier cannot
prove safe — they are the same computation.

Modes (``BF_SEGMENTS`` / ``Pipeline(segments=...)``):

- ``off`` (default) — no planning; byte-identical to the pre-segment
  runtime.
- ``auto`` — fuse every provably-safe maximal chain of >= 2 blocks.
- ``force`` — like ``auto``, but raise at submit time when NO segment
  forms (benches/tests asserting engagement; the error lists every
  boundary's reason).

Observability survives fusion: :mod:`bifrost_tpu.telemetry.segments`
synthesizes per-member compute spans, ``block.<member>.gulps``
counters, and SLO commit ages from the segment's in-dispatch markers,
and the members' perf ProcLogs keep publishing (``like_top`` shows
them alive with the segment's gulps-per-dispatch; ``pipeline2dot``
groups them into one cluster with the elided rings dashed).  Real
dispatch counts stay honest: ``block.*.dispatches`` counts SEGMENTS,
not member blocks.

The closed-loop auto-tuner (docs/autotune.md) gains a
segment-boundary knob: :func:`retune_split` lets it SPLIT a compiled
segment back into N sequentially-dispatched sub-programs (and re-fuse
by reverting) online — one giant program occasionally schedules worse
than two; the knob measures instead of guessing.  Splits change
dispatch count only, never ring topology, and ride the same
verifier-gated retune protocol as every other knob.
"""

from __future__ import annotations

import os

__all__ = ['MODES', 'REASONS', 'resolve_mode', 'plan',
           'compile_pipeline', 'SegmentBlock', 'retune_split',
           'SegmentPlanError']

MODES = ('off', 'auto', 'force')

#: stable fusion-breaking reason slugs (BF-I190 carries them; tests
#: assert them — treat as API like the diagnostic codes themselves)
REASONS = {
    'multi_reader': 'interior ring has more than one reader',
    'tap': 'a block_view tap reads the interior ring through a view',
    'overlap': 'consumer declares overlap/ghost history across gulps '
               'that the chain cannot carry in-program (not a '
               "'block'-mode stage chain, or the declared overlap "
               'does not match the stage-derived lookahead)',
    'overlap_carried': 'consumer overlap/ghost history is carried '
                       'INSIDE the compiled segment (halo carry): the '
                       'boundary fused, the ghost frames ride the '
                       'span head once, and the interior ring is '
                       'elided',
    'host': 'one side is not a jit-backed device stage block',
    'bridge': 'one side is a cross-host bridge endpoint',
    'mesh_reshard': 'the boundary crosses inequivalent mesh scopes',
    'tunables': 'the blocks resolve different scope tunables',
    'supervision': 'a block pins its own failure policy (restart/skip '
                   'blast radius must stay per-block)',
    'unguaranteed': 'the consumer reads unguaranteed',
    'collective': 'the block owns a cross-device collective schedule '
                  '(e.g. the correlator corner turn): its dispatch '
                  'boundary is the collective\'s synchronization '
                  'point and cannot be folded into a neighbour\'s '
                  'program',
    'disabled': 'segment compilation is off (BF_SEGMENTS)',
}


class SegmentPlanError(RuntimeError):
    """Raised by ``force`` mode when no segment forms: every candidate
    boundary's reason is listed so the operator can see exactly which
    constraint broke fusion."""


def resolve_mode(arg=None):
    """Effective segment-compiler mode: ``'off'`` | ``'auto'`` |
    ``'force'``.  ``arg`` is the ``Pipeline(segments=...)`` value;
    ``None`` defers to ``BF_SEGMENTS`` (default off)."""
    if arg is None:
        arg = os.environ.get('BF_SEGMENTS', '')
    if isinstance(arg, str):
        val = arg.strip().lower()
        if val in ('1', 'on', 'auto', 'true', 'yes'):
            return 'auto'
        if val == 'force':
            return 'force'
        return 'off'
    return 'auto' if arg else 'off'


# ---------------------------------------------------------------------------
# planning (shared verbatim with analysis.verify._check_segments)
# ---------------------------------------------------------------------------

def _base(ring):
    return getattr(ring, '_base_ring', ring)


def _stage_chain(block):
    """The jit-backed Stage list ``block`` executes, or None when the
    block is not a pure device stage chain (host blocks, movers,
    sources/sinks, bridges)."""
    from .blocks.fused import device_stages
    return device_stages(block)


def _eligible(block):
    """Whether ``block`` can be a segment MEMBER: a stage-backed
    device block with exactly one 'tpu' input ring and one 'tpu'
    output ring, reading guaranteed."""
    if _stage_chain(block) is None:
        return False
    irings = getattr(block, 'irings', None) or []
    orings = getattr(block, 'orings', None) or []
    if len(irings) != 1 or len(orings) != 1:
        return False
    if _base(irings[0]).space != 'tpu' or \
            _base(orings[0]).space != 'tpu':
        return False
    return bool(getattr(block, 'guarantee', True))


class _FakeSeq(object):
    """Header-less ReadSequence stand-in for the static overlap probe
    (mirrors analysis.verify._FakeSeq)."""
    header = {}


def _static_overlap(block):
    """The consumer's declared input overlap, derivable statically; a
    probe that raises returns None (unknown — conservatively treated
    as overlap)."""
    try:
        seqs = [_FakeSeq() for _ in block.irings]
        ov = list(block._define_input_overlap_nframe(seqs))
        return max(ov) if ov else 0
    except Exception:
        return None


#: tunables carried from the chain head onto the SegmentBlock — the
#: head's OWN pins only (per-block settings are not visible through
#: the parent scope), never the scope-RESOLVED values: a resolved
#: value would pin e.g. sync_depth below the root and silently cut
#: the auto-tuner's root-level retunes (and profile warm starts) off
#: from the fused hot path.  Scope-inherited values keep flowing
#: because the segment is constructed under the head's parent scope.
_CARRIED_TUNABLES = ('core', 'device', 'mesh', 'gulp_nframe',
                     'buffer_factor', 'buffer_nframe', 'sync_depth',
                     'sync_strict')
#: must RESOLVE identically across the chain for fusion (donate /
#: gulp_batch additionally: they are never carried at all, so root
#: retunes reach the segment)
_COMPAT_TUNABLES = _CARRIED_TUNABLES + ('donate', 'gulp_batch')


def _compatible(a, b):
    for t in _COMPAT_TUNABLES:
        va, vb = getattr(a, t), getattr(b, t)
        if va is not vb and va != vb:
            return False
    return True


def _pins_supervision(block):
    """Whether the block pins its OWN failure policy: fusing it would
    widen a deliberately per-block restart/skip blast radius to the
    whole segment."""
    d = block.__dict__
    return any(d.get('_' + k) is not None
               for k in ('on_failure', 'max_restarts',
                         'restart_backoff'))


def _meshes_ok(a, b):
    ma, mb = getattr(a, 'mesh', None), getattr(b, 'mesh', None)
    if ma is None and mb is None:
        return True
    try:
        from .parallel.scope import meshes_equivalent
        return meshes_equivalent(ma, mb)
    except Exception:
        return False


def _is_bridge(block):
    try:
        from .blocks.bridge import BridgeSink, BridgeSource
        return isinstance(block, (BridgeSink, BridgeSource))
    except Exception:
        return False


def _boundary_reason(producer, oring, consumers, mode):
    """Why the boundary at ``producer``'s output ring did not fuse, as
    a :data:`REASONS` slug — or None when it is provably fusable (and
    the mode admits fusion)."""
    if _is_bridge(producer) or any(_is_bridge(c) for c in consumers):
        return 'bridge'
    if len(consumers) != 1:
        return 'multi_reader'
    c = consumers[0]
    if not any(r is oring for r in (getattr(c, 'irings', None) or [])):
        # the sole consumer reads the base ring through a RingView: a
        # tap's header transform would be discarded by fusion
        return 'tap'
    if not getattr(c, 'guarantee', True):
        return 'unguaranteed'
    if getattr(producer, '_collective_boundary', False) or \
            getattr(c, '_collective_boundary', False):
        # more specific than 'host': the block WOULD be device math,
        # but it schedules its own cross-device collective (corner
        # turn / psum meeting point) and must keep the dispatch
        return 'collective'
    if not _eligible(producer) or not _eligible(c):
        return 'host'
    ov = _static_overlap(c)
    if ov is None:
        return 'overlap'
    # halo carry (docs/perf.md): a consumer's declared overlap no
    # longer breaks fusion when the MERGED chain can carry the ghost
    # history in-program — every stage time-concat equivariant
    # ('block' mode, so any span length computes with identical
    # per-frame math), the consumer's declaration matching its
    # stage-derived lookahead exactly, and the merged lookahead
    # converting to a whole head-input frame count.  The merged-chain
    # check also guards the subtler case of a ZERO-overlap boundary
    # downstream of a lookahead stage: fusing a non-equivariant stage
    # behind one would feed it ghost frames it cannot ignore.
    carried = False
    from .macro import chain_batch_mode
    from .stages import chain_overlap_nframe
    merged = (_stage_chain(producer) or []) + (_stage_chain(c) or [])
    merged_ov = chain_overlap_nframe(merged)
    if ov or merged_ov is None or merged_ov != 0:
        if merged_ov is None or \
                chain_batch_mode(merged) != 'block' or \
                chain_overlap_nframe(_stage_chain(c) or []) != ov:
            return 'overlap'
        carried = bool(ov)
    if not _meshes_ok(producer, c):
        return 'mesh_reshard'
    if not _compatible(producer, c):
        return 'tunables'
    if _pins_supervision(producer) or _pins_supervision(c):
        return 'supervision'
    if mode == 'off':
        return 'disabled'
    return 'overlap_carried' if carried else None


def plan(pipeline, mode=None):
    """Walk ``pipeline``'s block/ring graph and return
    ``(chains, boundaries)``:

    - ``chains`` — maximal fusable linear chains (lists of >= 2
      blocks, in stream order) the compiler would replace with one
      :class:`SegmentBlock` (always empty in ``off`` mode);
    - ``boundaries`` — one record per device-ring boundary that did
      NOT fuse: ``{'ring', 'producer', 'consumer', 'reason'}`` with a
      stable :data:`REASONS` slug.  ``analysis.verify`` turns each
      into a ``BF-I190`` diagnostic.

    Pure: the pipeline is never mutated (``compile_pipeline`` applies
    the plan)."""
    if mode is None:
        mode = resolve_mode(getattr(pipeline, 'segments', None))
    blocks = list(pipeline.blocks)
    consumers = {}
    for b in blocks:
        for r in getattr(b, 'irings', None) or []:
            consumers.setdefault(id(_base(r)), []).append(b)
    boundaries = []
    nxt, prev = {}, {}
    for p in blocks:
        orings = getattr(p, 'orings', None) or []
        for oring in orings:
            base = _base(oring)
            cs = consumers.get(id(base), [])
            if not cs:
                continue
            # device rings are the fusion candidates; host rings are
            # only reported when a bridge endpoint sits on them (the
            # cross-host hop is a boundary operators ask about —
            # every other host ring would be reason='host' noise)
            if getattr(base, 'space', None) != 'tpu' and \
                    not (_is_bridge(p) or any(_is_bridge(c)
                                              for c in cs)):
                continue
            reason = _boundary_reason(p, oring, cs, mode)
            if reason is None or reason == 'overlap_carried':
                # 'overlap_carried' boundaries FUSE — the record below
                # is informational (verify maps it to BF-I192), not a
                # break
                nxt[id(p)] = cs[0]
                prev[id(cs[0])] = p
            if reason is not None:
                boundaries.append({
                    'ring': getattr(base, 'name', '?'),
                    'producer': getattr(p, 'name', '?'),
                    'consumer': ','.join(getattr(c, 'name', '?')
                                         for c in cs),
                    'reason': reason})
    chains = []
    for b in blocks:
        if id(b) in nxt and id(b) not in prev:
            chain = [b]
            while id(chain[-1]) in nxt:
                chain.append(nxt[id(chain[-1])])
            chains.append(chain)
    return chains, boundaries


# ---------------------------------------------------------------------------
# the compiled-segment runner
# ---------------------------------------------------------------------------

#: the compiled-segment runner class, built lazily by
#: :func:`_segment_block_cls` (blocks.fused imports pipeline, so a
#: module-level import here would cycle at package init)
SegmentBlock = None


def _segment_block_cls():
    global SegmentBlock
    if SegmentBlock is not None:
        return SegmentBlock
    from .blocks.fused import FusedBlock

    class _SegmentBlock(FusedBlock):
        """One compiled program standing in for a fused chain of
        device blocks.  Inherits the whole FusedBlock execution stack
        — per-gulp and macro plan caches, ``macro.build_batched_fn``
        K-gulp scanning, donation (threaded through the interior
        buffers, which are now jit temporaries), mesh plans, prewarm,
        impl publishing — and adds:

        - member telemetry synthesis (telemetry.segments): per-member
          compute spans, ``block.<member>.gulps`` counters, SLO
          commit ages, and member perf-ProcLog rows, all derived from
          the segment's own dispatch markers;
        - the ``<name>/segment`` ProcLog (member + elided-ring lists)
          pipeline2dot renders as a cluster;
        - the auto-tuner's split knob: ``_segment_split`` (resolved
          per sequence, like macro-K) executes the chain as N+1
          sequential sub-programs instead of one — still ring-free —
          so the tuner can probe whether splitting a boundary
          schedules better, and re-fuse by reverting.
        """

        def __init__(self, iring, stages, members, member_sizes,
                     elided_rings, *args, **kwargs):
            super(_SegmentBlock, self).__init__(iring, stages, *args,
                                                **kwargs)
            #: member block names, in stream order
            self._members = list(members)
            #: stages contributed by each member (split points land
            #: only on member boundaries)
            self._member_sizes = list(member_sizes)
            self._elided = list(elided_rings)
            #: perf ProcLogs of the replaced blocks, kept publishing
            #: so monitors never show a fused block as dead
            self._member_proclogs = []
            #: auto-tuner split knob (segments.retune_split): number
            #: of member boundaries to split the compiled program at;
            #: resolved per sequence
            self._segment_split = 0
            self._splits_active = 0
            self._split_plans = {}
            self._gulp_index = 0
            #: real compiled-program dispatches the LAST on_data
            #: issued (splits+1 when split; consumed once by
            #: _observe_dispatch so skip-path zero-fills count 1)
            self._last_ndispatches = 1
            from .proclog import ProcLog
            ProcLog(self.name + '/segment').update(
                {'nmembers': len(self._members),
                 'members': ','.join(self._members),
                 'elided': ','.join(self._elided),
                 'split': 0}, force=True)

        # -- sequencing ------------------------------------------------
        def on_sequence(self, iseq):
            ohdr = super(_SegmentBlock, self).on_sequence(iseq)
            self._gulp_index = 0
            self._split_plans = {}
            splits = self._resolve_splits()
            if splits != self._splits_active:
                try:
                    from .proclog import ProcLog
                    ProcLog(self.name + '/segment').update(
                        {'split': splits}, force=True)
                except OSError:
                    pass
            self._splits_active = splits
            return ohdr

        def _prewarm(self, ihdr):
            # a split sequence never runs the fused plan: compiling
            # it would be pure wasted latency at sequence start (the
            # part plans build lazily on the first gulp)
            if self._resolve_splits():
                return
            super(_SegmentBlock, self)._prewarm(ihdr)

        def _resolve_splits(self):
            """Active split count for the NEXT sequence: the
            ``_segment_split`` knob clamped to the member-boundary
            count.  Mesh segments never split (the sub-programs would
            need their own in/out shardings per part; the fused mesh
            plan already exists and is the measured-better path).

            Splits compose with a carried halo: a halo-carrying
            segment is 'block'-mode throughout (the fusion rule
            requires it), so every part computes the FULL overlapped
            span — ghost frames propagate part to part and only
            contaminate output frames past the committed stride, which
            go uncommitted.  No per-part halo bookkeeping is needed."""
            if self.mesh is not None:
                return 0
            try:
                n = int(self._segment_split)
            except (TypeError, ValueError):
                n = 0
            return max(0, min(n, len(self._members) - 1))

        # -- split execution -------------------------------------------
        def _split_ranges(self):
            """Stage-index ranges of the active sub-programs: the
            member list divided into ``splits+1`` contiguous groups,
            as evenly as possible, converted to stage indices."""
            from .macro import split_ranges
            return split_ranges(self._member_sizes,
                                self._splits_active)

        def _split_part_plan(self, part, stage_lo, stage_hi, shape,
                             dtype, donate):
            """(Build and) fetch the compiled program for ONE
            sub-chain part at ``shape``: the part's stages composed
            through the same ``compose_stages`` the fused plan uses,
            macro-scanned with ``build_batched_fn`` when a batch is
            active, donating its input when ``donate`` (part 0: the
            claimed gulp; parts > 0: the interior array, exclusively
            ours by construction)."""
            key = (self._splits_active, part, tuple(shape),
                   str(dtype), bool(donate))
            plan = self._split_plans.get(key)
            if plan is not None:
                return plan
            import jax
            from .macro import build_batched_fn, chain_batch_mode
            from .ops.common import donating_jit
            from .stages import compose_stages
            stages = self.stages[stage_lo:stage_hi]
            headers = self._headers[stage_lo:stage_hi + 1]

            def per_shape(s):
                fn, _info = compose_stages(stages, headers, s, dtype)
                return fn

            # this PART's frames-per-gulp: the segment-input gulp
            # advanced through the stages BEFORE the part (a
            # frame-reducing member upstream shrinks the gulps every
            # later part slices by — sliced-mode batching must cut on
            # the part-local gulp boundaries, not the input's)
            gulp = self._macro_gulp_in
            if gulp:
                for st in self.stages[:stage_lo]:
                    gulp = st.output_nframe(gulp)
            if self._gulp_batch_active > 1 and gulp:
                taxis_in = headers[0]['_tensor']['shape'].index(-1)
                taxis_out = headers[-1]['_tensor']['shape'].index(-1)
                mode = chain_batch_mode(stages)
                fn = build_batched_fn(per_shape, taxis_in, taxis_out,
                                      int(gulp), (tuple(shape),),
                                      mode)
            else:
                fn = per_shape(tuple(shape))
            plan = donating_jit(fn, donate_argnums=(0,)) if donate \
                else jax.jit(fn)
            self._split_plans[key] = plan
            return plan

        def _execute_split(self, x, donate_first):
            """Run the chain as ``splits+1`` sequential compiled
            sub-programs (no rings between them — the interior arrays
            flow device-resident and are donated forward).  Returns
            the final output array and the dispatch count."""
            ranges = self._split_ranges()
            for part, (lo, hi) in enumerate(ranges):
                donate = donate_first if part == 0 else True
                plan = self._split_part_plan(part, lo, hi, x.shape,
                                             x.dtype, donate)
                x = self._dispatch_device(plan, (x,))
            return x, len(ranges)

        # -- the hot path ----------------------------------------------
        def on_data(self, ispan, ospan):
            import time
            from .telemetry import segments as _tseg
            from .telemetry import spans as _spans
            t0 = time.perf_counter()
            t0_us = _spans.now_us()
            if self._splits_active:
                x = self._take_donatable(ispan)
                donate_first = x is not None
                if not donate_first:
                    x = ispan.data
                out, ndisp = self._execute_split(x, donate_first)
                ospan.set(out, owned=True)
            else:
                super(_SegmentBlock, self).on_data(ispan, ospan)
                ndisp = 1
            dur_s = time.perf_counter() - t0
            ngulps = 1
            if self._gulp_batch_active > 1 and self._macro_gulp_in:
                # a carried halo rides the span head ONCE — it is
                # history, not an extra gulp's worth of work
                halo = getattr(self, '_macro_overlap_in', 0)
                ngulps = max(1, -(-(ispan.nframe - halo) //
                                  self._macro_gulp_in))
            _tseg.note_dispatch(
                self.name, self._members, ndispatches=ndisp,
                ngulps=ngulps, t0_us=t0_us, dur_us=dur_s * 1e6,
                seq=self._seq_count - 1, gulp=self._gulp_index,
                trace=(self._trace_ctx or {}).get('id'),
                header=self._headers[0] if self._headers else None,
                frame_end=ispan.frame_offset + ispan.nframe)
            self._gulp_index += ngulps
            self._last_ndispatches = ndisp
            self._publish_member_perf(dur_s, ngulps, ndisp)

        def _observe_dispatch(self, ngulps):
            """A split sequence issues splits+1 REAL compiled-program
            dispatches per on_data: keep ``block.<segment>.
            dispatches`` (and the G/D ratio and perf keys derived
            from it) aligned with the ``segment.*`` counters the
            regression sentinel watches — 'dispatches' means Python
            dispatches everywhere, split or fused."""
            extra = max(self._last_ndispatches - 1, 0)
            self._last_ndispatches = 1
            super(_SegmentBlock, self)._observe_dispatch(ngulps)
            if extra:
                from .telemetry import counters
                counters.inc('block.%s.dispatches' % self.name, extra)
                self._n_dispatches += extra

        def _publish_member_perf(self, dur_s, ngulps, ndisp):
            """Keep the replaced blocks' perf ProcLogs publishing:
            like_top rows stay alive, the G/D column shows the
            segment's amortization, and the ``in_segment`` key marks
            membership (rate-limited per member ProcLog)."""
            from .telemetry import segments as _tseg
            if not self._member_proclogs:
                return
            share = dur_s / max(len(self._member_proclogs), 1)
            for name, log in self._member_proclogs:
                _tseg.publish_member_perf(
                    log, self.name, share,
                    gulps_per_dispatch=ngulps / float(max(ndisp, 1)))

        def _perf_stats(self):
            stats = super(_SegmentBlock, self)._perf_stats()
            stats['segment_blocks'] = len(self._members)
            if self._n_dispatches:
                # the live dispatches-per-gulp pipeline2dot labels the
                # cluster with (the inverse of gulps_per_dispatch)
                stats['segment_dispatches_per_gulp'] = round(
                    self._n_dispatches /
                    float(max(self._n_gulps_logical, 1)), 4)
            return stats

    SegmentBlock = _SegmentBlock
    SegmentBlock.__name__ = 'SegmentBlock'
    return SegmentBlock


def retune_split(block, nsplits):
    """Runtime segment-boundary retune — the closed-loop auto-tuner's
    write path (docs/autotune.md).  Sets the segment's split count
    (0 = fully fused; N = the compiled program splits into N+1
    sequentially-dispatched sub-programs at member boundaries) and
    lets the NEXT sequence's ``_resolve_splits`` pick it up; the
    sequence in flight keeps its active plan (a segment's program
    cannot change mid-sequence, exactly like macro-K).  Returns the
    clamped value actually set."""
    n = max(int(nsplits), 0)
    n = min(n, max(len(getattr(block, '_members', [])) - 1, 0))
    block._segment_split = n
    return n


# ---------------------------------------------------------------------------
# application (Pipeline.run's hook)
# ---------------------------------------------------------------------------

def compile_pipeline(pipeline, mode=None):
    """Plan and APPLY segment fusion to ``pipeline``: each fusable
    chain is replaced by one :class:`SegmentBlock` wired from the
    chain head's input ring to the chain tail's output ring; the
    interior rings are elided (they survive as inert construction
    artifacts nobody writes, like auto-fusion's abandoned rings).
    Returns the list of created segments.  ``force`` raises
    :class:`SegmentPlanError` when nothing fuses."""
    mode = resolve_mode(getattr(pipeline, 'segments', None)) \
        if mode is None else mode
    if mode == 'off':
        return []
    chains, boundaries = plan(pipeline, mode)
    # force asserts ENGAGEMENT, not novelty: a pipeline whose segments
    # were already compiled (a test/tuner calling compile_pipeline
    # before run()) has nothing new to fuse and that is success
    if mode == 'force' and not chains and \
            not getattr(pipeline, '_segments', []):
        detail = '; '.join(
            '%s->%s over ring %r: %s'
            % (b['producer'], b['consumer'], b['ring'], b['reason'])
            for b in boundaries) or 'no device-ring boundaries found'
        raise SegmentPlanError(
            'BF_SEGMENTS=force but no compiled segment formed (%s)'
            % detail)
    from . import pipeline as _pl
    from .telemetry import counters
    cls = _segment_block_cls()
    segments = []
    for chain in chains:
        head, tail = chain[0], chain[-1]
        stages, members, member_sizes = [], [], []
        for blk in chain:
            st = _stage_chain(blk)
            stages.extend(st)
            members.append(blk.name)
            member_sizes.append(len(st))
        elided = [getattr(_base(blk.orings[0]), 'name', '?')
                  for blk in chain[:-1]]
        # construct under the head's scope so the SegmentBlock
        # inherits the same tunables, registering with THIS pipeline
        # regardless of the ambient default (the auto-fuse recipe)
        _pl._stacks.pipelines.append(pipeline)
        _pl._stacks.scopes.append(head._parent_scope or pipeline)
        try:
            seg = cls(head.irings[0], stages, members, member_sizes,
                      elided,
                      name='Segment_x%d_%s'
                           % (len(chain), head.name.split('/')[-1]),
                      **{t: head.__dict__.get('_' + t)
                         for t in _CARRIED_TUNABLES})
        finally:
            _pl._stacks.scopes.pop()
            _pl._stacks.pipelines.pop()
        # rewire: the chain tail's output ring becomes the segment's,
        # and its owner must follow (downstream fused-scope buffer
        # sharing and SLO commit attribution read iseq.ring.owner);
        # the segment's self-created ring is abandoned unwritten
        seg.orings = [tail.orings[0]]
        tail.orings[0].owner = seg
        seg._member_proclogs = [(blk.name, blk.perf_proclog)
                                for blk in chain
                                if getattr(blk, 'perf_proclog', None)
                                is not None]
        for blk in chain:
            pipeline.blocks.remove(blk)
            parent = blk._parent_scope
            if parent is not None and blk in parent._children:
                parent._children.remove(blk)
        counters.inc('segment.compiled')
        counters.inc('segment.elided_rings', len(elided))
        # halo-carry engagement signal (tools/telemetry_diff.py watches
        # it): overlap boundaries this chain absorbed in-program — a
        # drop to 0 on a lookahead chain means carry silently
        # disengaged and the chain broke at the overlap instead
        carried = sum(1 for b in boundaries
                      if b['reason'] == 'overlap_carried'
                      and b['producer'] in members)
        if carried:
            counters.inc('segment.overlap_carried', carried)
        segments.append(seg)
    # accumulate: a test/tuner may compile before run() re-plans (the
    # re-plan finds nothing new — compiled segments sit between
    # non-fusable neighbors — but must not clobber the record)
    pipeline._segments = list(getattr(pipeline, '_segments', [])) + \
        segments
    return segments
