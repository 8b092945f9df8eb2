"""Pipeline runtime: scoped configuration, thread-per-block execution,
gulp/overlap negotiation, and data-loss tolerance.

Semantics follow the reference pipeline (reference:
python/bifrost/pipeline.py:84-779): a Pipeline collects Blocks built under
it; ``run()`` launches one OS thread per block; blocks communicate through
rings; a two-phase init barrier aborts cleanly if any block fails to open
its sequences; unguaranteed readers that fall behind zero-fill skipped
frames and force-skip to catch up.

TPU-first differences:

- ``gpu=N`` becomes ``device=N`` (an index into ``jax.devices()``);
  ``gpu=`` is still accepted as an alias.
- Per-gulp synchronization is *lagged*: computed jax arrays are committed
  immediately (readers force them on use) and a bounded queue of pending
  outputs provides backpressure with ``sync_depth`` gulps of dispatch-ahead
  — hiding dispatch latency the way the reference hides it with one
  cudaStreamSynchronize per gulp (reference: pipeline.py:628).
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time
import traceback
import warnings
import queue as queue_mod
from collections import defaultdict, deque
from contextlib import ExitStack
from copy import copy

from . import affinity, device, memory
from .header_standard import (TRACE_CONTEXT_KEY, ensure_trace_context,
                              propagate_trace_context)
from .telemetry import exporter as _metrics_exporter
from .telemetry import histograms as _histograms
from .telemetry import slo as _slo
from .telemetry import spans as _spans
from .ring import Ring, ring_view, EndOfDataStop, RingPoisonedError
from .ndarray import memset_array
from .proclog import ProcLog
from .temp_storage import TempStorage
from .testing import faults

__all__ = ['Pipeline', 'BlockScope', 'Block', 'SourceBlock',
           'MultiTransformBlock', 'TransformBlock', 'SinkBlock',
           'get_default_pipeline', 'get_current_block_scope',
           'block_scope', 'block_view', 'get_ring', 'izip',
           'PipelineInitError', 'EndOfDataStop', 'RingPoisonedError',
           'resolve_donate', 'resolve_sync_depth']


def izip(*iterables):
    """Zip generators, stopping cleanly at first end-of-data
    (reference: pipeline.py:62-67)."""
    while True:
        try:
            yield [next(it) for it in iterables]
        except (EndOfDataStop, StopIteration):
            return


class _Stacks(threading.local):
    def __init__(self):
        self.pipelines = []
        self.scopes = []


_stacks = _Stacks()


def get_default_pipeline():
    if not _stacks.pipelines:
        _stacks.pipelines.append(Pipeline())
        _stacks.scopes.append(_stacks.pipelines[-1])
    return _stacks.pipelines[-1]


def get_current_block_scope():
    if _stacks.scopes:
        return _stacks.scopes[-1]
    get_default_pipeline()
    return _stacks.scopes[-1]


def block_scope(*args, **kwargs):
    return BlockScope(*args, **kwargs)


def resolve_donate(scope):
    """Effective buffer-donation setting for ``scope``: the ``donate``
    tunable when set anywhere in the scope chain, else the BF_DONATE
    environment default (off)."""
    d = scope.donate
    if d is not None:
        return bool(d)
    return os.environ.get('BF_DONATE', '0') == '1'


def resolve_sync_depth(scope):
    """Effective dispatch-ahead depth for ``scope``: the ``sync_depth``
    tunable when set anywhere in the scope chain, else the
    BF_SYNC_DEPTH environment default, else
    :data:`BlockScope.DEFAULT_SYNC_DEPTH`.  Read per gulp by
    ``Block._sync_gulp``, which makes the knob retunable at runtime —
    the closed-loop auto-tuner (docs/autotune.md) adjusts
    ``pipeline._sync_depth`` online and the next drain honors it."""
    d = scope.sync_depth
    if d is None:
        try:
            d = int(os.environ.get('BF_SYNC_DEPTH', '') or
                    BlockScope.DEFAULT_SYNC_DEPTH)
        except ValueError:
            d = BlockScope.DEFAULT_SYNC_DEPTH
    try:
        # 0 is legal: zero run-ahead, a hard drain every gulp (the
        # tightest device-memory bound)
        return max(int(d), 0)
    except (TypeError, ValueError):
        return BlockScope.DEFAULT_SYNC_DEPTH


def resolve_overload_policy(scope):
    """Effective ring overload policy for ``scope``'s OUTPUT rings:
    the ``overload_policy`` tunable when set anywhere in the scope
    chain, else the ``BF_OVERLOAD_POLICY`` environment default, else
    None (leave the ring at its own setting — 'block' unless set
    directly).  Values: 'block' | 'drop_oldest' | 'drop_newest'
    (docs/robustness.md "Overload & degradation"); a bad value raises
    here, at configuration time."""
    p = scope.overload_policy
    if p is None:
        p = os.environ.get('BF_OVERLOAD_POLICY', '').strip() or None
    if p is not None:
        from .ring import Ring
        if p not in Ring.OVERLOAD_POLICIES:
            raise ValueError(
                "Unknown overload policy %r (BF_OVERLOAD_POLICY / "
                "overload_policy scope tunable); expected one of %s"
                % (p, ', '.join(Ring.OVERLOAD_POLICIES)))
    return p


class BlockScope(object):
    """Nestable configuration scope; unset attributes inherit from the
    enclosing scope (reference: pipeline.py:84-162).

    Tunables: gulp_nframe, buffer_nframe, buffer_factor, core, device
    (index into jax.devices(); 'gpu' accepted as alias), mesh (a
    jax.sharding.Mesh for sharded ops within the scope), fuse,
    share_temp_storage, sync_depth (device run-ahead in gulps; default
    DEFAULT_SYNC_DEPTH — peak device memory grows with it), donate
    (opt-in XLA buffer donation of exclusively-owned gulp inputs on
    device blocks; requires single-consumer topology — see
    docs/transfer.md; default off, BF_DONATE=1 enables globally),
    gulp_batch (macro-gulp execution: eligible device blocks
    reserve/acquire K gulps of ring span in one operation and run ONE
    compiled XLA program over the batch, amortizing per-dispatch
    latency K-fold — see bifrost_tpu.macro and docs/perf.md; default
    1, BF_GULP_BATCH sets the global default; ineligible blocks fall
    back to K=1 automatically),
    on_failure ('abort' default | 'restart' | 'skip_sequence' — the
    supervision policy applied when a block's main loop raises, see
    docs/robustness.md), max_restarts / restart_backoff (restart-policy
    budget and exponential-backoff base; defaults BF_RESTART_MAX=3 and
    BF_RESTART_BACKOFF=0.1s),
    overload_policy ('block' default | 'drop_oldest' | 'drop_newest'
    — applied to the block's OUTPUT rings at the reserve path: under
    overload, drop policies shed COUNTED data instead of blocking
    back to capture; BF_OVERLOAD_POLICY sets the global default — see
    docs/robustness.md "Overload & degradation"),
    shed_tolerant (a consuming block's declaration that it accepts
    gapped input from a drop-policy ring; without it a guaranteed
    reader on such a ring is a silent-loss hazard the static verifier
    rejects with BF-E180).
    """

    #: default device run-ahead (gulps) when sync_depth is unset;
    #: the backpressure drain in Block._sync_gulp uses this
    DEFAULT_SYNC_DEPTH = 4

    instance_count = 0

    _TUNABLES = ('gulp_nframe', 'buffer_nframe', 'buffer_factor', 'core',
                 'device', 'mesh', 'share_temp_storage', 'sync_depth',
                 'sync_strict', 'donate', 'gulp_batch', 'on_failure',
                 'max_restarts', 'restart_backoff', 'overload_policy',
                 'shed_tolerant')

    def __init__(self, name=None, gulp_nframe=None, buffer_nframe=None,
                 buffer_factor=None, core=None, gpu=None, device=None,
                 mesh=None, share_temp_storage=False, fuse=False,
                 sync_depth=None, sync_strict=None, donate=None,
                 gulp_batch=None, on_failure=None, max_restarts=None,
                 restart_backoff=None, overload_policy=None,
                 shed_tolerant=None):
        if name is None:
            name = 'BlockScope_%i' % BlockScope.instance_count
            BlockScope.instance_count += 1
        self.name = name
        self._gulp_nframe = gulp_nframe
        self._buffer_nframe = buffer_nframe
        self._buffer_factor = buffer_factor
        self._core = core
        self._device = device if device is not None else gpu
        self._mesh = mesh
        self._share_temp_storage = share_temp_storage
        self._sync_depth = sync_depth
        self._sync_strict = sync_strict
        self._donate = donate
        self._gulp_batch = gulp_batch
        self._on_failure = on_failure
        self._max_restarts = max_restarts
        self._restart_backoff = restart_backoff
        self._overload_policy = overload_policy
        self._shed_tolerant = shed_tolerant
        self._fused = fuse
        self._temp_storage = {}
        self._parent_scope = get_current_block_scope() \
            if not isinstance(self, Pipeline) else None
        if self._parent_scope is not None:
            self._parent_scope._children.append(self)
            self.name = self._parent_scope.name + '/' + self.name
        self._children = []

    def __enter__(self):
        _stacks.scopes.append(self)
        return self

    def __exit__(self, typ, value, tb):
        popped = _stacks.scopes.pop()
        assert popped is self

    def __getattr__(self, name):
        # Inherit unset tunables from the parent scope.
        if name.startswith('_') or name not in BlockScope._TUNABLES:
            raise AttributeError(name)
        value = self.__dict__.get('_' + name)
        if value is not None:
            return value
        parent = self.__dict__.get('_parent_scope')
        if parent is not None:
            return getattr(parent, name)
        return None

    # alias for reference compatibility
    @property
    def gpu(self):
        return self.device

    # -- scope hierarchy ---------------------------------------------------
    def _scope_hierarchy(self):
        out, parent = [], self._parent_scope
        while parent is not None:
            out.append(parent)
            parent = parent._parent_scope
        return list(reversed(out))

    def cache_scope_hierarchy(self):
        self.scope_hierarchy = self._scope_hierarchy()
        self.fused_ancestor = None
        for ancestor in self.scope_hierarchy:
            if ancestor._fused:
                self.fused_ancestor = ancestor
                break

    def is_fused_with(self, other):
        return (self.fused_ancestor is not None and
                self.fused_ancestor is getattr(other, 'fused_ancestor', None))

    # -- temp storage ------------------------------------------------------
    def _own_temp_storage(self, space):
        if space not in self._temp_storage:
            self._temp_storage[space] = TempStorage(space)
        return self._temp_storage[space]

    def get_temp_storage(self, space):
        for scope in getattr(self, 'scope_hierarchy', self._scope_hierarchy()):
            if scope.share_temp_storage:
                return scope._own_temp_storage(space)
        return self._own_temp_storage(space)

    # -- visualization -----------------------------------------------------
    def dot_graph(self):
        """Graphviz DOT source of the block/ring graph
        (reference: pipeline.py:163-201)."""
        lines = ['digraph "%s" {' % self.name]
        space_colors = {'system': 'orange', 'tpu': 'limegreen',
                        'tpu_host': 'deepskyblue'}

        def walk(scope):
            for child in scope._children:
                if isinstance(child, Block):
                    lines.append('  "%s" [shape=box,style=filled,'
                                 'fillcolor=white];' % child.name)
                    for oring in child.orings:
                        lines.append('  "%s" [shape=ellipse,style=filled,'
                                     'fillcolor=%s];'
                                     % (oring.name,
                                        space_colors.get(oring.space,
                                                         'white')))
                        lines.append('  "%s" -> "%s";'
                                     % (child.name, oring.name))
                    for iring in child.irings:
                        lines.append('  "%s" -> "%s";'
                                     % (iring.name, child.name))
                else:
                    walk(child)

        walk(self)
        lines.append('}')
        return '\n'.join(lines)


class PipelineInitError(Exception):
    pass


def _try_join(thread, timeout=0.):
    thread.join(timeout)
    return not thread.is_alive()


def join_all(threads, timeout):
    deadline = time.time() + timeout
    alive = list(threads)
    while True:
        alive = [t for t in alive if not _try_join(t)]
        remaining = max(deadline - time.time(), 0)
        if not alive or remaining == 0:
            return alive
        alive[0].join(min(remaining, 0.5))


class Pipeline(BlockScope):
    """Collects blocks and runs each in its own thread
    (reference: pipeline.py:221-293)."""

    instance_count = 0

    def __init__(self, name=None, auto_fuse=None, watchdog_secs=None,
                 segments=None, **kwargs):
        if name is None:
            name = 'Pipeline_%i' % Pipeline.instance_count
            Pipeline.instance_count += 1
        super(Pipeline, self).__init__(name=name, **kwargs)
        if auto_fuse is None:
            auto_fuse = os.environ.get('BF_AUTO_FUSE',
                                       '0').strip() == '1'
        self.auto_fuse = auto_fuse
        #: segment-compiler mode (bifrost_tpu.segments; docs/perf.md
        #: "Compiled pipeline segments"): None defers to BF_SEGMENTS
        #: (default off), 'auto' fuses every provably-safe chain of
        #: device blocks into ONE compiled program and elides the
        #: interior rings, 'force' additionally raises when no
        #: segment forms
        self.segments = segments
        #: SegmentBlocks created by the compiler pass (run())
        self._segments = []
        #: stall-watchdog window in seconds (None: BF_WATCHDOG_SECS or
        #: off) — see docs/robustness.md
        self.watchdog_secs = watchdog_secs
        self.blocks = []
        self.threads = []
        self.shutdown_timeout = 5.
        #: the failure-policy engine; created by run()
        self.supervisor = None
        self._shutting_down = False
        self.all_blocks_finished_initializing_event = threading.Event()
        self.block_init_queue = queue_mod.Queue()

    def as_default(self):
        _stacks.pipelines.append(self)
        _stacks.scopes.append(self)

    def synchronize_block_initializations(self):
        """Init barrier: every block must open its output sequences before
        any block starts processing; a failed block aborts the pipeline
        (reference: pipeline.py:236-248)."""
        uninitialized = set(self.blocks)
        while uninitialized:
            block, ok = self.block_init_queue.get()
            uninitialized.discard(block)
            if not ok:
                self.shutdown()
                detail = ''
                if self.supervisor is not None:
                    recorded = self.supervisor.failures_for(block.name)
                    if recorded:
                        detail = '\n' + recorded[-1].traceback.rstrip()
                raise PipelineInitError(
                    "The following block failed to initialize: %s%s"
                    % (block.name, detail))
        self.all_blocks_finished_initializing_event.set()

    def _auto_fuse(self):
        """Collapse chains of adjacent single-Stage transform blocks
        into ONE FusedBlock each (one jitted computation per gulp, no
        intermediate ring traffic) — the pipeline-level analogue of
        XLA's op fusion.  A reference-style pipeline written as
        separate fft/detect/reduce blocks gets the fused chain's
        performance (and the Pallas spectrometer substitution, when
        the pattern matches) without rewriting to ``blocks.fused``.

        Opt-in: ``Pipeline(auto_fuse=True)`` or ``BF_AUTO_FUSE=1``.
        Chains only merge when the interior ring has exactly one
        consumer, no ``block_view`` tap, and every block resolves the
        same scope tunables (core/device/mesh/gulp...).  The replaced
        blocks never start threads; the FusedBlock writes into the
        chain tail's existing output ring so downstream blocks keep
        their references.  (The tail blocks' pre-created rings and
        ProcLog directories remain as inert artifacts of
        construction.)
        """
        from .blocks.fft import _StageBlock
        from .blocks.fused import FusedBlock

        def fusable(b):
            # device rings only: some stage blocks (reduce) also run a
            # host numpy path on 'system' rings, which cannot fuse
            return (isinstance(b, _StageBlock)
                    and len(b.irings) == 1 and len(b.orings) == 1
                    and b.irings[0].space == 'tpu'
                    and getattr(b, 'guarantee', True))

        tunables = ('core', 'device', 'mesh', 'gulp_nframe',
                    'buffer_factor', 'buffer_nframe', 'sync_depth',
                    'sync_strict')

        def compatible(a, b):
            for t in tunables:
                va, vb = getattr(a, t), getattr(b, t)
                if va is not vb and va != vb:
                    return False
            return True

        # key by the UNDERLYING ring: a block_view consumer reads
        # through a RingView whose identity differs from the producer's
        # oring, and a viewed interior ring must block fusion
        def base_ring(r):
            return getattr(r, '_base_ring', r)

        consumers = {}
        for b in self.blocks:
            for r in getattr(b, 'irings', ()):
                consumers.setdefault(id(base_ring(r)), []).append(b)

        def sole_consumer(prod):
            lst = consumers.get(id(base_ring(prod.orings[0])), [])
            if len(lst) != 1:
                return None
            # the sole consumer must read the ring DIRECTLY — a view
            # implies a header transform fusion would discard
            nxt = lst[0]
            direct = any(r is prod.orings[0] for r in nxt.irings)
            return nxt if direct else None

        chains = []
        in_chain = set()
        for b in self.blocks:
            if not fusable(b) or id(b) in in_chain:
                continue
            prod = getattr(b.irings[0], 'owner', None)
            if (prod is not None and fusable(prod)
                    and sole_consumer(prod) is b
                    and compatible(prod, b)):
                continue                  # interior of another chain
            chain = [b]
            while True:
                nxt = sole_consumer(chain[-1])
                if (nxt is not None and fusable(nxt)
                        and id(nxt) not in in_chain
                        and compatible(chain[-1], nxt)):
                    chain.append(nxt)
                else:
                    break
            if len(chain) >= 2:
                chains.append(chain)
                in_chain.update(id(x) for x in chain)

        for chain in chains:
            head, tail = chain[0], chain[-1]
            # construct under the head's scope so the FusedBlock
            # inherits the same tunables, registering with THIS
            # pipeline regardless of the ambient default
            _stacks.pipelines.append(self)
            _stacks.scopes.append(head._parent_scope or self)
            try:
                # carry the chain's RESOLVED tunables explicitly:
                # per-block settings (device=1 on the blocks
                # themselves) are not visible through the parent scope
                fb = FusedBlock(
                    head.irings[0], [blk._stage for blk in chain],
                    name='AutoFused_x%d_%s'
                         % (len(chain), head.name.split('/')[-1]),
                    **{t: getattr(head, t) for t in tunables})
            finally:
                _stacks.scopes.pop()
                _stacks.pipelines.pop()
            # rewire: the chain tail's output ring becomes fb's, and
            # its owner must follow (downstream fused-scope
            # buffer-sharing reads iseq.ring.owner); fb's self-created
            # ring is abandoned before anyone writes to it
            fb.orings = [tail.orings[0]]
            tail.orings[0].owner = fb
            for blk in chain:
                self.blocks.remove(blk)
                parent = blk._parent_scope
                if parent is not None and blk in parent._children:
                    parent._children.remove(blk)

    def run(self, autotune=None):
        """Launch every block thread and supervise them to completion.

        Failure semantics (docs/robustness.md): a block that raises is
        handled per its ``on_failure`` policy; a fatal failure poisons
        every ring (waking all blocked peers), winds the pipeline down
        within ``shutdown_timeout``, and re-raises here as
        :class:`~bifrost_tpu.supervision.PipelineRuntimeError` carrying
        the original traceback.  KeyboardInterrupt triggers a clean
        ``shutdown()``.  The stall watchdog is armed when
        ``watchdog_secs`` / ``BF_WATCHDOG_SECS`` is set.

        ``autotune`` starts the closed-loop auto-tuner
        (:mod:`bifrost_tpu.autotune`, docs/autotune.md): ``True`` (or
        ``BF_AUTOTUNE=1`` when left ``None``) retunes the hot-path
        knobs online from live telemetry; ``'freeze'`` (or
        ``BF_AUTOTUNE=freeze``) additionally pins the converged
        configuration and dumps it as a reusable JSON profile
        (``BF_AUTOTUNE_PROFILE``); ``False`` forces it off regardless
        of the environment.
        """
        from .supervision import Supervisor
        if self.auto_fuse:
            self._auto_fuse()
        # segment compiler (bifrost_tpu.segments; docs/perf.md
        # "Compiled pipeline segments"): fuse maximal provably-safe
        # chains of device blocks into ONE compiled program each and
        # elide the interior rings — 0 Python dispatches and 0 ring
        # handoffs per gulp inside a segment.  Runs BEFORE validation
        # so lint/strict modes judge the graph that will actually
        # execute; the verifier reports a BF-I190 reason for every
        # boundary that did not fuse (same planner, docs/analysis.md).
        from . import segments as _segments
        if _segments.resolve_mode(self.segments) != 'off':
            _segments.compile_pipeline(self)
        # lint mode (tools/bf_lint.py): validate the constructed graph,
        # report, and return WITHOUT launching anything — scripts run
        # end to end as pure topology builders
        if os.environ.get('BF_LINT', '').strip() == '1':
            from .analysis import verify as _verify
            _verify.lint_intercept(self)
            return
        # static pipeline verifier (docs/analysis.md): BF_VALIDATE=warn
        # (default) reports misconfigurations to stderr and the
        # analysis/verify ProcLog; strict refuses to start on any BF-E
        from .analysis import verify as _verify
        _vmode = _verify.validate_mode()
        if _vmode != 'off':
            _verify.gate_run(self, _vmode)
        # device-space pipelines: create the jax backend client from
        # THIS thread first, so a backend that cannot start fails here
        # on the caller's thread, not inside a block thread
        if any(r.space != 'system'
               for b in self.blocks
               for r in (getattr(b, 'irings', None) or []) +
                        (getattr(b, 'orings', None) or [])):
            from .device import ensure_backend
            ensure_backend()
        faults.arm_from_env()
        # honor BF_TRACE_FILE / BF_SPAN_BUFFER / BF_SLO_MS changes made
        # since the last run (tests, long-lived operator processes),
        # and drop dead threads' span buffers so this run's trace
        # export / flight record is not contaminated by earlier runs
        _spans.reconfigure()
        _spans.prune_dead_buffers()
        _spans.watch_jax()       # jit.compile spans from here on
        _slo.reset_budget()
        # honor BF_RINGCHECK toggles between runs the same way
        # (bifrost_tpu.analysis.ringcheck; docs/analysis.md)
        from .analysis import ringcheck as _ringcheck
        _ringcheck.reconfigure()
        self._shutting_down = False
        self.supervisor = Supervisor(self)
        # closed-loop auto-tuner (docs/autotune.md): reads
        # telemetry.snapshot(rates=...) and retunes gulp_batch /
        # sync_depth / bridge windows / ring capacity online through
        # the safe retune protocol; every decision lands on the
        # autotune.* counters + the analysis/autotune proclog.
        # Started BEFORE the block threads so a warm-start profile
        # (the last converged config) is applied before the first
        # sequence resolves its per-sequence tunables — otherwise the
        # first sequence races the profile and can run de-tuned
        from . import autotune as _autotune
        tuner = _autotune.maybe_start(self, autotune)
        try:
            self.threads = [threading.Thread(target=block.run,
                                             name=block.name)
                            for block in self.blocks]
            for block, thread in zip(self.blocks, self.threads):
                block._thread = thread
                thread.daemon = True
                thread.start()
            self.synchronize_block_initializations()
            self.supervisor.start_watchdog(self.watchdog_secs)
            # pipeline health state machine (docs/robustness.md):
            # OK/DEGRADED/SHEDDING/STALLED/FAILED derived from the
            # live SLO/shed/restart/heartbeat signals, published to
            # pipeline/health and exposed as Pipeline.health()
            self.supervisor.start_health()
            # periodic metrics publisher: telemetry/metrics +
            # rings_flow/<name> proclogs, BF_METRICS_FILE Prometheus
            # textfile (docs/observability.md)
            metrics = _metrics_exporter.MetricsPublisher(self)
            metrics.start()
        except BaseException:
            # init failed before the main join/finally below: don't
            # leave the already-started controller ticking against a
            # pipeline that never ran
            if tuner is not None:
                tuner.stop(wait=False)
            raise
        # Join in short slices (not one unbounded join): dead threads
        # are detected promptly, KeyboardInterrupt is serviced between
        # slices, and a fatal failure bounds the wind-down wait at
        # shutdown_timeout instead of hanging forever.
        abort_deadline = None
        try:
            alive = list(self.threads)
            while alive:
                alive[0].join(timeout=0.2)
                alive = [t for t in alive if t.is_alive()]
                if alive and self.supervisor.abort_event.is_set():
                    if abort_deadline is None:
                        abort_deadline = time.monotonic() + \
                            self.shutdown_timeout
                    elif time.monotonic() >= abort_deadline:
                        for t in alive:
                            warnings.warn(
                                "Thread %s did not shut down in time "
                                "after pipeline abort" % t.name,
                                RuntimeWarning)
                        break
        except KeyboardInterrupt:
            # leave no daemon threads behind: wake + wind down
            self.shutdown()
            raise
        finally:
            self.supervisor.stop_watchdog()
            self.supervisor.stop_health()
            if tuner is not None:
                tuner.stop()             # publishes the final knob state
            metrics.stop()               # publishes one final snapshot
            _spans.export_if_configured()
        self.supervisor.raise_if_failed()

    def validate(self):
        """Run the static pipeline verifier over the constructed
        block/ring graph WITHOUT running anything and return the list
        of :class:`~bifrost_tpu.analysis.verify.Diagnostic`
        (stable-coded ``BF-Exxx``/``BF-Wxxx``/``BF-Ixxx`` findings —
        docs/analysis.md has the catalog).  ``run()`` calls this
        automatically per ``BF_VALIDATE={off,warn,strict}``; note that
        auto-fusion (``auto_fuse``) and the segment compiler
        (``segments``/``BF_SEGMENTS``) rewrite the graph inside
        ``run`` BEFORE its validation pass, so a standalone
        ``validate()`` sees the pre-fusion topology — with a BF-I190
        info naming each boundary the segment compiler would (or
        could not) fuse."""
        from .analysis import verify
        return verify.verify_pipeline(self)

    def health(self):
        """Current pipeline health (docs/robustness.md "Overload &
        degradation"): ``{'state': 'OK'|'DEGRADED'|'SHEDDING'|
        'STALLED'|'FAILED', 'since': unix_ts, 'blocks': {name:
        state}, 'transitions': [...]}`` — the supervisor's health
        state machine, derived from the live SLO ages, shed counters,
        restart/reconnect records, and block heartbeats, with
        hysteresis so transient bursts don't flap.  Callable from any
        thread while ``run()`` is live (the monitor keeps it current);
        before/after a run it evaluates the signals on demand."""
        supervisor = getattr(self, 'supervisor', None)
        if supervisor is None:
            return {'state': 'OK', 'since': None,
                    'blocks': {b.name: 'OK' for b in self.blocks},
                    'transitions': []}
        return supervisor.health_snapshot()

    def shutdown(self):
        self._shutting_down = True
        for block in self.blocks:
            block.shutdown()
        # wake threads blocked inside ring waits: a shutdown event
        # alone cannot interrupt reserve/acquire, so poison the rings
        # (block threads treat poison-during-shutdown as clean exit)
        cause = RuntimeError("pipeline shutdown")
        for block in self.blocks:
            for ring in (list(getattr(block, 'orings', ())) +
                         list(getattr(block, 'irings', ()))):
                try:
                    ring.poison(cause)
                except Exception:
                    pass
        self.all_blocks_finished_initializing_event.set()
        join_all(self.threads, timeout=self.shutdown_timeout)
        for thread in self.threads:
            if thread.is_alive():
                warnings.warn("Thread %s did not shut down in time"
                              % thread.name, RuntimeWarning)

    def shutdown_on_signals(self, signals=None):
        if signals is None:
            signals = [signal.SIGHUP, signal.SIGINT, signal.SIGQUIT,
                       signal.SIGTERM, signal.SIGTSTP]
        for sig in signals:
            signal.signal(sig, self._handle_signal_shutdown)

    def _handle_signal_shutdown(self, signum, frame):
        warnings.warn("Received signal %d, shutting down pipeline" % signum,
                      RuntimeWarning)
        self.shutdown()

    def __enter__(self):
        _stacks.pipelines.append(self)
        _stacks.scopes.append(self)
        return self

    def __exit__(self, typ, value, tb):
        _stacks.scopes.pop()
        popped = _stacks.pipelines.pop()
        assert popped is self


def get_ring(block_or_ring):
    try:
        return block_or_ring.orings[0]
    except AttributeError:
        return block_or_ring


def block_view(block, header_transform):
    """A view of ``block`` whose output headers are transformed on the fly
    (reference: pipeline.py:305-322)."""
    new_block = copy(block)
    new_block.orings = [ring_view(oring, header_transform)
                        for oring in new_block.orings]
    return new_block


class Block(BlockScope):
    """Base class: ring ownership, thread entry, proclogs
    (reference: pipeline.py:324-434)."""

    instance_counts = defaultdict(lambda: 0)

    def __init__(self, irings, name=None, type_=None, **kwargs):
        self.type = type_ or self.__class__.__name__
        self.name = name or ('%s_%i'
                             % (self.type, Block.instance_counts[self.type]))
        Block.instance_counts[self.type] += 1
        super(Block, self).__init__(name=self.name, **kwargs)
        self.pipeline = get_default_pipeline()
        self.pipeline.blocks.append(self)

        self.irings = [get_ring(iring) for iring in irings]
        for i, (iring, valid) in enumerate(
                zip(self.irings, self._define_valid_input_spaces())):
            if not memory.space_accessible(iring.space, valid):
                raise ValueError(
                    "Block %s input %d's space (%s) must be accessible "
                    "from one of: %s" % (self.name, i, iring.space, valid))
        self.orings = []   # set by subclasses
        self.shutdown_event = threading.Event()
        #: supervision state: the thread running this block (set by
        #: Pipeline.run) and the heartbeat the stall watchdog reads
        self._thread = None
        self._hb_time = None
        self._hb_gulps = 0
        #: per-block latency histograms, created on first gulp
        self._h_gulp = None
        self._h_wait = None
        #: dispatch amortization observability (macro-gulp execution):
        #: one XLA/host dispatch may cover several logical gulps
        self._h_batch = None
        self._n_dispatches = 0
        self._n_gulps_logical = 0
        #: macro-gulp state for the CURRENT sequence (set per sequence
        #: by MultiTransformBlock._process_sequence; 1 = off)
        self._gulp_batch_active = 1
        self._macro_gulp_in = None
        #: mesh width of the executing plan (blocks running sharded
        #: plans set this when they publish impl info; 1 = one device).
        #: Rendered as like_top's Shd column from the perf proclog.
        self._shards_active = 1
        #: GEMM-class ops accounting: real ops per logical gulp of the
        #: current sequence (beamform/correlate blocks set this at
        #: on_sequence); published as the gemm_gops_per_s perf key and
        #: rendered as like_top's GOP/s column (docs/perf.md).  0 = not
        #: a GEMM-class block.
        self._gemm_ops = 0
        #: trace context of the CURRENT sequence (docs/observability.md
        #: "Distributed tracing & SLOs"): stamped by stream-origin
        #: blocks, propagated input->output by transforms/sinks, and
        #: carried in compute-span args so one gulp is traceable
        #: across blocks, pipelines, and hosts
        self._trace_ctx = None
        #: pipeline health state machine (docs/robustness.md
        #: "Overload & degradation"): kept current by the supervisor's
        #: health monitor — blocks may consult it per gulp (or
        #: override :meth:`on_health`) to cheapen work under pressure
        self.health_state = 'OK'
        self.bind_proclog = ProcLog(self.name + '/bind')
        self.in_proclog = ProcLog(self.name + '/in')
        rnames = {'nring': len(self.irings)}
        for i, r in enumerate(self.irings):
            rnames['ring%i' % i] = r.name
        self.in_proclog.update(rnames)
        self.init_trace = ''.join(traceback.format_stack()[:-1])

    def shutdown(self):
        self.shutdown_event.set()

    def heartbeat(self):
        """Record forward progress for the stall watchdog (called once
        per gulp via _sync_gulp and at sequence boundaries)."""
        self._hb_time = time.monotonic()
        self._hb_gulps += 1

    def on_health(self, state, prev):
        """Degraded-mode hook (docs/robustness.md): called by the
        supervisor's health monitor when this block's health state
        transitions (e.g. OK -> DEGRADED under SLO pressure, ->
        SHEDDING when its rings start dropping).  Blocks override it
        to cheapen work under pressure — skip optional taps, coarsen
        an integration, pause a debug export — and to restore full
        work on the way back to OK.  Called from the monitor thread;
        must be quick and must not raise (exceptions are swallowed
        and counted on ``health.hook_errors``)."""

    # -- observability (docs/observability.md) ----------------------------
    def _compute_span(self, seq, gulp):
        """Gulp-identity compute span: every gulp is traceable across
        blocks by its (sequence, gulp_index) args — and, when the
        stream carries a trace context, across PIPELINES AND HOSTS by
        the stream-unique trace id (tools/trace_merge.py joins on the
        (trace, seq, gulp) triple)."""
        if self._trace_ctx is not None:
            return _spans.timed(self.name + '.on_data', 'compute',
                                seq=seq, gulp=gulp,
                                trace=self._trace_ctx.get('id'))
        return _spans.timed(self.name + '.on_data', 'compute',
                            seq=seq, gulp=gulp)

    def _observe_exit_age(self, iheader, frame_end):
        """Capture->pipeline-exit SLO observation (sink blocks: the
        data is leaving the pipeline here).  No-op without a
        trace-context origin in the input header.  Streams that
        crossed >= 1 bridge hop additionally record the FABRIC
        end-to-end age (``slo.fabric_exit_age_s``): the same exit
        instant aged against the ORIGIN host's capture timestamp,
        skew-corrected by the per-hop handshake clock pings
        (docs/fabric.md)."""
        age = _slo.capture_age_s(iheader, frame_end)
        if age is not None:
            _slo.observe_exit(self.name, age)
            ctx = self._trace_ctx or {}
            if ctx.get('hops'):
                _slo.observe_fabric_exit(self.name, age)

    def _observe_gulp(self, acquire, reserve, process):
        """Record this gulp into the block's latency histograms
        (``block.<name>.gulp_s`` wall time, ``block.<name>.ring_wait_s``
        flow-control time)."""
        if self._h_gulp is None:
            self._h_gulp = _histograms.get_or_create(
                'block.%s.gulp_s' % self.name, unit='s')
            self._h_wait = _histograms.get_or_create(
                'block.%s.ring_wait_s' % self.name, unit='s')
        self._h_gulp.record(acquire + reserve + process)
        self._h_wait.record(acquire + reserve)

    def _observe_dispatch(self, ngulps):
        """Record one on_data dispatch covering ``ngulps`` logical
        gulps: the ``block.<name>.dispatches`` / ``block.<name>.gulps``
        counters and the batch-size histogram make dispatches-per-gulp
        observable (macro-gulp execution amortizes K gulps into one
        dispatch; K=1 blocks record 1:1)."""
        from .telemetry import counters
        ngulps = max(int(ngulps), 1)
        self._n_dispatches += 1
        self._n_gulps_logical += ngulps
        counters.inc('block.%s.dispatches' % self.name)
        counters.inc('block.%s.gulps' % self.name, ngulps)
        if self._h_batch is None:
            self._h_batch = _histograms.get_or_create(
                'block.%s.batch_gulps' % self.name, unit='gulps')
        self._h_batch.record(ngulps)

    def _perf_stats(self):
        """Percentile columns for the perf proclog (rendered by
        tools/like_top.py)."""
        if self._h_gulp is None:
            return {}
        stats = {'gulp_p50': round(self._h_gulp.percentile(50), 6),
                 'gulp_p99': round(self._h_gulp.percentile(99), 6),
                 'ring_wait_p99': round(self._h_wait.percentile(99), 6)}
        if self._n_dispatches:
            stats['gulps_per_dispatch'] = round(
                self._n_gulps_logical / float(self._n_dispatches), 3)
        if self._shards_active > 1:
            stats['shards'] = int(self._shards_active)
        # GEMM-class throughput (like_top's GOP/s column): the block's
        # declared real-op count per logical gulp over the median gulp
        # time — the per-chip ops/s the beamform/correlate bench rows
        # publish, live
        if self._gemm_ops and stats.get('gulp_p50', 0) > 0:
            stats['gemm_gops_per_s'] = round(
                self._gemm_ops / stats['gulp_p50'] / 1e9, 3)
        # capture-to-commit age p99 (telemetry.slo; like_top's Age99
        # column): transforms age at their output-ring commits, sinks
        # at pipeline exit
        h_age = _histograms.get('slo.%s.commit_age_s' % self.name) \
            or _histograms.get('slo.%s.exit_age_s' % self.name)
        if h_age is not None and h_age.count:
            stats['commit_age_p99'] = round(h_age.percentile(99), 6)
        return stats

    def create_ring(self, *args, **kwargs):
        return Ring(*args, owner=self, **kwargs)

    def run(self):
        if self.core is not None:
            affinity.set_core(self.core if isinstance(self.core, int)
                              else self.core[0])
        self.bind_proclog.update({'ncore': 1, 'core0': affinity.get_core()})
        # Re-publish ring wiring now that it is final: subclasses may
        # replace self.orings after construction (copy to another
        # space, SinkBlock dropping outputs), and the monitor tools
        # (like_ps/pipeline2dot) reconstruct the graph from these.
        for log, rings in ((self.in_proclog, self.irings),
                           (getattr(self, 'out_proclog', None),
                            self.orings)):
            if log is not None:
                rnames = {'nring': len(rings)}
                for i, r in enumerate(rings):
                    rnames['ring%i' % i] = r.name
                log.update(rnames, force=True)
        if self.device is not None:
            device.set_device(self.device)
        self.cache_scope_hierarchy()
        # overload policy (docs/robustness.md "Overload &
        # degradation"): resolve the scope tunable / BF_OVERLOAD_POLICY
        # onto this block's OUTPUT rings — the reserve path in both
        # ring cores then sheds (counted) instead of blocking when a
        # drop policy is configured
        _policy = resolve_overload_policy(self)
        if _policy is not None:
            for oring in self.orings:
                getattr(oring, '_base_ring',
                        oring).set_overload_policy(_policy)
        self._hb_time = time.monotonic()
        with ExitStack() as oring_stack:
            # The writing session is held open across restart attempts:
            # ending it between attempts would feed downstream a clean
            # end-of-data and dissolve the stream mid-recovery.
            active_orings = self.begin_writing(oring_stack, self.orings)
            self._supervised_main(active_orings)

    def _supervised_main(self, active_orings):
        """Run main() under the pipeline's failure policies.

        - normal return / clean end-of-data: done
        - RingPoisonedError: a peer died (or shutdown is winding us
          down) — propagate poison downstream and exit
        - anything else: apply the block's on_failure policy via the
          supervisor (abort / restart-with-backoff; skip_sequence is
          handled INSIDE main at sequence granularity)
        """
        supervisor = getattr(self.pipeline, 'supervisor', None)
        restarts = 0
        while True:
            try:
                faults.fire('block.run', self.name)
                self.main(active_orings)
                # a block can finish without ever opening a sequence
                # (empty input, every sequence skipped): release the
                # init barrier anyway (duplicates are discarded)
                self.pipeline.block_init_queue.put((self, True))
                if supervisor is not None:
                    supervisor.block_finished(self)
                return
            except RingPoisonedError as exc:
                if supervisor is not None:
                    supervisor.block_poisoned(self, exc)
                self._poison_orings(exc)
                # pre-barrier poison: unblock the init synchronization
                # (unless a clean shutdown() is already doing so)
                if (not self.pipeline.
                        all_blocks_finished_initializing_event.is_set()
                        and not getattr(self.pipeline,
                                        '_shutting_down', False)):
                    self.pipeline.block_init_queue.put((self, False))
                return
            except Exception as exc:
                if supervisor is not None and \
                        not self.shutdown_event.is_set():
                    decision, delay = supervisor.block_failed(
                        self, exc, restarts)
                    if decision == 'restart':
                        restarts += 1
                        # interruptible backoff: shutdown cancels it
                        if not self.shutdown_event.wait(delay):
                            continue
                        return
                # terminal: unblock the init barrier (consumed only
                # pre-barrier), wake downstream, and keep the
                # historical stderr trace for debugging
                self.pipeline.block_init_queue.put((self, False))
                self._poison_orings(exc)
                sys.stderr.write("From block instantiated here:\n")
                sys.stderr.write(self.init_trace)
                if supervisor is None:
                    raise
                traceback.print_exc()
                return

    def _poison_orings(self, exc):
        """Wake downstream consumers with RingPoisonedError instead of
        leaving them blocked on a ring that will never be fed."""
        for oring in self.orings:
            try:
                oring.poison(exc)
            except Exception:
                pass

    def _failure_policy(self):
        return getattr(self, 'on_failure', None) or 'abort'

    def _may_skip(self):
        """Whether a skip_sequence policy can absorb a failure HERE:
        only once the init barrier has been released.  Skipping a
        block's very first sequence would leave downstream blocks
        without any sequence to open and deadlock the barrier, so
        earlier failures escalate to the block's terminal path."""
        return (self._failure_policy() == 'skip_sequence' and
                self.pipeline.
                all_blocks_finished_initializing_event.is_set())

    def num_outputs(self):
        return len(self.orings)

    def begin_writing(self, exit_stack, orings):
        return [exit_stack.enter_context(oring.begin_writing())
                for oring in orings]

    def begin_sequences(self, exit_stack, orings, oheaders,
                        igulp_nframes, istride_nframes, batch=1):
        # The output header's gulp_nframe excludes overlap (stride-based;
        # reference: pipeline.py:383-399).  Under macro-gulp execution
        # (batch > 1) the passed nframes are MACRO values: the ring is
        # sized for the K-gulp span, but the header advertises the
        # LOGICAL gulp so downstream blocks' defaults (and their own
        # macro eligibility) are unchanged by this block's batching.
        ostride_nframes = self._define_output_nframes(istride_nframes)
        for ohdr, ostride in zip(oheaders, ostride_nframes):
            ohdr['gulp_nframe'] = ostride // batch
        ogulp_nframes = self._define_output_nframes(igulp_nframes)
        # Writers only buffer one gulp; extra depth belongs to readers.
        # EXCEPT under macro-gulp batching: a reader's guarantee lags
        # one of ITS spans behind consumption, and when the reader's
        # own buffering request is smaller than the writer's macro
        # span (a K=1 consumer reading logical gulps), a one-macro-
        # span ring can never grant the next macro reserve — the
        # writer carries a second macro span of depth instead.
        obuf_factor = 2 if batch > 1 else 1
        oseqs = [exit_stack.enter_context(
                     oring.begin_sequence(ohdr, ogulp,
                                          obuf_factor * ogulp))
                 for oring, ohdr, ogulp
                 in zip(orings, oheaders, ogulp_nframes)]
        # Init barrier (reference: pipeline.py:401-403).
        self.pipeline.block_init_queue.put((self, True))
        self.pipeline.all_blocks_finished_initializing_event.wait()
        self.heartbeat()     # sequence boundary counts as progress
        ogulp_overlaps = [g - s for g, s
                          in zip(ogulp_nframes, ostride_nframes)]
        return oseqs, ogulp_overlaps

    def reserve_spans(self, exit_stack, oseqs, igulp_nframes=()):
        ogulp_nframes = self._define_output_nframes(list(igulp_nframes))
        return [exit_stack.enter_context(oseq.reserve(onframe))
                for oseq, onframe in zip(oseqs, ogulp_nframes)]

    def commit_spans(self, ospans, ostrides_actual, ogulp_overlaps):
        if ostrides_actual is None:
            ostrides_actual = [None] * len(ospans)
        ostrides = [ostride if ostride is not None
                    else max(ospan.nframe - overlap, 0)
                    for ostride, ospan, overlap
                    in zip(ostrides_actual, ospans, ogulp_overlaps)]
        for ospan, ostride in zip(ospans, ostrides):
            ospan.commit(ostride)

    # -- dispatch-ahead backpressure --------------------------------------
    def _sync_gulp(self, ospans):
        """Bound device run-ahead: enqueue this gulp's device arrays
        and, once ``sync_depth`` gulps are outstanding, drain all but
        the newest with ONE wait (on the newest drained gulp — TPU
        executes in enqueue order, so that implies the older ones
        finished).  Steady state is therefore ONE hard host sync per
        ``sync_depth`` gulps, the bound the transfer-engine telemetry
        (``pipeline.sync_waits`` / ``pipeline.gulps``) verifies.
        After a drain the device holds one queued gulp of lookahead —
        enough to cover the host's per-gulp prep in the steady state
        (host dispatch is faster than device execution on the hot
        paths); a host-bound pipeline is bottlenecked by the host
        under ANY drain policy.

        Amortizing the wait matters: a block_until_ready per gulp
        serializes the host against the device and halves pipeline
        throughput (measured on the spectroscopy bench: 2.0 -> 3.9
        Gsamples/s).  Peak device memory held by the queue is about
        ``sync_depth`` gulps of outputs and at most
        ``memory.INFLIGHT_BYTES`` besides the newest: large outputs
        (a 2.1 GB visibility product) drain every time.

        Draining waits only on the newest popped gulp, which is
        sufficient on in-order backends (the TPU single-stream runtime);
        with BF_ASSUME_IN_ORDER=0 (out-of-order backend) every popped
        gulp is waited on instead.

        The drain also retires any completed async host transfers in
        the process transfer engine (xfer.TransferEngine.drain) — the
        non-blocking D2H completion queue is emptied here instead of
        at each readback.

        Strict mode (``sync_strict=True`` scope attribute, or
        BF_SYNC_STRICT=1): forces completion via a one-element value
        readback instead of block_until_ready.  On the local v5e
        block_until_ready itself waits for the device (chip_smoke.py
        fact i, PR 21), so the default drain already bounds in-flight
        device work and the HBM held by pending outputs; strict mode
        stays until ROADMAP D6 decides it."""
        import os
        from . import xfer
        from .telemetry import counters
        depth = resolve_sync_depth(self)
        strict = self.sync_strict
        if strict is None:
            strict = os.environ.get('BF_SYNC_STRICT', '0') == '1'
        pend = getattr(self, '_pending_outputs', None)
        if pend is None:
            pend = self._pending_outputs = deque()
        counters.inc('pipeline.gulps')
        self.heartbeat()
        from .planes import device_arrays
        arrays = [a for s in ospans
                  if getattr(s, '_device_array', None) is not None
                  for a in device_arrays(s._device_array)]
        if arrays:
            # device-output gulps: the denominator for the hard-sync
            # rate (waits per device gulp <= 1/sync_depth steady-state)
            counters.inc('pipeline.gulps_device')
            pend.append(arrays)
        # depth by bytes (memory.span_depth's rule): the queue keeps
        # these outputs alive whatever the ring does with them, so it
        # drains to the newest once they pass INFLIGHT_BYTES too
        if len(pend) > depth or (len(pend) > 1 and sum(
                int(getattr(a, 'nbytes', 0)) for gulp in pend
                for a in gulp) > memory.INFLIGHT_BYTES):
            popped = [pend.popleft() for _ in range(len(pend) - 1)]
            wait = device.force_completion if strict \
                else device.stream_synchronize

            def live(gulp):
                # donated (deleted) arrays cannot be waited on and
                # prove nothing about completion — waiting on them
                # would be a silent no-op while the telemetry claims
                # the run-ahead bound held
                return [a for a in gulp
                        if not getattr(a, 'is_deleted',
                                       lambda: False)()]

            def hard_wait(arrs):
                # where a device-bound block spends its time: waiting
                # for the device inside its own call, not in a ring
                counters.inc('pipeline.sync_waits')
                with _spans.timed(self.name + '.sync_wait', 'wait',
                                  'block.%s.sync_wait_s' % self.name):
                    wait(*arrs)
            if device.execution_in_order():
                # newest popped gulp with anything left to wait on
                for gulp in reversed(popped):
                    arrs = live(gulp)
                    if arrs:
                        hard_wait(arrs)
                        break
            else:
                for gulp in popped:
                    arrs = live(gulp)
                    if arrs:
                        hard_wait(arrs)
        # retire completed async D2H transfers without blocking
        xfer.engine().drain()

    # -- overridables ------------------------------------------------------
    def _define_output_nframes(self, input_nframes):
        return self.define_output_nframes(input_nframes)

    def define_output_nframes(self, input_nframes):
        raise NotImplementedError

    def _define_valid_input_spaces(self):
        return self.define_valid_input_spaces()

    def define_valid_input_spaces(self):
        return ['any'] * len(self.irings)


class SourceBlock(Block):
    """0-in/1-out block reading from named sources
    (reference: pipeline.py:436-507)."""

    def __init__(self, sourcenames, gulp_nframe, space=None, *args, **kwargs):
        super(SourceBlock, self).__init__([], *args,
                                          gulp_nframe=gulp_nframe, **kwargs)
        self.sourcenames = sourcenames
        if space is None:
            space = 'system'
        self.orings = [self.create_ring(space=space)]
        self._seq_count = 0
        self.perf_proclog = ProcLog(self.name + '/perf')
        self.out_proclog = ProcLog(self.name + '/out')
        rnames = {'nring': len(self.orings)}
        for i, r in enumerate(self.orings):
            rnames['ring%i' % i] = r.name
        self.out_proclog.update(rnames)

    def main(self, orings):
        # Restart-policy bookkeeping: a re-entered main resumes at the
        # source that failed instead of re-reading completed sources.
        sourcenames = list(self.sourcenames)
        if not hasattr(self, '_source_index'):
            self._source_index = 0
        while self._source_index < len(sourcenames):
            sourcename = sourcenames[self._source_index]
            if self.shutdown_event.is_set():
                break
            try:
                self._read_source(orings, sourcename)
            except (EndOfDataStop, RingPoisonedError):
                raise
            except Exception as exc:
                if not self._may_skip():
                    raise
                # graceful degradation: the failed source's output
                # sequence has ended (ExitStack unwound); record and
                # move on to the next source
                supervisor = getattr(self.pipeline, 'supervisor', None)
                if supervisor is not None:
                    supervisor.block_skipped(self, exc)
                # the skipped source's stale origin must not poison
                # this block's commit-age p99 (see the transform-side
                # skip path)
                _slo.reset_block_ages(self.name)
            self._source_index += 1

    def _read_source(self, orings, sourcename):
        with self.create_reader(sourcename) as ireader:
            faults.fire('block.on_sequence', self.name)
            oheaders = self.on_sequence(ireader, sourcename)
            ctx = None
            for ohdr in oheaders:
                ohdr.setdefault('time_tag', self._seq_count)
                ohdr.setdefault('name',
                                'unnamed-sequence-%i' % self._seq_count)
                # stream origin: stamp the stream-unique trace id +
                # capture timestamp here, at first commit — every
                # downstream block (and host, via the bridge) inherits
                # it (docs/observability.md).  One context per source
                # sequence: multi-output sources share the identity.
                if ctx is None:
                    ctx = ensure_trace_context(ohdr)
                elif isinstance(ohdr, dict):
                    ohdr.setdefault(TRACE_CONTEXT_KEY, dict(ctx))
            self._trace_ctx = ctx
            self._seq_count += 1
            seq_id = self._seq_count - 1
            gulp_index = 0
            with ExitStack() as oseq_stack:
                oseqs, ogulp_overlaps = self.begin_sequences(
                    oseq_stack, orings, oheaders,
                    igulp_nframes=[], istride_nframes=[])
                while not self.shutdown_event.is_set():
                    t0 = time.perf_counter()
                    with ExitStack() as ospan_stack:
                        ospans = self.reserve_spans(ospan_stack, oseqs)
                        t1 = time.perf_counter()
                        faults.fire('block.on_data', self.name)
                        with self._compute_span(seq_id, gulp_index):
                            ostrides = self.on_data(ireader, ospans)
                        self._sync_gulp(ospans)
                        self.commit_spans(ospans, ostrides,
                                          ogulp_overlaps)
                        if any(o == 0 for o in ostrides):
                            break
                    t2 = time.perf_counter()
                    gulp_index += 1
                    self._observe_gulp(0.0, t1 - t0, t2 - t1)
                    self._observe_dispatch(1)
                    perf = {'acquire_time': -1,
                            'reserve_time': t1 - t0,
                            'process_time': t2 - t1}
                    # percentiles only when the rate limiter will
                    # actually write them (3 bucket walks per gulp
                    # would otherwise be discarded work)
                    if self.perf_proclog.ready():
                        perf.update(self._perf_stats())
                    self.perf_proclog.update(perf)

    def define_output_nframes(self, _):
        return [self.gulp_nframe] * self.num_outputs()

    def define_valid_input_spaces(self):
        return []

    def static_oheaders(self):
        """Optional static-verification protocol (docs/analysis.md):
        the output sequence headers this source WILL advertise, when
        they are knowable without opening the source (a synthesized
        stream, a format with a fixed layout).  Return a list with one
        header dict per output ring, or None (the default) when the
        headers only exist at read time — the verifier then reports
        that propagation stops here instead of guessing.  Must have no
        side effects; ``on_sequence`` remains the runtime authority."""
        return None

    def create_reader(self, sourcename):
        raise NotImplementedError

    def on_sequence(self, reader, sourcename):
        """Return a list of output headers."""
        raise NotImplementedError

    def on_data(self, reader, ospans):
        """Fill ospans; return frames committed per output."""
        raise NotImplementedError


class MultiTransformBlock(Block):
    """N-in/N-out engine: zip-reads input rings, negotiates gulp/overlap,
    handles skipped and overwritten frames
    (reference: pipeline.py:517-688)."""

    def __init__(self, irings_, guarantee=True, *args, **kwargs):
        super(MultiTransformBlock, self).__init__(irings_, *args, **kwargs)
        self.guarantee = guarantee
        self.orings = [self.create_ring(space=iring.space)
                       for iring in self.irings]
        self._seq_count = 0
        self.perf_proclog = ProcLog(self.name + '/perf')
        self.sequence_proclogs = [ProcLog(self.name + '/sequence%i' % i)
                                  for i in range(len(self.irings))]
        self.out_proclog = ProcLog(self.name + '/out')
        rnames = {'nring': len(self.orings)}
        for i, r in enumerate(self.orings):
            rnames['ring%i' % i] = r.name
        self.out_proclog.update(rnames)

    def main(self, orings):
        for iseqs in izip(*[iring.read(guarantee=self.guarantee)
                            for iring in self.irings]):
            if self.shutdown_event.is_set():
                break
            try:
                if not self._process_sequence(orings, iseqs):
                    break               # shutdown requested mid-sequence
            except (EndOfDataStop, RingPoisonedError):
                raise
            except Exception as exc:
                if not self._may_skip():
                    raise
                # skip_sequence: the output sequence for the failed
                # input has ended (ExitStack unwound, 0 frames
                # committed past the failure); discard the rest of the
                # input and continue with the next sequence
                supervisor = getattr(self.pipeline, 'supervisor', None)
                if supervisor is not None:
                    supervisor.block_skipped(self, exc)
                # reset this block's SLO age tracking: the skipped
                # sequence's stale capture origin would otherwise
                # poison the commit-age p99 long after recovery
                # (the drain below re-observes nothing — drained
                # spans are discarded, not committed)
                _slo.reset_block_ages(self.name)
                self._drain_sequences(iseqs)

    # -- macro-gulp execution (bifrost_tpu.macro; docs/perf.md) -----------
    def macro_gulp_safe(self):
        """Whether this block's on_data can process a K-gulp macro span
        as ONE dispatch with per-gulp semantics preserved.  Default
        False: host/compute blocks fall back to K=1 automatically.
        Device blocks that batch (FusedBlock, the jitted _StageBlock
        wrappers, CopyBlock's space movers) override this."""
        return False

    def macro_overlap_safe(self):
        """Whether this block can process a K-gulp macro span that
        CARRIES its declared input overlap in-program: the span is
        read as K*stride + overlap frames (the ghost history sliced
        from the span head ONCE) and on_data must produce output whose
        committed K*stride frames are byte-identical to K sequential
        overlapped gulps.  Default False: a declared overlap forces
        K=1 (``macro.fallback.overlap``).  Stage-chain blocks whose
        chain is 'block'-mode equivariant with a derivable lookahead
        override this (FusedBlock, the jitted _StageBlock wrappers) —
        the in-segment halo carry, docs/perf.md."""
        return False

    def _macro_input_consumers(self):
        """Direct consumers of this block's input ring (by base-ring
        identity, so block_view taps count).  A multi-reader input
        ring used to force a K=1 fallback; macro acquire is now
        eligible there — each reader's guarantee independently pins
        its own oldest open span (both ring cores prove this since the
        PR 5 multi-open-span fix), and the reader-side resize sizes
        the ring for the largest consumer's macro span, so a K-gulp
        guarantee never wedges a K=1 peer.  The count is kept for the
        retirement telemetry (donation exclusivity is still enforced
        per-claim by ring._take_exclusive, which multi-reader rings
        fail by construction)."""
        def base(r):
            return getattr(r, '_base_ring', r)
        target = base(self.irings[0])
        n = 0
        for b in self.pipeline.blocks:
            for r in getattr(b, 'irings', ()):
                if base(r) is target:
                    n += 1
        return n

    def _macro_static_reason(self):
        """Macro-gulp fallback reason derivable from STATIC block /
        topology state (no open sequence required), or None.  Shared
        by _resolve_macro_batch and FusedBlock._prewarm, so prewarm
        never compiles K-gulp plans a static fallback would discard."""
        if not self.macro_gulp_safe():
            return 'block'
        if len(self.irings) != 1 or len(self.orings) > 1:
            return 'topology'
        if not getattr(self, 'guarantee', True):
            return 'unguaranteed'
        return None

    def _resolve_macro_batch(self, iseqs, istride_nframes,
                             igulp_overlaps):
        """Effective macro-gulp batch for THIS sequence: the requested
        K (gulp_batch tunable / BF_GULP_BATCH) when every eligibility
        condition holds, else 1.  Fallbacks are recorded on the
        ``macro.fallback.<reason>`` counters — batching silently
        disabling itself must still be observable."""
        from .macro import resolve_gulp_batch, fallback_reason
        k = resolve_gulp_batch(self)
        if k <= 1:
            return 1
        reason = self._macro_static_reason()
        if reason is None and any(igulp_overlaps) and \
                not self.macro_overlap_safe():
            reason = 'overlap'
        if reason is None and any(not g or g <= 0
                                  for g in istride_nframes):
            reason = 'dynamic_gulp'
        if reason is None:
            # nframe linearity: a K-gulp batch's output must be exactly
            # K per-gulp outputs for the one-commit macro span to equal
            # K sequential commits
            try:
                per = self._define_output_nframes(list(istride_nframes))
                mac = self._define_output_nframes(
                    [g * k for g in istride_nframes])
                if mac != [o * k for o in per]:
                    reason = 'nonlinear'
            except Exception:
                reason = 'nonlinear'
        if reason is not None:
            fallback_reason(reason)
            return 1
        if self._macro_input_consumers() > 1:
            # formerly a K=1 fallback; count each sequence that NOW
            # batches on a multi-reader ring (every other eligibility
            # condition already passed) so the retirement is observable
            # next to the remaining macro.fallback.* reasons
            fallback_reason('multi_reader_retired')
        return k

    def _drain_sequences(self, iseqs):
        """Consume and discard the remainder of the current input
        sequences (skip_sequence): a reader that merely stops reading
        would hold its guarantee at the abandoned offset and block the
        producer forever — reading through to the sequence end keeps
        data flowing while the failed sequence's output stays empty."""
        for iseq in iseqs:
            gulp = self.gulp_nframe or \
                iseq.header.get('gulp_nframe', 1) or 1
            for _span in iseq.read(gulp):
                self.heartbeat()
                if self.shutdown_event.is_set():
                    return

    def _process_sequence(self, orings, iseqs):
        for i, iseq in enumerate(iseqs):
            self.sequence_proclogs[i].update(iseq.header,
                                             force=True)
        faults.fire('block.on_sequence', self.name)
        oheaders = self._on_sequence(iseqs)
        for ohdr in oheaders:
            ohdr.setdefault('time_tag', self._seq_count)
        # trace-context propagation: the stream identity follows the
        # data input->output (a block's own on_sequence may override
        # by stamping `_trace` itself; absent upstream context — e.g.
        # BF_TRACE_CONTEXT=0 at the origin — nothing is stamped)
        self._trace_ctx = propagate_trace_context(iseqs[0].header,
                                                  oheaders)
        self._seq_count += 1
        seq_id = self._seq_count - 1
        gulp_index = 0

        igulp_nframes = [self.gulp_nframe or iseq.header['gulp_nframe']
                         for iseq in iseqs]
        igulp_overlaps = self._define_input_overlap_nframe(iseqs)
        istride_nframes = igulp_nframes[:]
        igulp_nframes = [g + o for g, o
                         in zip(igulp_nframes, igulp_overlaps)]

        # Macro-gulp execution (bifrost_tpu.macro): an eligible block
        # acquires/reserves K gulps per ring operation and its on_data
        # runs ONE compiled program over the batch.  The LOGICAL gulp
        # (istride before scaling) is recorded so on_data can recover
        # per-gulp geometry and telemetry can count logical gulps.
        batch = self._resolve_macro_batch(iseqs, istride_nframes,
                                          igulp_overlaps)
        self._gulp_batch_active = batch
        self._macro_gulp_in = istride_nframes[0] if istride_nframes \
            else None
        self._macro_overlap_in = igulp_overlaps[0] if igulp_overlaps \
            else 0
        if batch > 1:
            # halo carry: the span is K logical strides plus ONE copy
            # of the overlap history at the head — NOT K copies (the
            # interior handoffs happen inside the program), which is
            # what makes a carried K-gulp span cheaper than K
            # overlapped gulps
            igulp_nframes = [s * batch + o for s, o
                             in zip(istride_nframes, igulp_overlaps)]
            istride_nframes = [s * batch for s in istride_nframes]

        for iseq, igulp_nframe, istride_nframe, ioverlap in zip(
                iseqs, igulp_nframes, istride_nframes, igulp_overlaps):
            if self.buffer_factor is None:
                src_block = iseq.ring.owner
                # Fused scopes share one gulp of buffering so that
                # producer and consumer alternate (reference:
                # pipeline.py:558-568).
                if src_block is not None and \
                        self.is_fused_with(src_block):
                    buffer_factor = 1
                else:
                    buffer_factor = None
            else:
                buffer_factor = self.buffer_factor
            buf_nframe = self.buffer_nframe
            if ioverlap > 0 and buf_nframe is None and \
                    buffer_factor is None:
                # Overlap consumers hold span N while acquiring span
                # N+1 (ReadSequence.read hold-ahead) so the writer
                # can never reclaim the shared history frames.  That
                # only avoids deadlock when the ring also absorbs the
                # writer's reserve granularity (its ghost span, sized
                # by the producer which resized this ring before this
                # sequence became visible) on top of both spans.
                fb = iseq.tensor['frame_nbyte']
                ghost_nframe = -(-iseq.ring.ghost_span // fb)
                buf_nframe = max(3 * igulp_nframe,
                                 igulp_nframe + istride_nframe +
                                 ghost_nframe)
            iseq.resize(gulp_nframe=igulp_nframe,
                        buf_nframe=buf_nframe,
                        buffer_factor=buffer_factor)

        iframe0s = [0 for _ in igulp_nframes]
        force_skip = False

        with ExitStack() as oseq_stack:
            oseqs, ogulp_overlaps = self.begin_sequences(
                oseq_stack, orings, oheaders,
                igulp_nframes, istride_nframes, batch=batch)
            if self.shutdown_event.is_set():
                return False
            prev_time = time.perf_counter()
            for ispans in izip(*[iseq.read(igulp, istride, iframe0)
                                 for iseq, igulp, istride, iframe0
                                 in zip(iseqs, igulp_nframes,
                                        istride_nframes, iframe0s)]):
                if self.shutdown_event.is_set():
                    return False

                if any(ispan.nframe_skipped for ispan in ispans):
                    # Zero-fill frames lost to overwriting
                    # (reference: pipeline.py:590-606).
                    with ExitStack() as ospan_stack:
                        iskip_slices = [
                            slice(f0, f0 + ispan.nframe_skipped, istride)
                            for f0, istride, ispan
                            in zip(iframe0s, istride_nframes, ispans)]
                        iskip_nframes = [ispan.nframe_skipped
                                         for ispan in ispans]
                        ospans = self.reserve_spans(
                            ospan_stack, oseqs, iskip_nframes)
                        ostrides = self._on_skip(iskip_slices, ospans)
                        # skip spans commit their FULL zero-filled
                        # reservation: the lost frames carry no re-read
                        # history, so the overlap holdback that
                        # commit_spans applies to data spans would
                        # splice ``overlap`` frames out of the output
                        # stream at every skip
                        if ostrides is None:
                            ostrides = [None] * len(ospans)
                        ostrides = [osp.nframe if s is None else s
                                    for s, osp in zip(ostrides, ospans)]
                        self._sync_gulp(ospans)
                        # the zero-fill is a real dispatch: keep BOTH
                        # the ring-level (ring.<name>.gulps via
                        # _ngulps) and block-level (dispatches/gulps)
                        # logical-gulp counters symmetric for it
                        ng = 1
                        if batch > 1 and self._macro_gulp_in:
                            ng = max(1, -(-iskip_nframes[0] //
                                          self._macro_gulp_in))
                            for ospan in ospans:
                                ospan._ngulps = ng
                        self.commit_spans(ospans, ostrides,
                                          ogulp_overlaps)
                        self._observe_dispatch(ng)

                if all(ispan.nframe == 0 for ispan in ispans):
                    continue

                cur_time = time.perf_counter()
                acquire_time = cur_time - prev_time
                prev_time = cur_time

                with ExitStack() as ospan_stack:
                    cur_igulps = [ispan.nframe for ispan in ispans]
                    ospans = self.reserve_spans(ospan_stack, oseqs,
                                                cur_igulps)
                    cur_time = time.perf_counter()
                    reserve_time = cur_time - prev_time
                    prev_time = cur_time

                    if not force_skip:
                        faults.fire('block.on_data', self.name)
                        with self._compute_span(seq_id, gulp_index):
                            ostrides = self._on_data(ispans, ospans)
                        self._sync_gulp(ospans)

                    any_overwritten = any(ispan.nframe_overwritten
                                          for ispan in ispans)
                    if force_skip or any_overwritten:
                        # Force-skip a gulp to let interrupted pipelines
                        # catch up (reference: pipeline.py:630-644).
                        force_skip = any_overwritten
                        iskip_slices = [
                            slice(ispan.frame_offset,
                                  ispan.frame_offset +
                                  ispan.nframe_overwritten,
                                  istride)
                            for ispan, istride
                            in zip(ispans, istride_nframes)]
                        ostrides = self._on_skip(iskip_slices, ospans)
                        self._sync_gulp(ospans)

                    # logical gulps this dispatch covered (a partial
                    # macro span at sequence end rounds up: its tail
                    # sub-gulp is a real dispatch unit)
                    ngulps = 1
                    if batch > 1 and self._macro_gulp_in:
                        # overlap frames are history, not new gulps
                        ngulps = max(1, -(-(ispans[0].nframe -
                                            self._macro_overlap_in) //
                                          self._macro_gulp_in))
                    for ospan in ospans:
                        ospan._ngulps = ngulps
                    self.commit_spans(ospans, ostrides, ogulp_overlaps)
                cur_time = time.perf_counter()
                process_time = cur_time - prev_time
                prev_time = cur_time
                gulp_index += 1
                self._observe_gulp(acquire_time, reserve_time,
                                   process_time)
                self._observe_dispatch(ngulps)
                if not self.orings and self._trace_ctx is not None:
                    # sink block: the gulp leaves the pipeline here —
                    # record its capture->exit age (the pipeline-exit
                    # p50/p99 of the capture-to-commit SLO)
                    self._observe_exit_age(
                        iseqs[0].header,
                        ispans[0].frame_offset + ispans[0].nframe)
                perf = {'acquire_time': acquire_time,
                        'reserve_time': reserve_time,
                        'process_time': process_time}
                # percentiles only when the rate limiter will actually
                # write them (see SourceBlock._read_source)
                if self.perf_proclog.ready():
                    perf.update(self._perf_stats())
                self.perf_proclog.update(perf)
        self._on_sequence_end(iseqs)
        return True

    # -- dispatch shims ----------------------------------------------------
    def _on_sequence(self, iseqs):
        return self.on_sequence(iseqs)

    def _on_sequence_end(self, iseqs):
        return self.on_sequence_end(iseqs)

    def _on_data(self, ispans, ospans):
        return self.on_data(ispans, ospans)

    def _on_skip(self, islices, ospans):
        return self.on_skip(islices, ospans)

    def _define_input_overlap_nframe(self, iseqs):
        return self.define_input_overlap_nframe(iseqs)

    # -- overridables ------------------------------------------------------
    def define_input_overlap_nframe(self, iseqs):
        """Frames of overlap between successive input spans (per input) —
        used by FIR/FDMT for filter history."""
        return [0] * len(self.irings)

    def define_output_nframes(self, input_nframes):
        return input_nframes

    def on_sequence(self, iseqs):
        """Return oheaders (one per output)."""
        raise NotImplementedError

    def on_sequence_end(self, iseqs):
        pass

    def on_data(self, ispans, ospans):
        """Process ispans into ospans; return frames to commit per output
        (or None to commit complete spans)."""
        raise NotImplementedError

    def on_skip(self, islices, ospans):
        raise NotImplementedError


class TransformBlock(MultiTransformBlock):
    """1-in/1-out specialization (reference: pipeline.py:690-741)."""

    def __init__(self, iring, *args, **kwargs):
        super(TransformBlock, self).__init__([iring], *args, **kwargs)
        self.iring = self.irings[0]

    # -- buffer donation (shared by FusedBlock / _StageBlock) -------------
    def _donation_on(self):
        """Effective donation setting (scope tunable / BF_DONATE),
        resolved once per sequence (subclasses reset ``_donate_on`` to
        None in on_sequence)."""
        if getattr(self, '_donate_on', None) is None:
            self._donate_on = resolve_donate(self)
        return self._donate_on

    def _dispatch_device(self, fn, args):
        """One compiled-plan dispatch (shared by FusedBlock and the
        jitted stage blocks, per-gulp and macro paths alike): brackets
        the FIRST dispatch of the process with the JAX profiler when
        ``BF_JAX_PROFILE=<dir>`` is armed (telemetry.profiling — one
        capture, then free), and records a per-shard dispatch span
        when the executing plan is mesh-wide (cat 'mesh', args
        shards=N + the stream's trace id) so the Chrome trace shows
        which dispatches ran N chips wide."""
        from .telemetry import profiling
        thunk = lambda: fn(*args)               # noqa: E731
        if self._shards_active > 1:
            span_args = {'shards': int(self._shards_active)}
            if self._trace_ctx is not None:
                span_args['trace'] = self._trace_ctx.get('id')
            with _spans.timed('%s.dispatch' % self.name, 'mesh',
                              **span_args):
                return profiling.profiled_dispatch(thunk)
        return profiling.profiled_dispatch(thunk)

    def _take_donatable(self, ispan, allow_parts=False, words=False):
        """The input span's device chunk claimed exclusively for
        donation, or None (donation off / exclusivity unprovable —
        callers fall back to ``ispan.data``).  With ``allow_parts``
        (macro-gulp spans) the claim may return a LIST of
        exclusively-owned chunks exactly tiling the span — the macro
        plan concatenates them inside the donating jit, so upstream
        K=1 producers still feed a donating macro consumer.  Counts
        donation hits/misses.  A ci8 gulp held as its words
        (devrep.ComplexWords) comes as them to a caller whose program
        starts from ``words``, else as the int8 pairs made from them:
        a fresh array, the caller's alone."""
        if not self._donation_on():
            return None
        from .telemetry import counters
        if getattr(self, '_macro_overlap_in', 0):
            # overlapped reads share ring bytes between successive
            # spans: donating would let XLA recycle the history frames
            # the NEXT span re-reads
            counters.inc('donation.misses')
            return None
        x = ispan.take_data(allow_parts=allow_parts)
        counters.inc('donation.hits' if x is not None
                     else 'donation.misses')
        if not words:
            from .planes import whole
            x = [whole(p) for p in x] if isinstance(x, list) else whole(x)
        return x

    def _define_valid_input_spaces(self):
        return [self.define_valid_input_spaces()]

    def define_valid_input_spaces(self):
        return 'any'

    def _define_input_overlap_nframe(self, iseqs):
        return [self.define_input_overlap_nframe(iseqs[0])]

    def define_input_overlap_nframe(self, iseq):
        return 0

    def _define_output_nframes(self, input_nframes):
        return [self.define_output_nframes(input_nframes[0])]

    def define_output_nframes(self, input_nframe):
        return input_nframe

    def _on_sequence(self, iseqs):
        return [self.on_sequence(iseqs[0])]

    def on_sequence(self, iseq):
        raise NotImplementedError

    def _on_sequence_end(self, iseqs):
        return [self.on_sequence_end(iseqs[0])]

    def on_sequence_end(self, iseq):
        pass

    def _on_data(self, ispans, ospans):
        return [self.on_data(ispans[0], ospans[0])]

    def on_data(self, ispan, ospan):
        raise NotImplementedError

    def _on_skip(self, islices, ospans):
        return [self.on_skip(islices[0], ospans[0])]

    def on_skip(self, islice, ospan):
        """Zero-fill the output gulp for skipped input frames."""
        if ospan.ring.space == 'tpu':
            from .devrep import device_rep_zeros
            t = ospan.tensor
            shape = (t['ringlet_shape'] + [ospan.nframe] + t['frame_shape'])
            ospan.set(device_rep_zeros(shape, t['dtype']))
        else:
            memset_array(ospan.data, 0)


class SinkBlock(MultiTransformBlock):
    """1-in/0-out specialization (reference: pipeline.py:744-779)."""

    def __init__(self, iring, *args, **kwargs):
        super(SinkBlock, self).__init__([iring], *args, **kwargs)
        self.orings = []
        self.iring = self.irings[0]

    def _define_valid_input_spaces(self):
        return [self.define_valid_input_spaces()]

    def define_valid_input_spaces(self):
        return 'any'

    def _define_input_overlap_nframe(self, iseqs):
        return [self.define_input_overlap_nframe(iseqs[0])]

    def define_input_overlap_nframe(self, iseq):
        return 0

    def _define_output_nframes(self, input_nframes):
        return []

    def _on_sequence(self, iseqs):
        self.on_sequence(iseqs[0])
        return []

    def on_sequence(self, iseq):
        raise NotImplementedError

    def _on_sequence_end(self, iseqs):
        return [self.on_sequence_end(iseqs[0])]

    def on_sequence_end(self, iseq):
        pass

    def _on_data(self, ispans, ospans):
        self.on_data(ispans[0])
        return []

    def on_data(self, ispan):
        raise NotImplementedError

    def _on_skip(self, islices, ospans):
        return []
