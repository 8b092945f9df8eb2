"""Ring buffer runtime — the heart of the framework.

This re-implements the semantics of the reference ring
(reference: src/ring_impl.{hpp,cpp}, src/ring.cpp, python/bifrost/ring2.py)
with a TPU-first storage model:

- **Host rings** ('system' / 'tpu_host'): a numpy byte buffer of
  ``nringlet`` lanes, each ``size + ghost`` bytes.  The ghost region makes
  wrap-around spans contiguous (reference: ring_impl.cpp:249-288 ghost
  copies); spans are zero-copy strided numpy views.

- **Device rings** ('tpu'): HBM is owned by the XLA runtime, so instead of
  a byte buffer the ring keeps a *chunk map* of committed ``jax.Array``
  gulps keyed by absolute byte offset.  All flow-control/ordering/overwrite
  bookkeeping is identical to the host path; only the payload differs.
  Because jax arrays are async futures, committing a span does NOT
  synchronize the device — readers force values only when they consume
  them, which preserves bifrost's pipelined-gulp execution model without
  an explicit stream_synchronize (reference: pipeline.py:628).

Semantics preserved from the reference:

- absolute monotonic byte offsets; buffer index = offset % size
- sequences (named data units w/ JSON-able header, time_tag), linked in
  order (reference: ring_impl.hpp:262-295)
- guaranteed readers refcount-lock the tail; unguaranteed readers can have
  data overwritten out from under them and observe ``nframe_skipped`` /
  ``nframe_overwritten`` (reference: ring_impl.hpp:110-141, 444-452)
- blocking acquire with partial final span at sequence end
  (reference: ring_impl.cpp:633-704)
- in-order commit barrier for multiple outstanding write spans
  (reference: ring_impl.cpp:591-594)
- live resize that preserves buffered data (reference: ring_impl.cpp:115-210)

One deliberate improvement over the reference: skip offsets are rounded up
to whole frames inside the core (the reference notes this as a latent bug,
ring2.py:381-388).
"""

from __future__ import annotations

import json
import string
import threading
import weakref
from copy import copy, deepcopy
from functools import reduce

import numpy as np

from .dtype import DataType
from .header_standard import trace_context
from .space import canonical
from .ndarray import ndarray
from .planes import ComplexPlanes, whole as _whole
from .words import ComplexWords
from .testing import faults
# dynamic ring-protocol checker (BF_RINGCHECK=1; docs/analysis.md) —
# every seam call below is one module-bool test when disarmed
from .analysis import ringcheck as _ringcheck

__all__ = ['Ring', 'RingWriter', 'WriteSequence', 'ReadSequence',
           'WriteSpan', 'ReadSpan', 'EndOfDataStop', 'WouldBlock',
           'RingPoisonedError', 'split_shape', 'ring_view',
           'live_rings']

#: every constructed Ring (both cores), weakly held — the telemetry
#: exporter reads point-in-time occupancy from here so
#: ``telemetry.snapshot()`` works without a pipeline handle
_live_rings = weakref.WeakSet()


def live_rings():
    """Live Ring objects in this process (weak registry snapshot)."""
    return list(_live_rings)


# observability hooks (telemetry.histograms / telemetry.spans), cached
# after first use to keep the per-gulp cost to attribute lookups
_obs = None


def _observability():
    global _obs
    if _obs is None:
        from .telemetry import counters, histograms, spans, slo
        _obs = (counters, histograms, spans, slo)
    return _obs

_INF = float('inf')


class EndOfDataStop(Exception):
    """Raised when a read reaches the end of a ring's data
    (reference: libbifrost.py:131-136 BF_STATUS_END_OF_DATA)."""


class WouldBlock(Exception):
    """Raised by nonblocking reserve when space is unavailable
    (reference: BF_STATUS_WOULD_BLOCK)."""


class RingPoisonedError(RuntimeError):
    """Raised by blocking ring operations (reserve/acquire/sequence
    waits) after :meth:`Ring.poison` marked the ring dead — a producer
    or consumer failed and the data stream can never complete.  Unlike
    :class:`EndOfDataStop` this is an ERROR path: consumers must not
    treat the committed prefix as a complete stream.  ``cause`` carries
    the original failure when known."""

    def __init__(self, ring_name, cause=None):
        msg = "ring %r poisoned" % (ring_name,)
        if cause is not None:
            msg += " (cause: %s: %s)" % (type(cause).__name__, cause)
        super(RingPoisonedError, self).__init__(msg)
        self.ring_name = ring_name
        self.cause = cause


def split_shape(shape):
    """Split a tensor shape at the time axis (-1) into
    (ringlet_shape, frame_shape): (2,3,-1,4,5) -> ([2,3], [4,5])
    (reference: ring2.py:60-70)."""
    ringlet_shape = []
    for i, dim in enumerate(shape):
        if dim == -1:
            return ringlet_shape, list(shape[i + 1:])
        ringlet_shape.append(dim)
    raise ValueError("No time dimension (-1) found in shape %s" % (shape,))


def _slugify(name):
    valid = frozenset("-_.() %s%s" % (string.ascii_letters, string.digits))
    return ''.join(c for c in name if c in valid)


def ring_view(ring, header_transform):
    """A view of ``ring`` whose read sequences present transformed headers
    (reference: ring2.py:75-82)."""
    new_ring = ring.view()
    old = ring.header_transform
    if old is not None:
        inner = header_transform
        header_transform = lambda hdr: inner(old(hdr))
    new_ring.header_transform = header_transform
    return new_ring


def _tensor_info(header):
    """Compute per-frame layout from a sequence header's ``_tensor``
    (reference: ring2.py:193-212)."""
    t = header['_tensor']
    ringlet_shape, frame_shape = split_shape(t['shape'])
    dtype = DataType(t['dtype'])
    nringlet = reduce(lambda x, y: x * y, ringlet_shape, 1)
    frame_nelement = reduce(lambda x, y: x * y, frame_shape, 1)
    frame_nbit = frame_nelement * dtype.itemsize_bits
    if frame_nbit % 8:
        raise ValueError("Frame of %s x %s does not span whole bytes"
                         % (frame_shape, dtype))
    return {
        'dtype': dtype,
        'ringlet_shape': ringlet_shape,
        'nringlet': nringlet,
        'frame_shape': frame_shape,
        'frame_nbyte': frame_nbit // 8,
        'dtype_nbyte': (dtype.itemsize_bits + 7) // 8,
    }


# ---------------------------------------------------------------------------
# Storage backends
# ---------------------------------------------------------------------------

class _HostStorage(object):
    """Byte-buffer storage with ghost region (host spaces)."""

    def __init__(self):
        self.buf = None          # (nringlet, size + ghost) uint8
        self.size = 0
        self.ghost = 0
        self.nringlet = 1

    def allocate(self, size, ghost, nringlet, tail, head, old=None,
                 core=None):
        new = np.zeros((nringlet, size + ghost), dtype=np.uint8)
        if core is not None:
            # advisory NUMA bind of the ring pages to core's node
            # (reference: ring_impl.cpp:164-166 hwloc bind)
            from .affinity import bind_memory_to_core
            bind_memory_to_core(new, core)
        if old is not None and old.buf is not None and head > tail:
            # preserve [tail, head) across the re-layout; when the ringlet
            # count grows, only the existing lanes carry data (matches the
            # native core, native/ring.cpp min-lane copy)
            nl = min(old.nringlet, nringlet)
            n = head - tail
            if n > size:
                tail = head - size
                n = size
            o = tail
            while o < head:
                run = min(head - o, old.size - o % old.size,
                          size - o % size)
                new[:nl, o % size:o % size + run] = \
                    old.buf[:nl, o % old.size:o % old.size + run]
                o += run
        self.buf, self.size, self.ghost, self.nringlet = \
            new, size, ghost, nringlet

    def write_view(self, offset, nbyte):
        bo = offset % self.size
        return self.buf[:, bo:bo + nbyte]

    read_view = write_view

    def commit_ghost(self, offset, nbyte):
        """After a write that ran past the nominal end, mirror the overflow
        back to the buffer start (reference: _ghost_write,
        ring_impl.cpp:249-288)."""
        bo = offset % self.size
        over = bo + nbyte - self.size
        if over > 0:
            self.buf[:, :over] = self.buf[:, self.size:self.size + over]

    def refresh_ghost(self, offset, nbyte):
        """Before a read that runs past the nominal end, refresh the ghost
        from the buffer start (reference: _ghost_read)."""
        bo = offset % self.size
        over = bo + nbyte - self.size
        if over > 0:
            self.buf[:, self.size:self.size + over] = self.buf[:, :over]

    def discard_before(self, offset):
        pass  # byte buffer reclaims implicitly

    def fill_ghost_mirror(self, offset, nbyte):
        """Ghost maintenance for a deferred fill (xfer.HostFill) that
        landed after the span's commit-time mirror ran."""
        self.commit_ghost(offset, nbyte)


def _build_stitcher(plan, taxis):
    """Compile a stitcher for a piece plan: ('z', nframe) zero-fill and
    ('a', f0, f1, arg_index) slice pieces, concatenated along taxis.
    The plan is closure-static, so jit compiles one fused gather per
    distinct overlap pattern and per-gulp dispatch is a cache hit."""
    import jax
    import jax.numpy as jnp

    def fn(*arrs):
        parts = []
        for p in plan:
            if p[0] == 'z':
                ref = arrs[0]
                shp = list(ref.shape)
                shp[taxis] = p[1]
                parts.append(jnp.zeros(shp, ref.dtype))
            else:
                _, f0, f1, k = p
                a = arrs[k]
                idx = [slice(None)] * a.ndim
                idx[taxis] = slice(f0, f1)
                parts.append(a[tuple(idx)])
        if len(parts) == 1:
            return parts[0]
        return jnp.concatenate(parts, axis=taxis)

    return jax.jit(fn)


class _DeviceStorage(object):
    """Chunk-map storage for 'tpu' rings: committed gulps are jax arrays
    keyed by absolute byte offset.  Logical shape of each chunk is
    (*ringlet_shape, nframe, *frame_shape).

    Mesh-resident pipelines (docs/parallel.md): a committed chunk may be
    a SHARDED jax Array carrying a ``jax.sharding.NamedSharding`` — the
    ring then holds shard-local HBM buffers on each mesh device instead
    of one monolithic per-chip allocation, and readers that consume the
    chunk whole (the exact-cover fast path in :meth:`get`, and
    :meth:`take`/:meth:`take_tiling` donation claims) hand the array to
    the next block's plan with its layout intact — span exchange between
    mesh blocks costs zero reshards.  Only the multi-chunk stitch path
    collapses layouts (XLA inserts whatever movement the concatenate
    needs), which overlap reads pay anyway.

    Overlap reads (FIR/FDMT input history) straddle chunk boundaries
    every gulp; the piece plan is found by bisect over a maintained
    sorted offset index and executed by a per-pattern cached jitted
    stitcher — the hot loop pays one compiled-dispatch instead of a
    Python chunk scan + eager concatenate (measured 207us -> see
    CHANGELOG)."""

    def __init__(self):
        # abs byte offset -> (nbyte, jax.Array, time_axis, owned)
        # ``owned`` marks chunks whose array the framework created for
        # this ring exclusively (H2D staging output, a jitted stage's
        # result) — only those are eligible for buffer donation.
        self.chunks = {}
        self._offsets = []          # sorted keys of self.chunks
        from .utils import ObjectCache
        # piece plan -> jitted stitcher; LRU-bounded so shifting
        # gulp/overlap patterns can't accumulate compiled programs
        self._stitchers = ObjectCache(capacity=64)
        self.size = 0
        self.ghost = 0
        self.nringlet = 1

    def allocate(self, size, ghost, nringlet, tail, head, old=None,
                 core=None):
        if old is not None and old is not self:
            self.chunks = dict(old.chunks)
            self._offsets = sorted(self.chunks)
        self.size, self.ghost, self.nringlet = size, ghost, nringlet

    def put(self, offset, nbyte, array, time_axis, owned=False):
        import bisect
        if offset not in self.chunks:
            bisect.insort(self._offsets, offset)
        self.chunks[offset] = (nbyte, array, time_axis, owned)

    def take(self, offset, nbyte):
        """Claim exclusive ownership of the chunk covering EXACTLY
        [offset, offset+nbyte) for buffer donation: removes it from the
        map and returns the array, or None when no owned chunk covers
        the request exactly.  Later reads of the range see a gap (zero
        fill) — callers must guarantee single-consumption."""
        hit = self.chunks.get(offset)
        if hit is None or hit[0] != nbyte or not hit[3] or \
                isinstance(hit[1], ComplexPlanes):
            return None
        del self.chunks[offset]
        try:
            self._offsets.remove(offset)
        except ValueError:
            pass
        return hit[1]

    def take_tiling(self, offset, nbyte):
        """Macro-span donation claim: when SEVERAL owned chunks exactly
        tile [offset, offset+nbyte) — a K=1 producer feeding a K-gulp
        macro consumer commits K per-gulp chunks — remove them all and
        return the list of arrays (in offset order), else None with the
        map untouched.  Single-chunk covers go through :meth:`take`."""
        import bisect
        end = offset + nbyte
        i = bisect.bisect_left(self._offsets, offset)
        run, covered = [], offset
        while covered < end and i < len(self._offsets):
            o = self._offsets[i]
            if o != covered:
                return None          # gap or misaligned chunk
            cn, arr, _taxis, owned = self.chunks[o]
            if not owned or o + cn > end or \
                    isinstance(arr, ComplexPlanes):
                return None          # foreign chunk / ragged tail
            run.append((o, arr))
            covered = o + cn
            i += 1
        if covered != end or len(run) < 2:
            return None
        for o, _arr in run:
            del self.chunks[o]
        self._offsets = sorted(self.chunks)
        return [arr for _o, arr in run]

    def get(self, offset, nbyte, frame_nbyte, zeros_fn):
        """Assemble the logical array covering [offset, offset+nbyte).
        Fast path: a single committed chunk covers the request exactly."""
        import bisect
        hit = self.chunks.get(offset)
        if hit is not None and hit[0] == nbyte:
            return _whole(hit[1])
        end = offset + nbyte
        # piece plan over the sorted chunk index
        i = bisect.bisect_right(self._offsets, offset) - 1
        if i < 0:
            i = 0
        plan, arrs, covered, taxis = [], [], offset, 0
        while covered < end and i < len(self._offsets):
            o = self._offsets[i]
            cn, arr, ctaxis = self.chunks[o][:3]
            i += 1
            if o + cn <= covered:
                continue
            if o >= end:
                break
            if o > covered:  # gap (overwritten / never written): zeros
                plan.append(('z', (o - covered) // frame_nbyte))
                covered = o
            f0 = (covered - o) // frame_nbyte
            f1 = min(cn, end - o) // frame_nbyte
            plan.append(('a', f0, f1, len(arrs)))
            arrs.append(_whole(arr))
            taxis = ctaxis
            covered = o + f1 * frame_nbyte
        if covered < end:
            plan.append(('z', (end - covered) // frame_nbyte))
        if not arrs:
            return zeros_fn(nbyte // frame_nbyte)
        if len(plan) == 1:
            _, f0, f1, k = plan[0]
            a = arrs[k]
            idx = [slice(None)] * a.ndim
            idx[taxis] = slice(f0, f1)
            return a[tuple(idx)]
        key = (tuple(plan), taxis)
        fn = self._stitchers.get(key)
        if fn is None:
            fn = self._stitchers.put(key, _build_stitcher(plan, taxis))
        return fn(*arrs)

    def chunk_as(self, form, offset, nbyte):
        """The chunk covering exactly [offset, offset+nbyte) where it
        is held as a ``form`` (ComplexPlanes, ComplexWords), else
        None."""
        hit = self.chunks.get(offset)
        if hit is not None and hit[0] == nbyte and \
                isinstance(hit[1], form):
            return hit[1]
        return None

    def discard_before(self, offset):
        dead = [o for o, c in self.chunks.items() if o + c[0] <= offset]
        for o in dead:
            del self.chunks[o]
        if dead:
            self._offsets = sorted(self.chunks)

    def fill_ghost_mirror(self, offset, nbyte):
        pass   # device rings have no byte buffer / ghost region


# ---------------------------------------------------------------------------
# Sequence bookkeeping (internal)
# ---------------------------------------------------------------------------

class _Sequence(object):
    __slots__ = ('name', 'time_tag', 'header', 'begin', 'end', 'next',
                 'nringlet')

    def __init__(self, name, time_tag, header, begin, nringlet):
        self.name = name
        self.time_tag = time_tag
        self.header = header
        self.begin = begin      # absolute byte offset of frame 0
        self.end = None         # absolute byte offset one past last frame
        self.next = None
        self.nringlet = nringlet

    @property
    def finished(self):
        return self.end is not None


# ---------------------------------------------------------------------------
# Ring
# ---------------------------------------------------------------------------

class Ring(object):
    """A first-in-first-out multi-reader byte ring with named sequences.

    API mirrors the reference Ring (reference: python/bifrost/ring2.py:84-148)
    so pipelines written against bifrost run unmodified.
    """

    instance_count = 0

    def __new__(cls, space='system', name=None, owner=None, core=None):
        # Host-space rings use the native C++ core when available
        # (native/ring.cpp); device rings keep the Python chunk-map core
        # because their payloads are jax Arrays.
        if cls is Ring and canonical(space) != 'tpu':
            from .native import available
            if available():
                from .ring_native import NativeRing
                return super(Ring, cls).__new__(NativeRing)
        return super(Ring, cls).__new__(cls)

    def __init__(self, space='system', name=None, owner=None, core=None):
        self.space = canonical(space)
        if name is None:
            name = 'ring_%i' % Ring.instance_count
            Ring.instance_count += 1
        self.name = _slugify(name)
        self.owner = owner
        self.core = core
        self.header_transform = None
        self.base = None
        self.is_view = False

        self._lock = threading.RLock()
        self._read_cond = threading.Condition(self._lock)
        self._write_cond = threading.Condition(self._lock)
        self._seq_cond = threading.Condition(self._lock)
        self._span_cond = threading.Condition(self._lock)

        self._storage = _DeviceStorage() if self.space == 'tpu' \
            else _HostStorage()
        self._size = 0
        self._ghost = 0
        self._nringlet = 1
        self._tail = 0
        self._head = 0
        self._reserve_head = 0
        self._sequences = []          # ordered
        self._seq_by_name = {}
        self._open_wspans = []        # in reserve order
        self._guarantees = {}         # id(ReadSequence) -> abs offset
        #: id(ReadSequence) -> begin offsets of that reader's OPEN
        #: spans.  A guaranteed reader holding several spans (the
        #: bridge's credit window keeps spans un-released until the
        #: peer acks their bytes) pins the guarantee at the OLDEST
        #: open span — the reference refcount-locks the tail per span
        #: (ring_impl.hpp:110-141); a bare watermark would let a later
        #: acquire unlock bytes an earlier open span still exports
        #: zero-copy.
        self._open_reads = {}
        #: id(ReadSequence) -> {span begin: span end} for OPEN spans:
        #: a release advances the consumed frontier to the span's END
        #: (the reader read those bytes), which keeps the drop_oldest
        #: shed ledger exact — counting from the released BEGIN would
        #: double-count an already-consumed span as shed when a
        #: reserve-shed races the no-open-spans window
        self._open_read_ends = {}
        #: id(ReadSequence) -> highest span END that reader ever
        #: RELEASED: out-of-order releases (acquire 0 and 8, release
        #: 8 then 0) must advance the guarantee to the high-water
        #: mark once no span is open, not to the last-released begin
        self._release_high = {}
        self._writing = False
        self._eod = False
        self._nwrite_open = 0
        self._nread_open = 0
        #: committed-but-in-flight D2H fills (xfer.HostFill): readers
        #: gate on overlapping fills before touching span data
        self._pending_fills = []
        #: deferred geometry change (docs/autotune.md): target
        #: (contiguous, total, nringlet) recorded by request_resize()
        #: while spans were open, applied by the span-release path the
        #: moment the ring goes quiescent — a runtime retune must not
        #: block the caller NOR re-layout storage under a live span's
        #: zero-copy view
        self._pending_resize = None
        #: overload policy at the reserve path (docs/robustness.md
        #: "Overload & degradation"): 'block' (default — classic
        #: backpressure), 'drop_oldest' (advance guaranteed readers
        #: past the oldest unread data instead of blocking; sheds are
        #: counted and the skipped frames surface downstream as
        #: nframe_skipped), or 'drop_newest' (the reserve itself is
        #: shed — the writer's gulp is produced into scratch and
        #: discarded, counted).  Resolved from the owning block's
        #: ``overload_policy`` scope tunable / BF_OVERLOAD_POLICY by
        #: Block.run; settable directly on framework-external rings.
        self.overload_policy = 'block'
        #: counted shedding ledger (mirrors the ring.<name>.shed_*
        #: counters; kept on the ring too so writers can stamp
        #: cumulative totals into downstream sequence headers)
        self._shed_gulps = 0
        self._shed_bytes = 0
        #: set by poison(): the exception that killed the producing /
        #: consuming side; blocking ops then raise RingPoisonedError
        self._poisoned = None
        #: per-ring wait histograms (telemetry.histograms), created on
        #: first span so idle rings cost nothing
        self._h_reserve = None
        self._h_acquire = None
        _live_rings.add(self)

    # -- views ------------------------------------------------------------
    def view(self):
        """A reader-side view of this ring.  Views share ALL ring state
        (geometry, storage, synchronization) with the base ring and differ
        only in their header transform (reference: ring2.py:108-112)."""
        return RingView(self)

    # -- geometry ---------------------------------------------------------
    def resize(self, contiguous_bytes, total_bytes=None, nringlet=1):
        """(Re)allocate the ring: max contiguous span + total capacity,
        preserving live data (reference: bfRingResize / ring_impl.cpp:115-210).
        """
        with self._lock:
            if total_bytes is None:
                total_bytes = contiguous_bytes * 4
            # fold in any deferred request_resize target: the blocking
            # path reaches quiescence anyway, so the pending geometry
            # can land here instead of waiting for a span release
            if self._pending_resize is not None:
                pc, pt, pn = self._pending_resize
                contiguous_bytes = max(contiguous_bytes, pc)
                total_bytes = max(total_bytes, pt)
                nringlet = max(nringlet, pn)
                self._pending_resize = None
            ghost = max(self._ghost, contiguous_bytes)
            size = max(self._size, total_bytes)
            nringlet = max(self._nringlet, nringlet)
            if (size == self._size and ghost == self._ghost and
                    nringlet == self._nringlet):
                return
            # Wait until no spans are open anywhere AND no deferred D2H
            # fill still targets the old buffer (its cached view would
            # dangle after re-layout).  Waiting a fill drops the lock,
            # so re-check both conditions until stable
            # (reference: RingReallocLock, ring_impl.cpp:60-84).
            while True:
                while self._nwrite_open or self._nread_open:
                    self._span_cond.wait()
                fills = [f for f in self._pending_fills if not f.done]
                if not fills:
                    break
                self._lock.release()
                try:
                    for f in fills:
                        f.wait()
                finally:
                    self._lock.acquire()
            self._apply_geometry_locked(size, ghost, nringlet)
        self._write_ring_proclog()

    def _apply_geometry_locked(self, size, ghost, nringlet):
        """Re-layout storage to the new geometry.  Must hold the lock
        AND the ring must be quiescent (no open spans, no incomplete
        fills targeting the buffer) — the protocol checker
        (BF_RINGCHECK=1) asserts the latter against its shadow state."""
        rc = _ringcheck.hook(self)
        if rc is not None:
            rc.resize_applied(self._nwrite_open, self._nread_open,
                              size)
        old = copy(self._storage)
        old.buf = getattr(self._storage, 'buf', None)
        self._storage.allocate(size, ghost, nringlet,
                               self._tail, self._head, old=old,
                               core=self.core)
        self._size, self._ghost, self._nringlet = size, ghost, nringlet
        self._write_cond.notify_all()
        self._read_cond.notify_all()

    # -- deferred (non-blocking) resize -----------------------------------
    def request_resize(self, contiguous_bytes, total_bytes=None,
                       nringlet=1):
        """Non-blocking grow request (the auto-tuner's retune protocol,
        docs/autotune.md): apply the geometry change NOW when the ring
        is quiescent, else record it and let the span-release path
        apply it the moment the oldest open span releases and no other
        span remains open.  Never blocks the caller and never
        re-layouts storage under a live span's zero-copy view.

        Geometry semantics match :meth:`resize` (MAX-negotiated: the
        ring only ever grows).  Returns True when the new geometry is
        live on return, False while it is still pending — callers that
        need certainty re-issue the request (idempotent) or read
        :attr:`total_span`."""
        with self._lock:
            if total_bytes is None:
                total_bytes = contiguous_bytes * 4
            ghost = max(self._ghost, contiguous_bytes)
            size = max(self._size, total_bytes)
            nringlet = max(self._nringlet, nringlet)
            if (size == self._size and ghost == self._ghost and
                    nringlet == self._nringlet):
                return True              # no-op: already that large
            if self._pending_resize is not None:
                pc, pt, pn = self._pending_resize
                contiguous_bytes = max(contiguous_bytes, pc)
                total_bytes = max(total_bytes, pt)
                nringlet = max(nringlet, pn)
            self._pending_resize = (contiguous_bytes, total_bytes,
                                    nringlet)
            rc = _ringcheck.hook(self)
            if rc is not None:
                rc.resize_requested(contiguous_bytes, total_bytes)
                if faults.armed('ring.corrupt.resize_under_span',
                                self.name):
                    # simulate a buggy core re-layouting storage NOW,
                    # under whatever spans are open
                    rc.resize_applied(self._nwrite_open,
                                      self._nread_open,
                                      int(total_bytes))
            applied = self._maybe_apply_pending_locked()
        if applied:
            self._write_ring_proclog()
        return applied

    @property
    def resize_pending(self):
        """Whether a deferred request_resize has not yet applied."""
        return self._pending_resize is not None

    def _maybe_apply_pending_locked(self):
        """Apply a pending deferred resize if the ring is quiescent
        RIGHT NOW (no open spans, no incomplete deferred fills whose
        cached views would dangle).  Must hold the lock.  Returns True
        when the pending geometry (if any) is live on return."""
        if self._pending_resize is None:
            return True
        if self._nwrite_open or self._nread_open:
            return False
        if any(not f.done for f in self._pending_fills):
            # a deferred D2H fill still targets the old buffer; stay
            # pending — the next release/commit (or the fill-draining
            # blocking resize at sequence start) retries
            return False
        contig, total, nringlet = self._pending_resize
        self._pending_resize = None
        ghost = max(self._ghost, contig)
        size = max(self._size, total)
        nringlet = max(self._nringlet, nringlet)
        if (size == self._size and ghost == self._ghost and
                nringlet == self._nringlet):
            return True
        self._apply_geometry_locked(size, ghost, nringlet)
        return True

    def _publish_capacity(self, size, ghost, nringlet):
        """Gauges (docs/observability.md): the bytes this ring may hold
        at its geometry (on the host its buffer, ghost region and all;
        on the device the arrays of as many spans as fit), and their
        sum over the live rings of its space."""
        from .telemetry import counters
        if self.space == 'tpu':
            ghost = 0
        self._capacity_bytes = (size + ghost) * max(nringlet, 1)
        counters.set_gauge('ring.%s.capacity_bytes' % self.name,
                           self._capacity_bytes)
        counters.set_gauge(
            'ring.held_bytes.%s' % self.space,
            sum(getattr(r, '_capacity_bytes', 0) for r in live_rings()
                if r.space == self.space))

    def _write_ring_proclog(self):
        """Record this ring's geometry under rings/<name> for the
        monitor tools (reference: ring_impl.cpp:476-489 'size' log:
        space/binding/ghost/span/stride/nringlet), and in the capacity
        gauges."""
        self._publish_capacity(self._size, self._ghost, self._nringlet)
        try:
            from .proclog import ProcLog
            if getattr(self, '_geom_proclog', None) is None:
                self._geom_proclog = ProcLog('rings/%s' % self.name)
            self._geom_proclog.update({
                'space': self.space,
                'core': -1 if self.core is None else self.core,
                'ghost': self._ghost,
                'span': self._ghost,
                'stride': self._size,
                'nringlet': self._nringlet,
            }, force=True)
        except Exception:
            pass

    @property
    def total_span(self):
        return self._size

    @property
    def ghost_span(self):
        """Max contiguous span in bytes (the ghost region size) — the
        reserve granularity bound ReadSequence.read's hold-ahead
        capacity check needs, core-agnostic."""
        return self._ghost

    @property
    def nringlet(self):
        return self._nringlet

    def occupancy(self):
        """Point-in-time flow-control state (tail/head/reserve head in
        absolute bytes, buffer size, open span counts) — the watchdog's
        stall dump reads this to show where data stopped moving."""
        with self._lock:
            return {'tail': self._tail, 'head': self._head,
                    'reserve_head': self._reserve_head,
                    'size': self._size,
                    'nwrite_open': self._nwrite_open,
                    'nread_open': self._nread_open,
                    'eod': self._eod,
                    'poisoned': self._poisoned is not None}

    # -- overload policy & counted shedding (docs/robustness.md) ----------
    OVERLOAD_POLICIES = ('block', 'drop_oldest', 'drop_newest')

    def set_overload_policy(self, policy):
        """Set this ring's reserve-path overload policy ('block' |
        'drop_oldest' | 'drop_newest').  Validated here so a
        misspelled policy fails at configuration time, not at the
        first overloaded reserve."""
        if policy not in self.OVERLOAD_POLICIES:
            raise ValueError(
                "Unknown overload policy %r on ring %s (expected one "
                "of %s)" % (policy, self.name,
                            ', '.join(self.OVERLOAD_POLICIES)))
        self.overload_policy = policy
        return policy

    def shed_stats(self):
        """Cumulative counted-shedding ledger for this ring: every
        gulp/byte dropped by a drop_* overload policy.  Matches the
        ``ring.<name>.shed_gulps`` / ``ring.<name>.shed_bytes``
        telemetry counters."""
        with self._lock:
            return {'policy': self.overload_policy,
                    'shed_gulps': self._shed_gulps,
                    'shed_bytes': self._shed_bytes}

    def _note_shed(self, nbyte, ngulps, header=None, frame_end=None):
        """Account one shed (both cores, both drop policies): the
        per-ring ledger, the ``ring.<name>.shed_gulps/.shed_bytes``
        counters, and — when the stream carries a trace-context
        origin — the age of the data being dropped on the
        ``slo.shed_age_s`` histogram (how stale data was when the
        pipeline chose to lose it; the SLO view of shedding)."""
        if nbyte <= 0:
            return
        with self._lock:
            self._shed_gulps += ngulps
            self._shed_bytes += nbyte
        obs = _observability()
        c, slo = obs[0], obs[3]
        c.inc('ring.%s.shed_gulps' % self.name, ngulps)
        c.inc('ring.%s.shed_bytes' % self.name, nbyte)
        if header is not None:
            try:
                age = slo.capture_age_s(header, frame_end)
                if age is not None:
                    slo.observe_shed(age)
            except Exception:
                pass            # SLO feed must never break shedding

    def _reserve_span_shed(self, nbyte, frame_nbyte, span=None):
        """Blocking reserve under the ``drop_oldest`` overload policy:
        when flow control would block on a guaranteed reader, advance
        that reader's guarantee past the needed bytes in whole-frame
        steps — clamped at its oldest OPEN span, so a held span's
        zero-copy view is never invalidated — and count the
        min-guarantee advance as shed bytes.  Blocks only on the
        committed head (the writer's own commit barrier) and on
        readers pinned by open spans; both resolve by peer progress.
        Returns ``(begin, shed_bytes)``.  Overridden by NativeRing
        (the same protocol runs inside the C core there)."""
        frame_nbyte = max(int(frame_nbyte or 1), 1)
        shed = 0
        with self._lock:
            self._check_poison()
            for sp in self._open_wspans:
                if sp._closed and sp._commit_nbyte < sp._nbyte:
                    raise RuntimeError(
                        "Cannot reserve a span while a partial commit "
                        "is pending")
            if nbyte > self._ghost:
                self._lock.release()
                try:
                    self.resize(nbyte, max(self._size, nbyte * 4),
                                self._nringlet)
                finally:
                    self._lock.acquire()
            begin = self._reserve_head
            new_reserve = begin + nbyte
            while True:
                new_tail = new_reserve - self._size
                limit = min(self._head, self._min_guarantee())
                if new_tail <= limit:
                    break
                advanced = False
                if new_tail <= self._head and self._guarantees:
                    old_min = self._min_guarantee()
                    for key, g in list(self._guarantees.items()):
                        if g >= new_tail:
                            continue
                        target = g + -(-(new_tail - g) //
                                       frame_nbyte) * frame_nbyte
                        opens = self._open_reads.get(key)
                        if opens:
                            target = min(target, min(opens))
                        if target > g:
                            self._guarantees[key] = target
                            advanced = True
                    if advanced:
                        new_min = self._min_guarantee()
                        if old_min != _INF and new_min > old_min:
                            shed += new_min - old_min
                        continue        # re-check the limit
                self._write_cond.wait()
                self._check_poison()
            self._reserve_head = new_reserve
            if new_reserve - self._size > self._tail:
                self._advance_tail(new_reserve - self._size)
            return begin, shed

    # -- poisoning --------------------------------------------------------
    @property
    def poisoned(self):
        return self._poisoned is not None

    def _check_poison(self):
        # must hold self._lock (python core) or be called where a
        # stale read is acceptable (native wrappers)
        if self._poisoned is not None:
            raise RingPoisonedError(self.name, self._poisoned)

    def poison(self, exc=None):
        """Mark the ring dead: a producer or consumer failed and the
        stream can never complete.  Every blocked ``reserve`` /
        ``acquire`` / sequence wait wakes immediately with
        :class:`RingPoisonedError`, as does any later blocking call.
        Idempotent; releasing already-held spans still works so block
        threads can unwind cleanly.  ``exc`` is the original failure
        (carried on the raised errors for diagnosis)."""
        with self._lock:
            if self._poisoned is not None:
                return
            self._poisoned = exc if exc is not None else \
                RuntimeError("ring poisoned")
            # also mark end-of-data so state-inspection paths (and the
            # native core's blocked readers) observe a terminal ring
            self._eod = True
            self._writing = False
        from .telemetry import counters
        counters.inc('ring_poisoned')
        rc = _ringcheck.hook(self)
        if rc is not None:
            # snapshot the seam ops blocked in the core BEFORE waking:
            # the checker's wake timer then proves poison released them
            rc.poisoned_now()
        if faults.armed('ring.corrupt.poison_nowake', self.name):
            # deliberate protocol corruption (docs/analysis.md): leave
            # blocked spans asleep so tests prove the checker's
            # poison-wake invariant trips.  The test un-hangs its
            # blocked thread afterwards by calling _wake_all directly.
            return
        self._wake_all()

    def _wake_all(self):
        """Wake every thread blocked on this ring's conditions (and, in
        the native core, inside the C state machine) — the poison
        wakeup path, split out so the poison_nowake corruption seam and
        the tests exercising it can drive it directly."""
        with self._lock:
            for cond in (self._read_cond, self._write_cond,
                         self._seq_cond, self._span_cond):
                cond.notify_all()
        self._wake_external()

    def _wake_external(self):
        """Hook for cores that block outside the Python locks
        (NativeRing wakes its C-side condition variables here)."""

    # -- writer side ------------------------------------------------------
    def begin_writing(self):
        return RingWriter(self)

    def _begin_writing(self):
        with self._lock:
            self._writing = True
            self._eod = False

    def end_writing(self):
        with self._lock:
            self._writing = False
            self._eod = True
            self._read_cond.notify_all()
            self._seq_cond.notify_all()

    @property
    def writing_ended(self):
        return self._eod

    def _begin_sequence(self, name, time_tag, header, nringlet):
        with self._lock:
            self._check_poison()
            seq = _Sequence(name, time_tag, header, self._head, nringlet)
            if self._sequences:
                prev = self._sequences[-1]
                if not prev.finished:
                    raise RuntimeError(
                        "Cannot begin sequence %r: previous sequence %r "
                        "is still open" % (name, prev.name))
                prev.next = seq
            self._sequences.append(seq)
            self._seq_by_name[name] = seq
            self._seq_cond.notify_all()
            return seq

    def _end_sequence(self, seq):
        with self._lock:
            seq.end = self._head
            self._read_cond.notify_all()
            self._seq_cond.notify_all()

    def _min_guarantee(self):
        return min(self._guarantees.values()) if self._guarantees else _INF

    # -- reader registration hooks (overridden by NativeRing) -------------
    def _register_reader(self, rseq):
        if rseq.guarantee:
            with self._lock:
                self._guarantees[id(rseq)] = max(rseq._seq.begin,
                                                 self._tail)

    def _reader_moved(self, rseq, new_seq):
        if rseq.guarantee:
            with self._lock:
                g = max(new_seq.begin, self._tail)
                # never unlock bytes a still-open span of the previous
                # sequence is exporting
                opens = self._open_reads.get(id(rseq))
                if opens:
                    g = min(g, min(opens))
                self._guarantees[id(rseq)] = g

    def _reserve_span(self, nbyte, nonblocking=False, span=None):
        with self._lock:
            self._check_poison()
            # A queued partial commit truncates reserve_head when it
            # lands; reserving past it would hand out offsets the
            # truncation then invalidates.
            for sp in self._open_wspans:
                if sp._closed and sp._commit_nbyte < sp._nbyte:
                    raise RuntimeError(
                        "Cannot reserve a span while a partial commit "
                        "is pending")
            if nbyte > self._ghost:
                # Guaranteed-contiguous window too small; grow it.
                self._lock.release()
                try:
                    self.resize(nbyte, max(self._size, nbyte * 4),
                                self._nringlet)
                finally:
                    self._lock.acquire()
            begin = self._reserve_head
            new_reserve = begin + nbyte
            while True:
                new_tail = new_reserve - self._size
                limit = min(self._head, self._min_guarantee())
                if new_tail <= limit:
                    break
                if nonblocking:
                    raise WouldBlock()
                self._write_cond.wait()
                self._check_poison()
            self._reserve_head = new_reserve
            if new_reserve - self._size > self._tail:
                self._advance_tail(new_reserve - self._size)
            return begin

    def _advance_tail(self, new_tail):
        # Overwrite: pull the tail forward past unguaranteed readers
        # (reference: _advance_reserve_head tail-pull, ring_impl.cpp:509-555).
        self._tail = new_tail
        self._storage.discard_before(new_tail)
        # GC fully-consumed finished sequences
        while (len(self._sequences) > 1 and self._sequences[0].finished and
               self._sequences[0].end <= new_tail and
               self._sequences[0].next is not None):
            dead = self._sequences.pop(0)
            if self._seq_by_name.get(dead.name) is dead:
                del self._seq_by_name[dead.name]

    def _commit_span(self, wspan, commit_nbyte):
        with self._lock:
            # A partial commit truncates reserve_head, so it is only legal
            # on the newest outstanding span; reject it up front, before
            # any state changes.
            if commit_nbyte < wspan._nbyte and self._open_wspans and \
                    self._open_wspans[-1] is not wspan:
                raise RuntimeError(
                    "Partial commit with later spans outstanding")
            wspan._commit_nbyte = commit_nbyte
            wspan._closed = True
            # (The up-front check above plus _reserve_span's pending-
            # partial-commit rejection guarantee the closed prefix is
            # always legal to apply here.)
            # In-order commit barrier (reference: ring_impl.cpp:591-594):
            # apply commits only for the prefix of closed spans.
            while self._open_wspans and self._open_wspans[0]._closed:
                sp = self._open_wspans.pop(0)
                cb = sp._commit_nbyte
                if cb < sp._nbyte:
                    self._reserve_head = sp._begin + cb
                self._head = sp._begin + cb
                if cb > 0:
                    sp._finalize_storage(cb)
                self._nwrite_open -= 1
            # quiescence point: a deferred request_resize applies the
            # moment no span remains open (docs/autotune.md)
            resized = False
            if self._pending_resize is not None:
                resized = self._maybe_apply_pending_locked()
            self._read_cond.notify_all()
            self._span_cond.notify_all()
        if resized:
            self._write_ring_proclog()   # monitors see the new size
        if commit_nbyte:
            self._note_commit(wspan, commit_nbyte)

    def _note_commit(self, wspan, commit_nbyte):
        """Per-commit telemetry shared by BOTH ring cores: the logical
        gulp throughput counter (macro spans credit their K gulps), the
        capture-to-commit SLO age (telemetry.slo — when the sequence
        header carries a trace-context origin, which crosses hosts via
        the bridge), and — for device rings whose committed chunk is a
        mesh-resident array — sharded-chunk accounting:
        ``ring.<name>.sharded_gulps`` and ``ring.<name>.shard_bytes``
        (bytes landing on EACH device; the per-chip slice of the
        span).  The storage itself holds the sharded jax Array, i.e.
        shard-local HBM buffers per device rather than one monolithic
        allocation — these counters are how an operator sees that
        layout without a device query."""
        obs = _observability()
        c, slo = obs[0], obs[3]
        ngulps = getattr(wspan, '_ngulps', 1)
        c.inc('ring.%s.gulps' % self.name, ngulps)
        try:
            header = wspan._sequence.header
            if trace_context(header) is not None:
                owner = getattr(self, 'owner', None)
                name = owner.name if owner is not None else self.name
                frame_end = wspan.frame_offset + \
                    commit_nbyte // wspan.frame_nbyte
                age = slo.capture_age_s(header, frame_end)
                if age is not None:
                    slo.observe_commit(name, age, ngulps)
        except Exception:
            pass                     # SLO feed must never break commits
        arr = getattr(wspan, '_device_array', None)
        if arr is None:
            return
        try:
            ndev = len(arr.sharding.device_set)
        except Exception:
            ndev = 1
        if ndev > 1:
            c.inc('ring.%s.sharded_gulps' % self.name, ngulps)
            c.inc('ring.%s.shard_bytes' % self.name,
                  commit_nbyte // ndev)
            c.inc('mesh.sharded_commits')

    # -- reader side ------------------------------------------------------
    def open_sequence(self, name, guarantee=True):
        return ReadSequence(self, which='specific', name=name,
                            guarantee=guarantee)

    def open_sequence_at(self, time_tag, guarantee=True):
        return ReadSequence(self, which='at', time_tag=time_tag,
                            guarantee=guarantee)

    def open_latest_sequence(self, guarantee=True):
        return ReadSequence(self, which='latest', guarantee=guarantee)

    def open_earliest_sequence(self, guarantee=True):
        return ReadSequence(self, which='earliest', guarantee=guarantee)

    def read(self, whence='earliest', guarantee=True):
        """Generator over sequences as they appear
        (reference: ring2.py:140-148)."""
        with ReadSequence(self, which=whence, guarantee=guarantee,
                          header_transform=self.header_transform) as cur_seq:
            while True:
                try:
                    yield cur_seq
                    cur_seq.increment()
                except EndOfDataStop:
                    return

    def _open_seq(self, which, name=None, time_tag=None):
        with self._lock:
            while True:
                if which == 'specific':
                    if name in self._seq_by_name:
                        return self._seq_by_name[name]
                elif which == 'at':
                    for seq in self._sequences:
                        if seq.time_tag == time_tag:
                            return seq
                elif which == 'latest':
                    if self._sequences:
                        return self._sequences[-1]
                elif which == 'earliest':
                    # earliest sequence with any unconsumed data
                    for seq in self._sequences:
                        if not seq.finished or seq.end > self._tail:
                            return seq
                    if self._sequences:
                        return self._sequences[-1]
                else:
                    raise ValueError("Invalid 'which': %r" % which)
                self._check_poison()
                if self._eod:
                    raise EndOfDataStop("No sequence available")
                self._seq_cond.wait()

    def _next_seq(self, seq):
        with self._lock:
            while seq.next is None:
                self._check_poison()
                if self._eod and seq.finished:
                    raise EndOfDataStop("No next sequence")
                self._seq_cond.wait()
            return seq.next

    def _acquire_span(self, rseq, offset, nbyte, frame_nbyte):
        """Block until [seq.begin+offset, +nbyte) is readable; returns
        (abs_begin, actual_nbyte) with skip rounded up to whole frames
        (reference: ring_impl.cpp:633-704)."""
        seq = rseq._seq
        with self._lock:
            self._check_poison()
            want_begin = seq.begin + offset
            # pre-wait bump: only when no span is open — an open span's
            # begin already bounds the guarantee and must keep doing so
            if rseq.guarantee and not self._open_reads.get(id(rseq)):
                self._guarantees[id(rseq)] = max(
                    self._guarantees.get(id(rseq), want_begin),
                    min(want_begin, self._head))
            while True:
                self._check_poison()
                seq_end = seq.end if seq.finished else None
                if seq_end is not None and want_begin >= seq_end:
                    raise EndOfDataStop("Sequence consumed")
                limit = seq_end if seq_end is not None else \
                    (self._head if self._eod else None)
                if self._eod and limit is not None and want_begin >= limit:
                    raise EndOfDataStop("Ring consumed")
                if want_begin + nbyte <= self._head:
                    end = want_begin + nbyte
                    break
                if limit is not None and limit <= self._head:
                    end = min(limit, want_begin + nbyte)
                    break
                self._read_cond.wait()
            # Skip data already overwritten, rounding up to frames.
            begin = want_begin
            if begin < self._tail:
                skip = self._tail - begin
                skip = -(-skip // frame_nbyte) * frame_nbyte
                begin = min(begin + skip, end)
            if rseq.guarantee:
                opens = self._open_reads.setdefault(id(rseq), [])
                opens.append(begin)
                ends = self._open_read_ends.setdefault(id(rseq), {})
                ends[begin] = max(ends.get(begin, 0), end)
                # guarantee = oldest open span (never jumps past a
                # held span; no overwrite beyond it until released);
                # an ADVANCE frees writer space, so notify
                g = min(opens)
                if g > self._guarantees.get(id(rseq), g):
                    self._write_cond.notify_all()
                self._guarantees[id(rseq)] = g
            self._nread_open += 1
            return begin, max(end - begin, 0)

    def _release_span(self, rseq, span_begin):
        with self._lock:
            if rseq.guarantee and id(rseq) in self._guarantees:
                opens = self._open_reads.get(id(rseq))
                if opens:
                    try:
                        opens.remove(span_begin)
                    except ValueError:
                        pass
                ends = self._open_read_ends.get(id(rseq), {})
                span_end = span_begin
                if span_begin not in (opens or ()):
                    span_end = ends.pop(span_begin, span_begin)
                rh = max(self._release_high.get(id(rseq), 0),
                         span_end)
                self._release_high[id(rseq)] = rh
                # advance to the oldest still-open span, else to the
                # high-water released span's END: the reader CONSUMED
                # those bytes, so a drop_oldest shed racing the
                # no-open-spans window must not count them again
                # (delivered + shed would exceed produced)
                g = min(opens) if opens else rh
                self._guarantees[id(rseq)] = max(
                    self._guarantees[id(rseq)], g)
            self._nread_open -= 1
            # quiescence point for deferred resize (docs/autotune.md):
            # "the oldest open span releases" — apply once no span at
            # all remains open
            resized = False
            if self._pending_resize is not None:
                resized = self._maybe_apply_pending_locked()
            self._write_cond.notify_all()
            self._span_cond.notify_all()
        if resized:
            self._write_ring_proclog()   # monitors see the new size

    def _close_read_seq(self, rseq):
        with self._lock:
            self._guarantees.pop(id(rseq), None)
            self._open_reads.pop(id(rseq), None)
            self._open_read_ends.pop(id(rseq), None)
            self._release_high.pop(id(rseq), None)
            self._write_cond.notify_all()

    def _overwritten_in(self, begin, nbyte):
        with self._lock:
            return max(0, min(self._tail - begin, nbyte))

    # -- deferred D2H fills (xfer.HostFill) -------------------------------
    def _register_fill(self, fill):
        with self._lock:
            self._pending_fills.append(fill)

    def _fills_overlapping(self, begin, nbyte):
        """Snapshot of incomplete fills overlapping [begin, begin+nbyte)
        in absolute offsets; also prunes completed fills.  Callers wait
        the returned fills OUTSIDE the ring lock."""
        with self._lock:
            self._pending_fills = [f for f in self._pending_fills
                                   if not f.done]
            return [f for f in self._pending_fills
                    if f.begin is not None
                    and f.begin < begin + nbyte
                    and begin < f.begin + f.nbyte]

    def _fills_before(self, limit):
        """Incomplete fills whose bytes a reservation ending past
        ``limit + size`` is about to overwrite (modular reuse of the
        same buffer region) — the writer completes these before any new
        bytes land."""
        with self._lock:
            self._pending_fills = [f for f in self._pending_fills
                                   if not f.done]
            return [f for f in self._pending_fills
                    if f.begin is not None and f.begin < limit]

    # -- protocol-corruption hook (testing/faults.py; docs/analysis.md) ---
    def _corrupt_guarantee_jump(self, rseq):
        """Deliberately force ``rseq``'s guarantee forward to the head
        while it may still hold open spans — reproducing the pre-PR-5
        watermark bug so tests prove the ring-protocol checker
        (BF_RINGCHECK=1) catches the overwriting reserve it admits.
        Only ever called from the ``ring.corrupt.guarantee_jump`` fault
        seam; overridden by NativeRing to corrupt the C core."""
        with self._lock:
            if id(rseq) in self._guarantees:
                self._guarantees[id(rseq)] = self._head
            self._open_reads.pop(id(rseq), None)
            self._write_cond.notify_all()

    # -- device-chunk donation hook ---------------------------------------
    def _take_exclusive(self, begin, nbyte, allow_parts=False):
        """Claim the committed device chunk covering exactly
        [begin, begin+nbyte) for buffer donation, or None when
        exclusivity cannot be established: the chunk must be
        framework-owned and this ring must have exactly one reader
        holding exactly one open span (the caller's).  With
        ``allow_parts`` (macro-gulp spans) a run of several owned
        chunks exactly tiling the range is claimed as a LIST — the
        donation proof extends chunk-by-chunk over the macro span.
        This is a point-in-time check — a second reader that is
        momentarily between spans (e.g. an unguaranteed monitor tap)
        is NOT detected and would later see zero-fill where the
        donated chunk was.  Donation is therefore opt-in (BF_DONATE /
        BlockScope(donate=True)) and requires a single-consumer
        topology by contract — see docs/transfer.md."""
        if self.space != 'tpu':
            return None
        with self._lock:
            if self._nread_open != 1 or len(self._guarantees) > 1:
                return None
            got = self._storage.take(begin, nbyte)
            if got is not None or not allow_parts:
                return got
            return self._storage.take_tiling(begin, nbyte)


class RingView(object):
    """Delegating reader-side view of a Ring: same buffer, same
    synchronization, different header transform.  (The reference implements
    this as a shallow copy over a shared C++ object, ring2.py:108-112;
    here the Python Ring *is* the implementation, so the view must forward
    every stateful operation to the base.)"""

    def __init__(self, base, header_transform=None):
        if isinstance(base, RingView):
            base = base._base_ring
        self._base_ring = base
        self.header_transform = header_transform
        self.is_view = True

    @property
    def base(self):
        return self._base_ring

    def view(self):
        return RingView(self._base_ring, self.header_transform)

    def __getattr__(self, name):
        return getattr(self._base_ring, name)

    def open_sequence(self, name, guarantee=True):
        return ReadSequence(self._base_ring, which='specific', name=name,
                            guarantee=guarantee,
                            header_transform=self.header_transform)

    def open_sequence_at(self, time_tag, guarantee=True):
        return ReadSequence(self._base_ring, which='at', time_tag=time_tag,
                            guarantee=guarantee,
                            header_transform=self.header_transform)

    def open_latest_sequence(self, guarantee=True):
        return ReadSequence(self._base_ring, which='latest',
                            guarantee=guarantee,
                            header_transform=self.header_transform)

    def open_earliest_sequence(self, guarantee=True):
        return ReadSequence(self._base_ring, which='earliest',
                            guarantee=guarantee,
                            header_transform=self.header_transform)

    def read(self, whence='earliest', guarantee=True):
        with ReadSequence(self._base_ring, which=whence,
                          guarantee=guarantee,
                          header_transform=self.header_transform) as cur_seq:
            while True:
                try:
                    yield cur_seq
                    cur_seq.increment()
                except EndOfDataStop:
                    return


class RingWriter(object):
    """Writing session: ``with ring.begin_writing() as w:``
    (reference: ring2.py:150-162)."""

    def __init__(self, ring):
        self.ring = ring
        self.ring._begin_writing()

    def __enter__(self):
        return self

    def __exit__(self, typ, value, tb):
        self.ring.end_writing()

    def begin_sequence(self, header, gulp_nframe, buf_nframe):
        return WriteSequence(self.ring, header, gulp_nframe, buf_nframe)


class _SequenceAPI(object):
    """Shared header/tensor helpers for read+write sequences
    (reference: ring2.py:164-227)."""

    @property
    def ring(self):
        return self._ring

    @property
    def name(self):
        return self._seq.name

    @property
    def time_tag(self):
        return self._seq.time_tag

    @property
    def nringlet(self):
        return self._seq.nringlet

    @property
    def header(self):
        return self._seq.header

    @property
    def tensor(self):
        if self._tensor is None:
            self._tensor = _tensor_info(self.header)
        return self._tensor


class WriteSequence(_SequenceAPI):
    def __init__(self, ring, header, gulp_nframe, buf_nframe):
        self._ring = ring
        self._tensor = None
        header['_tensor']['dtype'] = str(header['_tensor']['dtype'])
        # Round-trip through JSON: enforces serializability and decouples
        # the stored header from the caller's dict (reference stores the
        # serialized header: ring2.py:235).
        self._stored_header = json.loads(json.dumps(header))
        # Overload stamp (docs/robustness.md): on a ring running a
        # drop policy, every new sequence header carries the ring's
        # CUMULATIVE shed ledger, so consumers (including remote ones
        # — the bridge ships headers verbatim) know the stream is
        # gapped and by how much, without a telemetry channel.
        policy = getattr(ring, 'overload_policy', 'block')
        if policy != 'block':
            stats = ring.shed_stats()
            # MERGE with any stamp already riding the header: an
            # upstream hop's fields (e.g. the fabric fan-in's
            # ``fabric_gapped`` origin map — docs/fabric.md) must
            # survive this ring's own stamp, or a drop-policy hop
            # would silently strip the upstream loss disclosure
            stamp = dict(self._stored_header.get('_overload') or {})
            stamp.update({
                'policy': policy,
                'shed_gulps': stats['shed_gulps'],
                'shed_bytes': stats['shed_bytes'],
            })
            self._stored_header['_overload'] = stamp
        tensor = _tensor_info(self._stored_header)
        ring.resize(gulp_nframe * tensor['frame_nbyte'],
                    buf_nframe * tensor['frame_nbyte'],
                    tensor['nringlet'])
        name = header.get('name', '')
        time_tag = header.get('time_tag', -1)
        self._seq = ring._begin_sequence(name, time_tag,
                                         self._stored_header,
                                         tensor['nringlet'])

    @property
    def header(self):
        return self._stored_header

    def __enter__(self):
        return self

    def __exit__(self, typ, value, tb):
        self.end()

    def end(self):
        self._ring._end_sequence(self._seq)

    def reserve(self, nframe, nonblocking=False):
        return WriteSpan(self._ring, self, nframe, nonblocking)


class ReadSequence(_SequenceAPI):
    def __init__(self, ring, which='specific', name="", time_tag=None,
                 guarantee=True, header_transform=None):
        self._ring = ring
        self._tensor = None
        self.guarantee = guarantee
        self.header_transform = header_transform
        self._seq = ring._open_seq(which, name=name, time_tag=time_tag)
        ring._register_reader(self)
        rc = _ringcheck.hook(ring)
        if rc is not None:
            rc.reader_opened(self)

    def __enter__(self):
        return self

    def __exit__(self, typ, value, tb):
        self.close()

    def close(self):
        self._ring._close_read_seq(self)
        rc = _ringcheck.hook(self._ring)
        if rc is not None:
            rc.reader_closed(self)

    def increment(self):
        """Move to the next sequence (reference: ring2.py:293-298)."""
        nxt = self._ring._next_seq(self._seq)
        self._seq = nxt
        self._tensor = None
        self._ring._reader_moved(self, nxt)
        rc = _ringcheck.hook(self._ring)
        if rc is not None:
            rc.reader_moved(self, nxt.begin)

    @property
    def header(self):
        hdr = self._seq.header
        if self.header_transform is not None:
            hdr = self.header_transform(deepcopy(hdr))
            if hdr is None:
                raise ValueError("Header transform returned None")
        return hdr

    def acquire(self, frame_offset, nframe):
        return ReadSpan(self, frame_offset, nframe)

    def read(self, nframe, stride=None, begin=0):
        """Generator of gulp-sized spans (reference: ring2.py:301-311).

        Overlapped reads (stride < nframe, i.e. the consumer declared
        overlap history) acquire span N+1 BEFORE releasing span N.
        The core's reader guarantee then steps from span N's begin to
        span N+1's begin — never past the history frames both spans
        share.  The release-then-reacquire order instead advances the
        guarantee to span N's END (the drop_oldest shed accounting
        requires that for fully-consumed spans), leaving the trailing
        ``overlap`` frames unprotected for a moment; a writer that
        fills the ring in that window overwrites the reader's history
        and the next acquire comes back short (nframe_skipped > 0),
        silently corrupting the stream.  Holding ahead is only
        deadlock-free when the ring can absorb the writer's reserve
        granularity on top of both spans: while the guarantee is
        pinned at span N's begin, the writer must still be able to
        reserve up to one full ghost span past the bytes span N+1
        waits for (writer limit: reserve_head - size <=
        min_guarantee), i.e. ``size >= (nframe + stride) * frame_nbyte
        + ghost``.  When the ring is smaller, GROW it (request_resize
        is MAX-negotiated and applies at quiescence) and fall back to
        release-first — the pre-fix behavior, racy only in the
        overwrite window — until the new geometry lands; fused scopes
        that share ONE gulp of buffering simply never hold.
        """
        if stride is None:
            stride = nframe
        offset = begin
        if stride >= nframe:
            while True:
                try:
                    with self.acquire(offset, nframe) as ispan:
                        yield ispan
                        offset += stride
                except EndOfDataStop:
                    return
        fb = self.tensor['frame_nbyte']
        hold_nbyte = (nframe + stride) * fb
        prev = None
        try:
            while True:
                if prev is not None:
                    # ghost re-read each stride: the writer's first
                    # oversized reserve may grow it mid-stream
                    ring = self._ring
                    ghost = ring.ghost_span
                    need = hold_nbyte + ghost
                    if ring.total_span < need and \
                            not ring.request_resize(ghost, need):
                        prev.release()
                        prev = None
                try:
                    span = self.acquire(offset, nframe)
                except EndOfDataStop:
                    return
                if prev is not None:
                    prev.release()
                prev = span
                yield span
                offset += stride
        finally:
            if prev is not None:
                prev.release()

    def resize(self, gulp_nframe, buf_nframe=None, buffer_factor=None):
        """Reader-side buffering request; the default buffer_factor of
        3 gives the double-buffered async depth (reference:
        ring2.py:312-319), and 2 once a gulp is large
        (:func:`bifrost_tpu.memory.span_depth`: depth by bytes)."""
        tensor = self.tensor
        if buf_nframe is None:
            if buffer_factor is None:
                from .memory import span_depth
                buffer_factor = span_depth(
                    gulp_nframe * tensor['frame_nbyte'], 3)
            buf_nframe = int(np.ceil(gulp_nframe * buffer_factor))
        return self._ring.resize(gulp_nframe * tensor['frame_nbyte'],
                                 buf_nframe * tensor['frame_nbyte'])


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

class _SpanAPI(object):
    @property
    def ring(self):
        return self._ring

    @property
    def sequence(self):
        return self._sequence

    @property
    def tensor(self):
        return self._sequence.tensor

    @property
    def frame_nbyte(self):
        return self.tensor['frame_nbyte']

    @property
    def nframe(self):
        return self._nbyte // self.frame_nbyte

    @property
    def frame_offset(self):
        return (self._begin - self._sequence._seq.begin) // self.frame_nbyte

    @property
    def shape(self):
        t = self.tensor
        return t['ringlet_shape'] + [self.nframe] + t['frame_shape']

    @property
    def dtype(self):
        return self.tensor['dtype']

    def lane_memoryviews(self):
        """Zero-copy byte views over this span's ring storage, one
        contiguous ``memoryview`` per ringlet lane in ringlet-major
        order (the bridge wire layout).  Host rings only — returns
        ``None`` for device ('tpu') rings and empty spans.  Works on
        BOTH cores (the native storage also exposes per-lane
        contiguous numpy views).  The views alias the ring buffer:
        they are valid only while the span is open, and writable for
        write spans (``recv_into`` targets) as well as read spans
        (vectored ``sendmsg`` sources)."""
        if self._ring.space == 'tpu' or not self._nbyte:
            return None
        raw = self._ring._storage.read_view(self._begin, self._nbyte)
        return [memoryview(raw[i]) for i in range(raw.shape[0])]

    def _host_view(self, writeable):
        """Zero-copy strided numpy view over the ring buffer, shaped
        (*ringlet_shape, nframe, *frame_shape)."""
        raw = self._ring._storage.write_view(self._begin, self._nbyte)
        return self._typed_view(raw, writeable)

    def _typed_view(self, raw, writeable):
        t = self.tensor
        dtype = t['dtype']
        if dtype.is_packed or dtype.as_numpy_dtype().names is not None \
                or not t['frame_shape']:
            npdtype = np.uint8 if dtype.is_packed else dtype.as_numpy_dtype()
        else:
            npdtype = dtype.as_numpy_dtype()
        if npdtype == np.uint8 and dtype.is_packed:
            frame_shape = list(t['frame_shape'])
            frame_shape[-1] = frame_shape[-1] * dtype.itemsize_bits // 8
            typed = raw
        else:
            typed = raw.view(npdtype)
            frame_shape = t['frame_shape']
        shape = t['ringlet_shape'] + [self.nframe] + list(frame_shape)
        if t['nringlet'] == 1:
            view = typed.reshape(shape) if shape else typed[0, 0]
        else:
            view = typed.reshape([t['nringlet'], self.nframe] +
                                 list(frame_shape))
            view = view.reshape(shape)
        view.flags['WRITEABLE'] = writeable
        return ndarray(view, dtype=dtype, space=self._ring.space,
                       shape=self.shape)


class WriteSpan(_SpanAPI):
    """Reserved output region (reference: ring2.py:451-476).

    Host rings: ``.data`` is a writable zero-copy view.
    Device rings: assign the computed jax array with ``span.data = arr``
    or ``span.set(arr)``; nothing is copied and nothing synchronizes.
    """

    def __init__(self, ring, sequence, nframe, nonblocking=False):
        faults.fire('ring.reserve', ring.name)
        self._ring = ring
        self._sequence = sequence
        self._nbyte = nframe * sequence.tensor['frame_nbyte']
        self._closed = False
        self._commit_nbyte = None
        self._device_array = None
        self._native_id = None
        self._owned = False
        self._fill = None
        #: logical gulps this span covers (macro-gulp spans set >1 so
        #: the per-ring ``ring.<name>.gulps`` throughput counter keeps
        #: counting LOGICAL gulps when K are committed at once)
        self._ngulps = 1
        #: drop_newest overload shed (docs/robustness.md): the reserve
        #: was refused without blocking — this span is SCRATCH (no
        #: ring bytes); its commit is counted as shed, not published
        self._shed = False
        # ring-wait observability: how long the writer was blocked in
        # flow control (covers BOTH cores — the native reserve happens
        # inside this call)
        _, hist, spans_ = _observability()[:3]
        # ring-protocol checker seam (both cores): track the blocking
        # reserve and validate the granted span against the shadow
        # guarantees (BF_RINGCHECK=1; docs/analysis.md)
        rc = _ringcheck.hook(ring)
        rc_tok = rc.reserve_enter(self._nbyte) if rc is not None else None
        # overload policy at the reserve path (both cores — this
        # constructor IS the shared reserve seam); explicit
        # nonblocking callers keep WouldBlock semantics untouched
        policy = getattr(ring, 'overload_policy', 'block')
        if nonblocking:
            policy = 'block'
        if ring._h_reserve is None:
            ring._h_reserve = hist.get_or_create(
                'ring.%s.reserve_s' % ring.name, unit='s')
        shed_nbyte = 0
        with spans_.timed('%s.reserve' % ring.name, 'ring',
                          ring._h_reserve) as tm:
            try:
                if policy == 'drop_oldest':
                    self._begin, shed_nbyte = ring._reserve_span_shed(
                        self._nbyte, sequence.tensor['frame_nbyte'],
                        span=self)
                elif policy == 'drop_newest':
                    try:
                        self._begin = ring._reserve_span(
                            self._nbyte, True, span=self)
                    except WouldBlock:
                        # shed THIS gulp: the writer computes into
                        # scratch and the commit is counted instead of
                        # published
                        self._shed = True
                        self._begin = None
                else:
                    self._begin = ring._reserve_span(self._nbyte,
                                                     nonblocking,
                                                     span=self)
            except BaseException:
                if rc is not None:
                    rc.reserve_abort(rc_tok)
                raise
            if not self._shed:
                # identity across threads: the gulp follows from the
                # span's first frame in its sequence
                tm.args = {'frame': self.frame_offset}
        if self._shed:
            if rc is not None:
                rc.reserve_abort(rc_tok)
            # best-effort logical position (frame_offset): where the
            # span WOULD have landed — the committed head
            try:
                self._begin = ring.occupancy().get(
                    'head', sequence._seq.begin)
            except Exception:
                self._begin = sequence._seq.begin
            self.commit_nframe = 0
            self._data = None
            return
        if shed_nbyte and rc is not None:
            # mirror the forced guarantee advance in the shadow
            # checker BEFORE it validates this overwriting reserve
            rc.shed_advance(self._begin + self._nbyte -
                            ring.total_span)
        if rc is not None:
            rc.reserve_done(rc_tok, self, self._begin, self._nbyte,
                            ring.total_span)
        if shed_nbyte:
            # drop_oldest accounting: shed bytes are whole frames of
            # the live sequence (the audit a sequential guaranteed
            # reader performs via nframe_skipped); gulps derived from
            # the header's LOGICAL gulp
            fb = sequence.tensor['frame_nbyte']
            try:
                gulp = int(sequence.header.get('gulp_nframe', 0) or 0)
            except Exception:
                gulp = 0
            gulp_nbyte = gulp * fb if gulp > 0 else self._nbyte
            ngulps = max(1, -(-shed_nbyte // max(gulp_nbyte, 1)))
            ring._note_shed(shed_nbyte, ngulps,
                            header=sequence.header,
                            frame_end=max(
                                (self._begin + self._nbyte -
                                 ring.total_span -
                                 sequence._seq.begin) // fb, 0))
        with ring._lock:
            ring._open_wspans.append(self)
            ring._nwrite_open += 1
        # A wrapped reservation reuses buffer bytes a still-pending
        # deferred fill targets; complete those before writing.
        if ring.space != 'tpu' and getattr(ring, '_pending_fills', None):
            limit = self._begin + self._nbyte - ring.total_span
            for f in ring._fills_before(limit):
                f.wait()
        # Default to committing 0 frames so an exception in on_data doesn't
        # publish garbage (reference: ring2.py:463-464).
        self.commit_nframe = 0
        self._data = None

    @property
    def data(self):
        if self._ring.space == 'tpu':
            return self._device_array
        if self._data is None:
            if self._shed:
                # drop_newest scratch: same shape/dtype as a real
                # span, but backed by throwaway memory — the writer's
                # compute proceeds unchanged and the commit is counted
                # as shed instead of published
                t = self.tensor
                raw = np.zeros((t['nringlet'], self._nbyte),
                               dtype=np.uint8)
                self._data = self._typed_view(raw, writeable=True)
            else:
                self._data = self._host_view(writeable=True)
        return self._data

    @data.setter
    def data(self, array):
        self.set(array)

    def set(self, array, owned=False):
        """Publish a computed gulp into this span.  ``owned=True``
        (device rings) marks the array as created exclusively for this
        ring — the committed chunk is then eligible for buffer donation
        downstream (ring._take_exclusive).  A complex gulp computed on
        two real planes may be set as them (devrep.ComplexPlanes), a
        ci8 gulp as its int16 words (devrep.ComplexWords): readers of
        ``.data`` see the complex array, or the int8 (re, im) pairs,
        all the same."""
        if self._ring.space == 'tpu':
            if isinstance(array, ndarray):
                array = array.as_jax()
            self._device_array = array
            self._owned = bool(owned)
        else:
            from .ndarray import copy_array
            copy_array(self.data, array)
        return self

    def set_fill(self, fill):
        """Publish this host span's bytes as a deferred D2H fill
        (xfer.HostFill targeting a view of this span): the span commits
        immediately and readers gate on the fill, so the writer never
        hard-syncs on the transfer."""
        if self._ring.space == 'tpu':
            raise ValueError("set_fill is for host-space rings")
        self._fill = fill
        return self

    def commit(self, nframe):
        assert nframe <= self.nframe
        self.commit_nframe = nframe

    def __enter__(self):
        return self

    def __exit__(self, typ, value, tb):
        self.close()

    def close(self):
        commit_nbyte = self.commit_nframe * self.frame_nbyte
        if self._shed:
            # drop_newest: nothing entered the ring — account what the
            # writer WOULD have published (0 frames on the exception
            # path: nothing was lost, nothing is counted)
            if commit_nbyte:
                self._ring._note_shed(
                    commit_nbyte, self._ngulps,
                    header=self._sequence.header,
                    frame_end=self.frame_offset + self.commit_nframe)
            if self._fill is not None:
                self._fill.cancel()
            return
        if self._ring.space != 'tpu':
            if self._fill is not None:
                if commit_nbyte == self._nbyte:
                    # commit now, bytes later: the fill redoes the
                    # ghost mirror once data lands; readers gate on it
                    self._fill.attach(self._ring, self._begin,
                                      commit_nbyte)
                    self._ring._register_fill(self._fill)
                elif commit_nbyte:
                    # PARTIAL commit: the fill targets the full span
                    # view, but the truncated tail's bytes roll back
                    # and become re-reservable the moment this commit
                    # lands — complete the fill NOW, while the whole
                    # reservation is still ours
                    self._fill.attach(self._ring, self._begin,
                                      commit_nbyte)
                    self._fill.wait()
                else:
                    # nothing published: a late write would land in
                    # re-reservable bytes
                    self._fill.cancel()
            elif commit_nbyte:
                self._ring._storage.commit_ghost(self._begin,
                                                 commit_nbyte)
        # protocol checker seam BEFORE the core commit: an illegal
        # commit (double / out-of-order partial) is caught before it
        # can corrupt core state (BF_RINGCHECK=1)
        rc = _ringcheck.hook(self._ring)
        if rc is not None:
            rc.commit(self, commit_nbyte)
        self._ring._commit_span(self, commit_nbyte)
        if faults.armed('ring.corrupt.double_commit', self._ring.name):
            # deliberate corruption: commit the same span AGAIN — the
            # checker (when armed) raises before the core sees it
            if rc is not None:
                rc.commit(self, commit_nbyte)
            self._ring._commit_span(self, commit_nbyte)

    def _finalize_storage(self, commit_nbyte):
        # called under ring lock once this commit lands in order
        if self._ring.space == 'tpu' and self._device_array is not None:
            t = self._sequence.tensor
            arr = self._device_array
            taxis = len(t['ringlet_shape'])
            nframe_c = commit_nbyte // t['frame_nbyte']
            if nframe_c < self.nframe:
                idx = [slice(None)] * arr.ndim
                idx[taxis] = slice(0, nframe_c)
                arr = arr[tuple(idx)]
            self._ring._storage.put(self._begin, commit_nbyte, arr,
                                    taxis, owned=self._owned)


class ReadSpan(_SpanAPI):
    """Acquired input region (reference: ring2.py:478-503)."""

    def __init__(self, sequence, frame_offset, nframe):
        faults.fire('ring.acquire', sequence.ring.name)
        self._ring = sequence.ring
        self._sequence = sequence
        t = sequence.tensor
        fb = t['frame_nbyte']
        # ring-wait observability: reader blocked-time in flow control
        # (both cores — the native acquire happens inside this call)
        _, hist, spans_ = _observability()[:3]
        # ring-protocol checker seam (both cores): track the blocking
        # acquire and validate the granted span against the shadow
        # committed head (BF_RINGCHECK=1; docs/analysis.md)
        rc = _ringcheck.hook(self._ring)
        rc_tok = rc.acquire_enter(
            sequence, sequence._seq.begin + frame_offset * fb) \
            if rc is not None else None
        ring = self._ring
        if ring._h_acquire is None:
            ring._h_acquire = hist.get_or_create(
                'ring.%s.acquire_s' % ring.name, unit='s')
        with spans_.timed('%s.acquire' % ring.name, 'ring',
                          ring._h_acquire) as tm:
            try:
                begin, nbyte = ring._acquire_span(
                    sequence, frame_offset * fb, nframe * fb, fb)
            except BaseException:
                if rc is not None:
                    rc.acquire_abort(rc_tok)
                raise
            tm.args = {'frame': (begin - sequence._seq.begin) // fb}
        if rc is not None:
            rc_nbyte = nbyte
            if faults.armed('ring.corrupt.acquire_uncommitted',
                            self._ring.name):
                # deliberate corruption: report a span extending one
                # frame past what the core returned, simulating a core
                # that hands out frames no commit ever published
                rc_nbyte = nbyte + fb
            rc.acquire_done(rc_tok, sequence, begin, rc_nbyte)
        if faults.armed('ring.corrupt.guarantee_jump',
                        self._ring.name):
            # deliberate corruption: jump this reader's CORE guarantee
            # to the head while this span is still open (the pre-PR-5
            # watermark bug) — the checker catches the overwriting
            # reserve the core now admits
            self._ring._corrupt_guarantee_jump(sequence)
        self._begin, self._nbyte = begin, nbyte
        self.requested_frame_offset = frame_offset
        self.nframe_skipped = min(self.frame_offset - frame_offset, nframe)
        if self._ring.space != 'tpu' and nbyte:
            # materialize any in-flight D2H fill overlapping this span
            # before exposing its bytes (outside the ring lock; by now
            # the transfer has usually finished — residual wait only).
            # A FAILED fill raises here: release the just-acquired span
            # first so the ring's open-span accounting stays balanced
            # while the error propagates to the block's failure policy.
            try:
                for f in self._ring._fills_overlapping(begin, nbyte):
                    f.wait()
                self._ring._storage.refresh_ghost(begin, nbyte)
            except BaseException:
                if rc is not None:
                    rc.release(sequence, begin, nbyte)
                self._ring._release_span(sequence, begin)
                raise
        self._data = None

    @property
    def data(self):
        if self._data is not None:
            return self._data
        if self._ring.space == 'tpu':
            t = self.tensor

            def zeros_fn(nframe):
                from .devrep import device_rep_zeros
                shape = (t['ringlet_shape'] + [nframe] + t['frame_shape'])
                return _whole(device_rep_zeros(shape, t['dtype']))

            data = self._ring._storage.get(
                self._begin, self._nbyte, t['frame_nbyte'], zeros_fn)
            from .devrep import device_rep_dtype
            shape = tuple(self.shape) + \
                ((2,) if device_rep_dtype(t['dtype'])[1] else ())
            if tuple(data.shape) != shape and \
                    data.size == int(np.prod(shape, dtype=np.int64)):
                # a view that merges, splits or drops axes
                # (bifrost_tpu.views) changes the header alone: the
                # writer's array takes the shape the reader's header
                # gives it, its elements in the same order
                data = data.reshape(shape)
            self._data = data
        else:
            self._data = self._host_view(writeable=False)
        return self._data

    @property
    def planes(self):
        """Device rings: this span's chunk as the
        :class:`~bifrost_tpu.devrep.ComplexPlanes` its writer set, for
        a reader that can use the two real planes of a complex array
        and spare the device the array itself; None where the span is
        not exactly one such chunk (``.data`` is the complex array
        either way)."""
        if self._ring.space != 'tpu':
            return None
        return self._ring._storage.chunk_as(ComplexPlanes, self._begin,
                                            self._nbyte)

    @property
    def words(self):
        """Device rings: this span's chunk as the
        :class:`~bifrost_tpu.devrep.ComplexWords` an H2D copy of a ci8
        gulp set (one int16 a complex sample, the host's bytes in the
        host's order), for a reader whose program starts from the
        words and spares the device the int8 array with its (re, im)
        axis; None where the span is not exactly one such chunk
        (``.data`` is the int8 array either way)."""
        if self._ring.space != 'tpu':
            return None
        return self._ring._storage.chunk_as(ComplexWords, self._begin,
                                            self._nbyte)

    def take_data(self, allow_parts=False):
        """Device rings: claim this span's committed chunk exclusively
        for buffer donation (the array is consumed in place by a
        donating jit and must not be read again).  Returns the array,
        or None when exclusivity cannot be proven — partial span,
        multi-chunk stitch, multi-reader ring, or a chunk the framework
        does not own (WriteSpan.set(..., owned=True)).  Callers fall
        back to ``.data`` on None.  The chunk of a ci8 gulp that an
        H2D copy set comes as it is held, its words
        (:class:`~bifrost_tpu.devrep.ComplexWords`): ``chunk.pairs()``
        is the int8 array, made for the caller alone.

        ``allow_parts=True`` (macro-gulp spans) additionally claims a
        run of owned chunks exactly tiling the span, returned as a
        LIST in offset order.  The caller must consume every part —
        after a parts claim this span's ``.data`` would zero-fill."""
        if self._ring.space != 'tpu' or self._data is not None \
                or not self._nbyte:
            return None
        arr = self._ring._take_exclusive(self._begin, self._nbyte,
                                         allow_parts=allow_parts)
        if arr is not None and not isinstance(arr, list):
            self._data = arr
        return arr

    @property
    def nframe_overwritten(self):
        """Frames of this span overwritten while held — unguaranteed
        readers use this to detect they fell behind
        (reference: ring2.py:491-497)."""
        if self._sequence.guarantee:
            return 0
        nbyte = self._ring._overwritten_in(self._begin, self._nbyte)
        return -(-nbyte // self.frame_nbyte) if nbyte else 0

    def __enter__(self):
        return self

    def __exit__(self, typ, value, tb):
        self.release()

    def release(self):
        # protocol checker seam BEFORE the core release: a double
        # release is caught before it can unbalance core accounting
        rc = _ringcheck.hook(self._ring)
        if rc is not None:
            rc.release(self._sequence, self._begin, self._nbyte)
        self._ring._release_span(self._sequence, self._begin)
        if faults.armed('ring.corrupt.double_release',
                        self._ring.name):
            # deliberate corruption: release the same span AGAIN — the
            # checker (when armed) raises before the core sees it
            if rc is not None:
                rc.release(self._sequence, self._begin)
            self._ring._release_span(self._sequence, self._begin)
