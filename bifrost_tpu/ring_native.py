"""NativeRing: host-space Ring backed by the C++ core (native/ring.cpp).

Implements the same internal protocol as the Python Ring — the
WriteSequence/ReadSequence/WriteSpan/ReadSpan wrappers in ring.py are
shared, so behavior-visible semantics are identical; only the locked
state machine and the byte buffer live in C++.  Flow control (blocking
reserve/acquire, guarantees, the in-order commit barrier, ghost copies,
live resize) all run native, releasing the GIL while blocked.
"""

from __future__ import annotations

import ctypes
import json
import threading

import numpy as np

from . import native
from .analysis import ringcheck as _ringcheck
from .testing import faults
from .ring import (Ring, EndOfDataStop, WouldBlock, RingPoisonedError,
                   _observability)

__all__ = ['NativeRing']

_WHICH = {'specific': 0, 'at': 1, 'latest': 2, 'earliest': 3}


class _NativeSeq(object):
    """Sequence facade over a native handle (attributes match the Python
    core's _Sequence)."""

    __slots__ = ('_lib', '_handle', 'name', 'time_tag', 'header', 'begin',
                 'nringlet')

    def __init__(self, lib, handle):
        self._lib = lib
        self._handle = handle
        name = ctypes.c_char_p()
        ttag = ctypes.c_longlong()
        hdr = ctypes.c_char_p()
        hlen = ctypes.c_longlong()
        begin = ctypes.c_longlong()
        nrl = ctypes.c_longlong()
        native.check(lib.bft_seq_info(
            handle, ctypes.byref(name), ctypes.byref(ttag),
            ctypes.byref(hdr), ctypes.byref(hlen), ctypes.byref(begin),
            ctypes.byref(nrl)), 'seq_info')
        self.name = (name.value or b'').decode()
        self.time_tag = ttag.value
        raw = ctypes.string_at(hdr, hlen.value) if hlen.value else b'{}'
        self.header = json.loads(raw.decode())
        self.begin = begin.value
        self.nringlet = nrl.value

    @property
    def end(self):
        e = ctypes.c_longlong()
        native.check(self._lib.bft_seq_end_offset(self._handle,
                                                  ctypes.byref(e)))
        return None if e.value < 0 else e.value

    @property
    def finished(self):
        return self.end is not None


class _NativeStorage(object):
    """Zero-copy numpy views over the native buffer.  Ghost maintenance
    happens inside the C core (commit/acquire), so the hook methods are
    no-ops here."""

    def __init__(self, ring):
        self._ring = ring

    def _view(self, offset, nbyte):
        lib = self._ring._lib
        buf = ctypes.POINTER(ctypes.c_ubyte)()
        size = ctypes.c_longlong()
        ghost = ctypes.c_longlong()
        nrl = ctypes.c_longlong()
        native.check(lib.bft_ring_geometry(
            self._ring._handle, ctypes.byref(buf), ctypes.byref(size),
            ctypes.byref(ghost), ctypes.byref(nrl)), 'geometry')
        lane = size.value + ghost.value
        total = nrl.value * lane
        base = np.ctypeslib.as_array(buf, shape=(total,))
        bo = offset % size.value
        lanes = np.lib.stride_tricks.as_strided(
            base[bo:], shape=(nrl.value, nbyte), strides=(lane, 1))
        return lanes

    def write_view(self, offset, nbyte):
        return self._view(offset, nbyte)

    read_view = write_view

    def commit_ghost(self, offset, nbyte):
        pass   # done by bft_ring_commit

    def refresh_ghost(self, offset, nbyte):
        pass   # done by bft_reader_acquire

    def discard_before(self, offset):
        pass

    def fill_ghost_mirror(self, offset, nbyte):
        """Re-run the wrap-around ghost mirror after a deferred D2H
        fill (xfer.HostFill) landed: the C core mirrored at commit
        time, BEFORE the fill's bytes existed, so a wrapped span's
        overflow must be mirrored back to the buffer start again."""
        lib = self._ring._lib
        buf = ctypes.POINTER(ctypes.c_ubyte)()
        size = ctypes.c_longlong()
        ghost = ctypes.c_longlong()
        nrl = ctypes.c_longlong()
        native.check(lib.bft_ring_geometry(
            self._ring._handle, ctypes.byref(buf), ctypes.byref(size),
            ctypes.byref(ghost), ctypes.byref(nrl)), 'geometry')
        bo = offset % size.value
        over = bo + nbyte - size.value
        if over <= 0:
            return
        lane = size.value + ghost.value
        base = np.ctypeslib.as_array(buf, shape=(nrl.value * lane,))
        lanes = base.reshape(nrl.value, lane)
        lanes[:, :over] = lanes[:, size.value:size.value + over]


class NativeRing(Ring):
    def __init__(self, space='system', name=None, owner=None, core=None):
        super(NativeRing, self).__init__(space=space, name=name,
                                         owner=owner, core=core)
        self._lib = native.load()
        if self._lib is None:
            raise native.NativeError("native library unavailable")
        handle = ctypes.c_void_p()
        native.check(self._lib.bft_ring_create(
            ctypes.byref(handle), self.name.encode()), 'create')
        self._handle = handle
        if core is not None and not isinstance(core, (list, tuple)):
            # NUMA-bind ring allocations to this core's node
            # (reference: ring_impl.cpp:164-166)
            self._lib.bft_ring_set_core(handle, int(core))
        elif isinstance(core, (list, tuple)) and core:
            self._lib.bft_ring_set_core(handle, int(core[0]))
        self._storage = _NativeStorage(self)
        self._seq_cache = {}    # native ptr -> _NativeSeq
        self._cache_lock = threading.Lock()
        #: live native reader ids — poison() releases their guarantees
        #: so writers blocked inside bft_ring_reserve wake up
        self._native_reader_ids = set()
        #: deferred D2H fills holding a C-side resize hold: each one's
        #: cached numpy view into the native buffer would dangle under
        #: a deferred-resize re-layout (released by _prune_fill_holds)
        self._fill_holds = []

    def __del__(self):
        try:
            if getattr(self, '_handle', None) is not None and \
                    not getattr(self, 'is_view', False):
                self._lib.bft_ring_destroy(self._handle)
                self._handle = None
        except Exception:
            pass

    _SEQ_CACHE_MAX = 64

    def _wrap_seq(self, handle_value):
        with self._cache_lock:
            seq = self._seq_cache.get(handle_value)
            if seq is None:
                seq = _NativeSeq(self._lib, ctypes.c_void_p(handle_value))
                self._seq_cache[handle_value] = seq
                # bound the cache: retired sequences' parsed headers can
                # be large; evict oldest entries (LRU-ish insertion order)
                while len(self._seq_cache) > self._SEQ_CACHE_MAX:
                    self._seq_cache.pop(next(iter(self._seq_cache)))
            return seq

    # -- geometry ---------------------------------------------------------
    def resize(self, contiguous_bytes, total_bytes=None, nringlet=1):
        # deferred D2H fills hold numpy views into the current native
        # buffer; complete them before the core may re-layout it.
        # (Best-effort for the native core: a fill registered between
        # the last check and the C resize could still target the old
        # buffer — in practice resizes happen at sequence start and
        # fills drain within the engine's bounded depth.)
        for _ in range(8):
            fills = [f for f in self._pending_fills if not f.done]
            if not fills:
                break
            for f in fills:
                f.wait()
        native.check(self._lib.bft_ring_resize(
            self._handle, contiguous_bytes,
            -1 if total_bytes is None else total_bytes, nringlet),
            'resize')
        self._write_ring_proclog()

    def request_resize(self, contiguous_bytes, total_bytes=None,
                       nringlet=1):
        """Non-blocking grow request (see :meth:`Ring.request_resize`):
        recorded in the C core and applied by the native commit /
        release paths the moment the ring goes quiescent.  Deferred
        D2H fills block the apply through C-side resize holds
        (released here and at the acquire-path fill prunes once the
        fill completes), so a re-layout can never dangle a fill's
        cached buffer view.  Idempotent — callers re-issue until it
        reports True (applied)."""
        self._prune_fill_holds()
        rc = _ringcheck.hook(self)
        if rc is not None:
            total = total_bytes if total_bytes is not None \
                else contiguous_bytes * 4
            rc.resize_requested(contiguous_bytes, total)
            if faults.armed('ring.corrupt.resize_under_span',
                            self.name):
                rc.resize_applied(self._nwrite_open,
                                  self._nread_open, int(total))
        applied = ctypes.c_int()
        native.check(self._lib.bft_ring_request_resize(
            self._handle, contiguous_bytes,
            -1 if total_bytes is None else total_bytes, int(nringlet),
            ctypes.byref(applied)), 'request_resize')
        if applied.value:
            self._write_ring_proclog()
        else:
            # the C core will apply at a commit/release quiescence
            # point: watch for it there so the rings/<name> proclog
            # reflects the new geometry when it lands
            self._resize_proclog_watch = True
        return bool(applied.value)

    @property
    def resize_pending(self):
        pending = ctypes.c_int()
        native.check(self._lib.bft_ring_resize_pending(
            self._handle, ctypes.byref(pending)))
        return bool(pending.value)

    # -- deferred-fill resize holds ---------------------------------------
    def _register_fill(self, fill):
        super(NativeRing, self)._register_fill(fill)
        # the fill writes through a numpy view of the CURRENT native
        # buffer after its span closes: block the C core's deferred-
        # resize apply until it completes
        with self._lock:
            self._fill_holds.append(fill)
        try:
            self._lib.bft_ring_resize_hold(self._handle, 1)
        except Exception:
            pass

    def _prune_fill_holds(self):
        with self._lock:
            done = [f for f in self._fill_holds if f.done]
            self._fill_holds = [f for f in self._fill_holds
                                if not f.done]
        for _ in done:
            try:
                self._lib.bft_ring_resize_hold(self._handle, -1)
            except Exception:
                pass

    def _fills_overlapping(self, begin, nbyte):
        out = super(NativeRing, self)._fills_overlapping(begin, nbyte)
        self._prune_fill_holds()
        return out

    def _fills_before(self, limit):
        out = super(NativeRing, self)._fills_before(limit)
        self._prune_fill_holds()
        return out

    def _write_ring_proclog(self):
        """Geometry proclog for the monitor tools; queries the native
        core (overrides Ring._write_ring_proclog, which reads the
        Python core's attributes)."""
        try:
            from .proclog import ProcLog
            size = ctypes.c_longlong()
            ghost = ctypes.c_longlong()
            nringlet = ctypes.c_longlong()
            native.check(self._lib.bft_ring_geometry(
                self._handle, None, ctypes.byref(size),
                ctypes.byref(ghost), ctypes.byref(nringlet)))
            self._publish_capacity(size.value, ghost.value,
                                   nringlet.value)
            if getattr(self, '_geom_proclog', None) is None:
                self._geom_proclog = ProcLog('rings/%s' % self.name)
            self._geom_proclog.update({
                'space': self.space,
                'core': -1 if self.core is None else self.core,
                'ghost': ghost.value,
                'span': ghost.value,
                'stride': size.value,
                'nringlet': max(nringlet.value, 1),
            }, force=True)
        except Exception:
            pass

    @property
    def total_span(self):
        size = ctypes.c_longlong()
        native.check(self._lib.bft_ring_geometry(
            self._handle, None, ctypes.byref(size), None, None))
        return size.value

    @property
    def ghost_span(self):
        ghost = ctypes.c_longlong()
        native.check(self._lib.bft_ring_geometry(
            self._handle, None, None, ctypes.byref(ghost), None))
        return ghost.value

    @property
    def nringlet(self):
        nrl = ctypes.c_longlong()
        native.check(self._lib.bft_ring_geometry(
            self._handle, None, None, None, ctypes.byref(nrl)))
        return nrl.value

    def occupancy(self):
        """Flow-control snapshot read from the native core (the Python
        attributes are unused by this core)."""
        tail = ctypes.c_longlong()
        head = ctypes.c_longlong()
        size = ctypes.c_longlong()
        try:
            native.check(self._lib.bft_ring_tail_head(
                self._handle, ctypes.byref(tail), ctypes.byref(head)))
            native.check(self._lib.bft_ring_geometry(
                self._handle, None, ctypes.byref(size), None, None))
        except native.NativeError as exc:
            return {'error': repr(exc)}
        return {'tail': tail.value, 'head': head.value,
                'size': size.value,
                'poisoned': self._poisoned is not None}

    # -- poisoning --------------------------------------------------------
    def _wake_external(self):
        """Wake threads blocked inside the C core: end_writing releases
        blocked readers / sequence waiters (they observe EOD, and the
        Python wrappers convert that to RingPoisonedError), and moving
        every live reader guarantee up to the head releases the space
        blocked writers are waiting for (the data no longer matters —
        the ring is dead)."""
        try:
            self._lib.bft_ring_end_writing(self._handle)
            head = ctypes.c_longlong()
            native.check(self._lib.bft_ring_tail_head(
                self._handle, None, ctypes.byref(head)))
            with self._lock:
                rids = list(self._native_reader_ids)
            for rid in rids:
                # mode 2: force past open spans (a held span must not
                # keep a blocked writer waiting on a dead ring)
                self._lib.bft_reader_set_guarantee(
                    self._handle, rid, head.value, 2)
        except Exception:
            pass

    # -- protocol-corruption hook (testing/faults.py; docs/analysis.md) ---
    def _corrupt_guarantee_jump(self, rseq):
        """Deliberately force ``rseq``'s guarantee in the C core forward
        to the head while it may still hold open spans (mode 2 = force
        past open spans) — the native-core arm of the
        ``ring.corrupt.guarantee_jump`` fault seam, so tests prove the
        ring-protocol checker catches the overwriting reserve the
        corrupted core then admits."""
        rid = getattr(rseq, '_native_reader_id', None)
        if rid is None:
            return
        head = ctypes.c_longlong()
        try:
            native.check(self._lib.bft_ring_tail_head(
                self._handle, None, ctypes.byref(head)))
            self._lib.bft_reader_set_guarantee(self._handle, rid,
                                               head.value, 2)
        except Exception:
            pass

    # -- writer side ------------------------------------------------------
    def _begin_writing(self):
        with self._lock:
            self._writing = True
            self._eod = False
        native.check(self._lib.bft_ring_begin_writing(self._handle))

    def end_writing(self):
        with self._lock:
            self._writing = False
            self._eod = True
        native.check(self._lib.bft_ring_end_writing(self._handle))

    def _begin_sequence(self, name, time_tag, header, nringlet):
        self._check_poison()
        hdr = json.dumps(header).encode()
        out = ctypes.c_void_p()
        rc = self._lib.bft_ring_begin_sequence(
            self._handle, name.encode(), int(time_tag), hdr, len(hdr),
            int(nringlet), ctypes.byref(out))
        if rc == -2:
            raise RuntimeError(
                "Cannot begin sequence %r: previous sequence is still "
                "open" % name)
        native.check(rc, 'begin_sequence')
        return self._wrap_seq(out.value)

    def _end_sequence(self, seq):
        native.check(self._lib.bft_ring_end_sequence(self._handle,
                                                     seq._handle))

    def _reserve_span(self, nbyte, nonblocking=False, span=None):
        if span is None:
            raise RuntimeError("NativeRing reserve requires a span object")
        self._check_poison()
        begin = ctypes.c_longlong()
        sid = ctypes.c_longlong()
        rc = self._lib.bft_ring_reserve(
            self._handle, nbyte, 1 if nonblocking else 0,
            ctypes.byref(begin), ctypes.byref(sid))
        # poison may have landed while blocked inside the C core (its
        # wakeup hands back a now-meaningless reservation)
        self._check_poison()
        if rc == native.BFT_WOULD_BLOCK:
            raise WouldBlock()
        native.check(rc, 'reserve')
        span._native_id = sid.value
        return begin.value

    def _reserve_span_shed(self, nbyte, frame_nbyte, span=None):
        """drop_oldest overload reserve (see Ring._reserve_span_shed):
        the guarantee-advance shed protocol runs inside the C core
        (bft_ring_reserve_shed) under the ring mutex; the counted
        min-guarantee advance comes back as shed bytes."""
        if span is None:
            raise RuntimeError("NativeRing reserve requires a span "
                               "object")
        self._check_poison()
        begin = ctypes.c_longlong()
        sid = ctypes.c_longlong()
        shed = ctypes.c_longlong()
        rc = self._lib.bft_ring_reserve_shed(
            self._handle, nbyte, int(max(frame_nbyte or 1, 1)),
            ctypes.byref(begin), ctypes.byref(sid),
            ctypes.byref(shed))
        self._check_poison()
        native.check(rc, 'reserve_shed')
        span._native_id = sid.value
        return begin.value, shed.value

    def _commit_span(self, wspan, commit_nbyte):
        native.check(self._lib.bft_ring_commit(
            self._handle, wspan._native_id, commit_nbyte), 'commit')
        with self._lock:
            if wspan in self._open_wspans:
                self._open_wspans.remove(wspan)
                self._nwrite_open -= 1
        if getattr(self, '_resize_proclog_watch', False) \
                and not self.resize_pending:
            self._resize_proclog_watch = False
            self._write_ring_proclog()   # deferred resize landed
        if commit_nbyte:
            # shared commit telemetry (Ring._note_commit): the per-ring
            # logical-gulp throughput counter the exporter derives
            # gulps/s from, macro spans crediting their K gulps; the
            # sharded-chunk accounting inside is a no-op here (native
            # rings are host-space — no device arrays)
            self._note_commit(wspan, commit_nbyte)

    # -- reader side ------------------------------------------------------
    def _register_reader(self, rseq):
        rid = ctypes.c_longlong()
        native.check(self._lib.bft_reader_create(
            self._handle, 1 if rseq.guarantee else 0, ctypes.byref(rid)),
            'reader_create')
        rseq._native_reader_id = rid.value
        with self._lock:
            self._native_reader_ids.add(rid.value)
        if rseq.guarantee:
            # clamp-forward-only: bft_reader_create seeded the guarantee
            # at the current tail; never move it backward below the tail
            # (would deadlock the writer against unreadable space)
            native.check(self._lib.bft_reader_set_guarantee(
                self._handle, rid.value, rseq._seq.begin, 1))

    def _reader_moved(self, rseq, new_seq):
        if rseq.guarantee:
            native.check(self._lib.bft_reader_set_guarantee(
                self._handle, rseq._native_reader_id, new_seq.begin, 1))

    def _open_seq(self, which, name=None, time_tag=None):
        self._check_poison()
        out = ctypes.c_void_p()
        rc = self._lib.bft_ring_open_sequence(
            self._handle, _WHICH[which], (name or '').encode(),
            int(time_tag or 0), ctypes.byref(out))
        self._check_poison()
        if rc == native.BFT_END_OF_DATA:
            raise EndOfDataStop("No sequence available")
        native.check(rc, 'open_sequence')
        return self._wrap_seq(out.value)

    def _next_seq(self, seq):
        self._check_poison()
        out = ctypes.c_void_p()
        rc = self._lib.bft_seq_next(self._handle, seq._handle,
                                    ctypes.byref(out))
        self._check_poison()
        if rc == native.BFT_END_OF_DATA:
            raise EndOfDataStop("No next sequence")
        native.check(rc, 'seq_next')
        return self._wrap_seq(out.value)

    def _acquire_span(self, rseq, offset, nbyte, frame_nbyte):
        self._check_poison()
        begin = ctypes.c_longlong()
        got = ctypes.c_longlong()
        rc = self._lib.bft_reader_acquire(
            self._handle, rseq._native_reader_id, rseq._seq._handle,
            offset, nbyte, frame_nbyte, ctypes.byref(begin),
            ctypes.byref(got))
        # the poison wakeup surfaces as END_OF_DATA (or a partial span)
        # from the C core; report the true cause instead
        self._check_poison()
        if rc == native.BFT_END_OF_DATA:
            raise EndOfDataStop("Sequence consumed")
        native.check(rc, 'acquire')
        return begin.value, got.value

    def _release_span(self, rseq, span_begin):
        native.check(self._lib.bft_reader_release(
            self._handle, rseq._native_reader_id, span_begin), 'release')
        if getattr(self, '_resize_proclog_watch', False) \
                and not self.resize_pending:
            self._resize_proclog_watch = False
            self._write_ring_proclog()   # deferred resize landed

    def _close_read_seq(self, rseq):
        rid = getattr(rseq, '_native_reader_id', None)
        if rid is not None:
            with self._lock:
                self._native_reader_ids.discard(rid)
            native.check(self._lib.bft_reader_destroy(self._handle, rid))
            rseq._native_reader_id = None

    def _overwritten_in(self, begin, nbyte):
        out = ctypes.c_longlong()
        native.check(self._lib.bft_ring_overwritten_in(
            self._handle, begin, nbyte, ctypes.byref(out)))
        return out.value
