"""Asynchronous host↔device transfer engine.

The original module exposed two blocking primitives: ``to_device``
(which made a *defensive* full copy of every host gulp, because on the
CPU backend ``device_put`` of an aligned numpy array is ZERO-COPY and
the resulting array would alias ring-buffer memory the writer recycles)
and ``to_host`` (which hard-synced on every D2H via ``np.asarray``).
That put one full host copy plus one hard synchronization on the gulp
path of every host↔device pipeline — the round-5 verdict's top-cited
bottleneck.

This engine replaces both with a pipelined staging layer, the TPU
analogue of bifrost's per-block CUDA streams + async memcpy
(reference: src/cuda.cpp streams; Cranmer et al. 2017):

- **H2D staging ring** — a host gulp the caller may recycle at once is
  copied once into a small, 128-byte-aligned staging buffer and
  shipped with ``device_put`` (zero-copy on the CPU backend, async DMA
  on TPU).  On copying backends the buffers form a reusable ring,
  recycled once the DMA is observed complete.  On zero-copy backends
  each transfer gets a fresh aligned buffer: the device array aliases
  the buffer for its whole lifetime, and reuse is provably unsafe even
  after the array dies (an in-flight computation still reads it) — but
  alignment alone already halves the copy count versus the old
  defensive ``np.array`` (which landed unaligned and forced the
  runtime into a second copy).  Both modes preserve the
  aliasing-safety the old defensive copy bought.

- **H2D from the ring span** — a gulp that lies in a read span of a
  host ring (a ``CopyBlock``'s input) is not copied at all on a
  copying backend: ``device_put`` is given the span's own memory and
  the span stays open, a second open span of the same reader over the
  same bytes, until the runtime has let go of the memory
  (:class:`_Hold`; docs/transfer.md, "From the ring span").  Who can
  be served so is decided from what the engine can observe
  (:meth:`TransferEngine._lendable`); everybody else is staged.

- **non-blocking D2H** — ``to_host_async`` starts the readback with
  ``copy_to_host_async()`` and returns a :class:`TransferFuture`; a
  bounded completion queue (drained by the pipeline's dispatch-ahead
  loop) retires finished transfers without a hard sync.  ``to_host``
  keeps its blocking contract but now *starts* the DMA before
  converting, so the wait only covers the in-flight remainder.

- **deferred ring fills** — :class:`HostFill` lets a block commit a
  host ring span whose bytes are still in flight; the ring gates
  readers on the fill (see ring.py), so the writer thread never blocks
  on D2H.  The engine's own completion threads (``xfer-d2h-<n>``,
  ``_D2H_WORKERS`` of them) take the transfer's result and copy it
  into the span, oldest fill first; a fill is claimed once, by
  whoever completes it.  A reader (or a wrapping writer, a resize, the depth
  bound) that needs a fill nobody has claimed yet completes it
  itself; one that finds it claimed waits for it to land
  (``d2h.peer_wait``).  A block's per-gulp ``drain()`` neither
  completes a fill nor waits for one.  A large product crosses in
  pieces small enough for the allocator to keep between products
  (``_D2H_PIECE_BYTES``), whatever its dtype and whichever axis has to
  be cut, each copied into its place in the span as it arrives
  (:class:`_PieceFuture`; docs/transfer.md).  A LARGE product whose
  cut has no complex argument is cut up, all of it, when its landing
  starts: by ``host_fill`` where nothing is landing, else by the
  completion thread before it announces the landing ahead of it, so
  that the programs a producer dispatches the moment it is let go
  queue behind the cuts and not the cuts behind them.

Device to host, a product that crosses whole crosses as it is: the
local v5e runtime transfers complex64 bit-exactly both ways
(chip_smoke.py fact ii, PR 21), and complex128 exists on the CPU
backend alone, where ``np.asarray`` hands it over as it is.  The
pieces of a complex product do not: the runtime interleaves a
complex64 array on the host, one transfer at a time, at 2.5 GB/s, so
the cut program interleaves on the device and each piece leaves as
rows of 32-bit words, re and im element by element, which the host
sees as complex again with a view (:func:`_pairs`; PERF.md section 6,
PR 29).  A product that reaches the engine as the two float32 planes
it was computed in (:class:`~bifrost_tpu.devrep.ComplexPlanes`: a
correlator's, through ``ReadSpan.planes``) is cut from the planes, so
no program between the integration and the host has a complex64
argument or result: a program with one splits the whole of it into
planes first, 13 ms for 2.1 GB, in each of sixteen cuts a product
(PERF.md section 6, PR 31), which is also what makes cutting such a
product up at once affordable (PR 32).  A pair that crosses whole is
joined first and crosses as the complex64 it stands for.  Host to
device, complex data still goes as (re, im) float planes recombined
under jit (ROADMAP D6).  A ci8 gulp is no complex array to this
engine: ``devrep.to_device_rep`` hands it over as the int16 words the
host holds (one device) or as int8 (re, im) pairs (a mesh), and the
words of a device ring come back as the int16 array they are, into
the span seen as int16 words (:meth:`TransferEngine.host_fill`;
docs/transfer.md, "Words").

Tunables (environment):

- ``BF_XFER_ASYNC=0``      disable the async engine (legacy blocking
                           behavior; also implied by BF_SYNC_STRICT=1)
- ``BF_XFER_DEPTH``        max in-flight async D2H transfers (default
                           4), and never more than
                           ``memory.INFLIGHT_BYTES`` of them besides
                           the newest
- ``BF_XFER_STAGING``      staging slots per (shape, dtype) (default 4)
- ``BF_XFER_STAGE_MIN``    min bytes to use a staging slot, or to ship
                           from a ring span (default 16384)
"""

from __future__ import annotations

import atexit
import os
import threading
import weakref
from collections import deque
from contextlib import contextmanager

import numpy as np

from .testing import faults

__all__ = ['to_device', 'to_device_batch', 'to_host', 'to_host_async',
           'prefetch', 'engine', 'reset_engine', 'async_enabled',
           'strict_mode', 'TransferEngine', 'TransferFuture',
           'HostFill']

_ALIGN = 128

#: completion threads an engine starts with its first deferred fill.
#: The runtime takes one transfer at a time (two concurrent
#: ``np.asarray`` of ready products: 1.8 times one, tools/d2h_probe.py)
#: and one thread keeps up with the served cell, at work for 93 % of
#: its window there: the cell reads the same with one, two and four
#: to a hundredth, and 6 % less with none (PERF.md section 6, PR 27).
#: A fill that finds the thread busy is not held up by it: whoever
#: needs it first completes it (:class:`HostFill`).
_D2H_WORKERS = 1

#: a product on its way into a host ring span crosses in pieces of at
#: most this many bytes (cut on the device along its first axis longer
#: than one), once it is larger than twice this.  The runtime lands
#: every transfer in a fresh numpy buffer, and glibc serves a request
#: over 32 MiB with an mmap of its own that it unmaps at free(),
#: whatever ``mallopt`` says once a process has threads: a 268 MB
#: product then first-touches 65536 pages, 0.8 CPU-seconds and most of
#: its ``np.asarray`` on the v5e host.  Pieces of 16 MiB come from the
#: heap and are found again there by the next product's
#: (tools/d2h_probe.py; PERF.md section 6, PR 27).
_D2H_PIECE_BYTES = 16 << 20

#: pieces of a LARGE product (``memory.LARGE_SPAN_BYTES``) are cut from
#: it this many to a program and land this many at a time, and never a
#: second product beside it.  A smaller product is cut in one program,
#: all of it on its way at once (PR 27's 268 MB products).
_D2H_GROUP = 8

#: groups of a large product whose readback is started ahead of the
#: group being taken: 128 MiB of landing buffers on the host each, and
#: as much of the runtime's own on the device.  Of a LARGE complex64
#: ARRAY they are also the groups that are CUT ahead: each of its cut
#: programs splits the whole array into planes first (13 ms and 1.07 GB
#: of temporaries for 2.1 GB), so its cuts are issued a group at a
#: time, and queue on the one device stream behind whatever the block
#: that makes the products has dispatched meanwhile.  The served xcorr
#: cell, while its products were such arrays (PERF.md section 6,
#: PR 29), read 1806 Msamples/s with one group ahead, 2035-2112 with
#: two (peak HBM 11.95 GB of 13, peak RSS 26.8 GB as at the parent
#: commit), 5 % more with three or four (12.2 and 12.5 GB; 27.3 and
#: 27.6 GB), no more with five: a look-ahead buys off, with memory, a
#: wait that is a matter of order.  What a cut queued behind there was
#: a whole integration's gulp programs, dispatched within milliseconds
#: of one another the moment a landing freed the correlator's span
#: (PR 31's stamps), so the thread waited a third of its time for cuts
#: that cost the device a millisecond each.  A LARGE product of real
#: words (float planes, or any real dtype) is therefore cut up at
#: once, ahead of that moment (:class:`_PieceFuture`,
#: :func:`_complete_fills`; PR 32), and for it this constant is the
#: readback's look-ahead alone.  Two of eight is what fits both
#: budgets either way.
_D2H_AHEAD = 2

_combine_fn = None


def _combine(re, im):
    global _combine_fn
    if _combine_fn is None:
        import jax
        _combine_fn = jax.jit(lambda r, i: r + 1j * i)
    return _combine_fn(re, im)


def _piece_plan(arr):
    """``(axis, step)``: ``arr`` (a jax array, or the planes of a
    complex one) crosses in pieces of ``step`` indices along ``axis``,
    its first axis longer than one, so that every piece is one
    stretch of the product's bytes: as few pieces as fit
    ``_D2H_PIECE_BYTES`` each (one index at least), and those of one
    length where the axis divides, so that one program cuts them all
    (a 56.6 MB product of 1024 rows in pieces of the 296 that fit
    left a fourth piece of 136 rows to a program of its own, a gulp:
    four of 256 do not; PERF.md section 6, PR 35).  None where it
    crosses whole: no larger than two pieces, or on more than one
    device."""
    nbytes = int(arr.nbytes)
    if nbytes <= 2 * _D2H_PIECE_BYTES or \
            len(arr.sharding.device_set) != 1:
        return None
    axis = next(i for i, n in enumerate(arr.shape) if n > 1)
    length = arr.shape[axis]
    most = max(_D2H_PIECE_BYTES * length // nbytes, 1)
    return axis, -(-length // -(-length // most))


#: the runtime hands a device array to the host in the device's own
#: order of axes, which for an array whose last axis is shorter than a
#: lane is not the host's: a (1, 8, 256, 2, 256, 2) complex64 piece
#: arrives with strides (.., 8192, 4096, 8, 2048), and copying it into
#: a span is a gather at 2.5 GB/s where a row-major piece copies at
#: 10 (my chip runs, PERF.md section 6, PR 28).  Such pieces are cut as
#: rows, ``(step, everything else)``: the same bytes in the same
#: order, relaid on the device.  An axis of one before the last says
#: nothing about order either: of a (1024, 64, 1, 864) u8 product the
#: compiler lays pieces of 256 rows out with the FIRST axis along the
#: lanes (``{0,3,2,1}``; pieces of 296 rows it happened to leave in the
#: host's order), and the completion thread then spent 105 ms a product
#: taking them apart where rows cost it 10 (PR 35).
_LANE = 128


def _as_rows(shape):
    """Whether pieces of a product of ``shape`` are cut as rows."""
    return shape[-1] < _LANE or (len(shape) > 2 and shape[-2] == 1)
_cut_fn = None


def _cut(arr, start, axis, step, count, rows):
    """``count`` pieces of ``step`` indices along ``axis`` from index
    ``start`` on, as ``(step, the rest)`` with ``rows``: one program
    on the device, whose start is an argument, so that one compilation
    serves every group of every product of a shape.  A piece of a
    complex product leaves as real rows (:func:`_pairs`): of a
    complex64 array its real and imaginary parts, of planes
    (``devrep.ComplexPlanes``) their two slices, and then the program
    has no complex type in it."""
    global _cut_fn
    if _cut_fn is None:
        import jax
        from jax import lax

        def cut(planes, start, axis, step, count, rows):
            pieces = ([lax.dynamic_slice_in_dim(x, start + j * step,
                                                step, axis)
                       for x in planes] for j in range(count))
            if len(planes) == 2:
                return tuple(_pairs(re, im) for re, im in pieces)
            if planes[0].dtype.kind == 'c':
                return tuple(_pairs(p.real, p.imag) for p, in pieces)
            return tuple(p.reshape(step, -1) if rows else p
                         for p, in pieces)
        _cut_fn = jax.jit(cut, static_argnums=(2, 3, 4, 5))
    from .planes import device_arrays
    return _cut_fn(device_arrays(arr), start, axis, step, count, rows)


def _pairs(re, im):
    """The two planes of a complex piece, under jit, as real rows with
    re and im interleaved element by element: byte for byte what the
    host calls complex, so that the runtime moves plain 32-bit words
    and the host takes the piece with a view
    (``_PieceFuture._take_group``).  Moves alone, never arithmetic,
    so NaN payloads, -0.0 and infinities
    arrive as they left: float32 planes are stacked as the words they
    are, because libtpu's compiler joins two float arrays with a
    ``maximum`` over ``-inf`` pads, which no unsigned word minds
    (float64 planes, which only the CPU backend has, as they are:
    there a stack is a copy).  A row is the fewest trailing axes that
    fill a lane, each plane folded to ``(rows, those axes)`` before
    the two are stacked: XLA then interleaves inside the tiles the
    planes already have (tools/d2h_probe.py on the chip, the sixteen
    cuts of a (1, 1024, 256, 2, 256, 2) product: 0.215 s from
    complex64, 0.208 of them the split into planes that a program
    with a complex argument starts with; as ``(step, everything
    else)`` 0.517, as the complex64 rows of before 0.507; stacking
    the unfolded planes copies the whole product first)."""
    import jax.numpy as jnp
    from jax import lax
    tail = 1
    while tail < re.ndim and int(np.prod(re.shape[-tail:])) < _LANE:
        tail += 1
    tail = re.shape[-tail:]
    planes = [p.reshape((-1,) + tail) for p in (re, im)]
    if planes[0].dtype == jnp.float32:
        planes = [lax.bitcast_convert_type(p, jnp.uint32) for p in planes]
    return jnp.stack(planes, -1).reshape(-1, 2 * int(np.prod(tail)))


def _counters():
    from .telemetry import counters
    return counters


_obs_mods = None


def _obs():
    """(histograms, spans) — transfer-time/size observability, cached
    after first import (docs/observability.md)."""
    global _obs_mods
    if _obs_mods is None:
        from .telemetry import histograms, spans
        _obs_mods = (histograms, spans)
    return _obs_mods


def _timed(name, cat, hist=None, **args):
    """``spans.timed``: one span and one histogram observation from
    the same two stamps (telemetry/spans.py)."""
    return _obs()[1].timed(name, cat, hist, **args)


def _first(host):
    """The identity conversion of a one-array readback."""
    return host[0]


def _cross(arrays, nbytes, convert=_first):
    """Host copies of device ``arrays`` whose readback has been
    started, converted: D2H completion as the host sees it, in its
    parts (the wait for the device and the DMA's remainder, the copy
    out of the runtime's buffer, the conversion where there is one)."""
    from jax import block_until_ready
    with _timed('d2h', 'xfer', 'xfer.d2h_wait_s', bytes=nbytes):
        faults.fire('xfer.result')
        live = [a for a in arrays if not a.is_deleted()]
        if not all(a.is_ready() for a in live):
            _counters().inc('xfer.sync_waits')
        with _timed('d2h.ready', 'wait', 'xfer.d2h_ready_s'):
            block_until_ready(live)
        with _timed('d2h.asarray', 'xfer', 'xfer.d2h_asarray_s'):
            host = [np.asarray(a) for a in arrays]
        if convert in (_first, list):      # no conversion: no span
            return convert(host)
        with _timed('d2h.convert', 'xfer', 'xfer.d2h_convert_s'):
            return convert(host)


def _peer_wait():
    """The span of a thread that needs a transfer which a peer (a
    completion thread, or a caller that claimed it first) is
    completing: a span, so that the wait is nobody's unexplained
    stall."""
    return _timed('d2h.peer_wait', 'wait', 'xfer.d2h_peer_wait_s')


@contextmanager
def _held(lock):
    """``with lock``, for a future's lock.  Where a peer thread holds
    it, it is completing this very transfer, and this thread sits out
    the rest of it (:func:`_peer_wait`)."""
    if not lock.acquire(False):
        with _peer_wait():
            lock.acquire()
    try:
        yield
    finally:
        lock.release()


def _env_int(name, default):
    try:
        return int(os.environ.get(name, '') or default)
    except ValueError:
        return default


def async_enabled():
    """Whether the non-blocking D2H queue / deferred fills are active.
    BF_SYNC_STRICT=1 implies synchronous transfers: strict mode's whole
    point is that completion is forced at known program points."""
    if os.environ.get('BF_XFER_ASYNC', '1') == '0':
        return False
    return not strict_mode()


def strict_mode():
    return os.environ.get('BF_SYNC_STRICT', '0') == '1'


def _alloc_aligned(shape, dtype):
    """Fresh numpy buffer aligned to _ALIGN bytes — aligned hosts make
    device_put zero-copy on the CPU backend and DMA-friendly on TPU
    (an unaligned source forces the runtime into a second copy).
    Zero-size shapes yield a valid empty array."""
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape)) * dtype.itemsize
    raw = np.empty(nbytes + _ALIGN, np.uint8)
    off = (-raw.ctypes.data) % _ALIGN
    return raw[off:off + nbytes].view(dtype).reshape(shape)


def _zero_copy_backend():
    """True when device_put of an aligned host array may alias host
    memory (the CPU backend) — staging slots then live as long as the
    arrays created from them."""
    try:
        import jax
        return jax.default_backend() == 'cpu'
    except Exception:
        return True      # be conservative before backend init


class _Slot(object):
    """One staging buffer, either free (in the pool) or bound to the
    device array created from it."""

    __slots__ = ('buf', 'key', 'recycled', 'ref', '__weakref__')

    def __init__(self, buf, key):
        self.buf = buf
        self.key = key
        self.recycled = False
        self.ref = None          # weakref to the bound device array


class _StagingPool(object):
    """Bounded per-(shape, dtype) ring of reusable aligned host staging
    buffers — COPYING backends only.

    A slot returns to the free list only when its transfer is observed
    complete (``is_ready`` scan at acquire time): the device then holds
    its own copy and the host bytes are dead.  On zero-copy backends
    (CPU) the pool must never be used — the device array aliases the
    slot's memory for its whole lifetime, and even the array's *death*
    does not prove safety (a dispatched-but-unfinished computation
    still reads the buffer; measured: overwriting a staging buffer
    after dropping the array corrupts an in-flight matmul).  The engine
    routes zero-copy backends to fresh aligned buffers instead.

    A slot whose array died before its transfer was ever observed
    complete is dropped rather than recycled (the runtime's keepalive
    on the source numpy object protects the memory until the DMA
    drains; the pool just allocates a replacement).

    When a key's slots are all busy the caller falls back to a fresh
    aligned copy — correctness never depends on pool capacity.
    """

    def __init__(self, depth):
        self.depth = max(int(depth), 1)
        # RLock: _on_array_death is a weakref finalizer and may run
        # from a GC pass triggered INSIDE a locked region on the same
        # thread — a plain Lock would self-deadlock there
        self._lock = threading.RLock()
        self._free = {}      # key -> [np buffer]
        self._busy = []      # [_Slot]
        self._nalloc = {}    # key -> slots currently accounted

    def _drop_slot(self, slot):
        # under self._lock: retire a slot whose transfer completion was
        # never observed — its buffer must NEVER be reused (the DMA may
        # still read it; the runtime's keepalive on the numpy object
        # protects the memory until it drains)
        if not slot.recycled:
            slot.recycled = True
            self._nalloc[slot.key] = \
                max(self._nalloc.get(slot.key, 1) - 1, 0)
            try:
                self._busy.remove(slot)
            except ValueError:
                pass

    def _on_array_death(self, slot):
        with self._lock:
            self._drop_slot(slot)

    def release_unused(self, slot):
        """Return a slot no device array was ever bound to (the
        transfer failed before/at device_put) straight to the free
        list."""
        with self._lock:
            if not slot.recycled:
                slot.recycled = True
                self._free.setdefault(slot.key, []).append(slot.buf)

    def reclaim(self):
        """Return to the free list every slot whose transfer is
        observed done (the device then owns a copy).  A DELETED array
        (donated downstream) proves nothing about the DMA — donation
        deletes at dispatch time — and polling is_ready() on it
        crashes the runtime: such slots are dropped, not reused (same
        policy as _on_array_death).

        Called by :meth:`acquire`, and by the engine's per-gulp
        ``drain()`` on every block thread: at a gulp every 60 ms the
        next ``acquire`` alone comes too late for some arrays (their
        consumer has let go of them by then), and each slot lost so is
        replaced by a first touch of fresh pages, 0.5-0.7 s for a
        268 MB slot on the v5e host against 16 ms for the copy into a
        reused one: ``h2d.stage`` read 103 ms a gulp in the mean, and
        the served cell 1180 Msamples/s against 2960 with this
        (PERF.md section 6, PR 27)."""
        with self._lock:
            for slot in list(self._busy):
                if slot.recycled:
                    continue
                arr = slot.ref() if slot.ref is not None else None
                if arr is None:
                    continue           # finalizer owns it
                if arr.is_deleted():
                    self._drop_slot(slot)
                elif arr.is_ready():
                    slot.recycled = True
                    self._free.setdefault(slot.key, []).append(slot.buf)
                    try:
                        self._busy.remove(slot)
                    except ValueError:
                        pass

    def acquire(self, shape, dtype):
        """A staging buffer for (shape, dtype), or None when the pool
        for that key is exhausted."""
        key = (tuple(shape), str(np.dtype(dtype)))
        with self._lock:
            self.reclaim()
            free = self._free.get(key)
            if free:
                return _Slot(free.pop(), key)
            if self._nalloc.get(key, 0) < self.depth:
                self._nalloc[key] = self._nalloc.get(key, 0) + 1
                return _Slot(_alloc_aligned(shape, dtype), key)
            return None

    def bind(self, slot, device_array):
        """Tie ``slot`` to the array created from it; the slot recycles
        once the transfer is observed complete."""
        slot.ref = weakref.ref(device_array,
                               lambda _ref, s=slot:
                               self._on_array_death(s))
        with self._lock:
            self._busy.append(slot)


#: seconds between two looks of a thread that waits for a lent span.
#: The runtime lets go of host memory on a thread of its own, and
#: jaxlib drops the reference it kept at the next call into it from
#: Python (``collect_garbage`` is one), so there is no event to sleep
#: on: the waiter asks, a thousand times a second at most.
_HOLD_POLL_S = 1e-3

_collect_fn = None


def _collect_runtime_garbage():
    """Have jaxlib drop, now, the Python references its runtime has
    finished with: it parks them until some thread next calls into it
    (one that holds the GIL), and a pipeline that has come to rest
    makes no such call."""
    global _collect_fn
    if _collect_fn is None:
        try:
            from jax._src.lib import xla_client
            _collect_fn = xla_client._xla.collect_garbage
        except Exception:
            _collect_fn = lambda: None         # noqa: E731
    _collect_fn()


class _Lease(object):
    """Host memory lent to the runtime for one transfer.  numpy sees
    it through the array interface, so the array made from it
    (``np.asarray(lease)``, what ``device_put`` is handed) and every
    view or re-typing of that array that anybody makes and keeps has
    this object at the end of its chain of bases.  The runtime keeps
    the array it was handed until it is done with the host bytes (the
    keepalive :class:`_StagingPool` leans on for a slot whose array
    died), so this object's death says that nobody reads the memory
    any more, whoever deleted or donated the DEVICE array meanwhile:
    a ``weakref.finalize`` on it is a handle to the transfer's
    completion that no reader of the device ring can delete.  It
    cannot fire early; a runtime that copied the bytes before it
    returned and kept nothing lets it fire as soon as the caller drops
    the array, which is right too."""

    __slots__ = ('__array_interface__', '__weakref__')

    def __init__(self, view):
        self.__array_interface__ = dict(view.__array_interface__)


class _Hold(object):
    """One read span of a host ring kept open while the runtime reads
    a gulp from its memory: a second open span of the block's own
    reader over the bytes of the span the block was given, which both
    ring cores count (the guarantee stays at the oldest open span, so
    no writer gets at the bytes when the pipeline releases its own).
    ``lend()`` is the array to ship; once the runtime has let go of it
    the hold is ``consumed()`` and whoever looks next releases the span
    (:meth:`TransferEngine._reap`)."""

    __slots__ = ('span', 'view', 'nbytes', '_gone', '_timer')

    def __init__(self, span, view):
        #: its ``sequence`` is the reader it belongs to, whose holds
        #: are released in the order they were made, and before it
        #: moves on or closes
        self.span = span
        self.view = view           # keeps the ring's buffer too
        self.nbytes = int(view.nbytes)
        self._gone = threading.Event()
        self._timer = None

    def lend(self):
        lease = _Lease(self.view)
        weakref.finalize(lease, self._gone.set).atexit = False
        return np.asarray(lease)

    def shipped(self):
        """``device_put`` has returned: the hold's span starts."""
        # an interval, not a thread's time: a later call releases it
        self._timer = _obs()[1].interval(
            'h2d.hold', 'wait', 'xfer.h2d_hold_s', bytes=self.nbytes)
        self._timer.__enter__()

    def consumed(self):
        return self._gone.is_set()

    def wait(self):
        while not self._gone.wait(_HOLD_POLL_S):
            _collect_runtime_garbage()

    def release(self):
        if self._timer is not None:
            self._timer.__exit__(None, None, None)
        self.span.release()


class TransferFuture(object):
    """Handle for one non-blocking D2H readback.

    ``ready()`` is a cheap poll; ``result()`` blocks on the in-flight
    remainder (counting a hard sync only when a wait actually
    happened) and caches the converted numpy value.  Futures complete
    correctly in any order — the queue in :class:`TransferEngine` only
    bounds how many are outstanding.

    A transfer that FAILS (deleted source array, backend error,
    injected fault) completes the future with that error: every
    ``result()`` call re-raises it, ``done`` becomes True so the
    engine's drain retires it instead of retrying forever, and
    deferred ring fills propagate it into ring poisoning (see
    :class:`HostFill`).
    """

    __slots__ = ('_arrays', '_convert', '_done', '_result', '_error',
                 '_lock', '_nbytes')

    def __init__(self, arrays, convert, result=None, done=False):
        self._arrays = list(arrays)
        self._convert = convert
        self._done = done
        self._result = result
        self._error = None
        self._lock = threading.Lock()
        self._nbytes = sum(int(getattr(a, 'nbytes', 0) or 0)
                           for a in self._arrays)

    def ready(self):
        if self._done:
            return True
        try:
            # is_deleted first: polling is_ready on a deleted array
            # crashes the runtime (result() will raise cleanly instead)
            return all(a.is_deleted() or a.is_ready()
                       for a in self._arrays)
        except Exception:
            return True            # invalid: result() will raise

    def result(self):
        with _held(self._lock):
            return self._take()

    def poll(self):
        """Harvest the transfer if it has finished on its own and no
        peer is completing it; never waits, for the device or for a
        peer.  True once done; a recorded failure raises."""
        if not self._done and self.ready() and self._lock.acquire(False):
            try:
                self._take()
            finally:
                self._lock.release()
        if self._done and self._error is not None:
            raise self._error
        return self._done

    def _take(self):
        # under self._lock
        if self._done:
            if self._error is not None:
                raise self._error
            return self._result
        try:
            self._result = self._fetch()
        except Exception as exc:
            self._error = exc
            self._done = True
            self._arrays = []
            _counters().inc('xfer.errors')
            raise
        self._done = True
        self._arrays = []      # drop device refs promptly
        return self._result

    def _fetch(self):
        return _cross(self._arrays, self._nbytes, self._convert)

    @property
    def nbytes(self):
        """Bytes of the device arrays this transfer brings over."""
        return self._nbytes

    @property
    def error(self):
        return self._error

    @property
    def done(self):
        return self._done


class _PieceFuture(TransferFuture):
    """The D2H of one large product in pieces (:func:`_piece_plan`),
    for :class:`HostFill`: :meth:`land` hands each group of host
    pieces, with the place of each in the product, to the caller's
    ``put`` as it arrives, so the product is never whole on the host
    outside its destination.  A complex product's pieces cross as
    real (re, im) pairs (:func:`_cut`) and are seen as complex again
    here, with no pass over them, whether the product came as a
    complex64 array or as its two planes (``devrep.ComplexPlanes``).
    ``result()`` lands it into an array of its own.

    When the groups are cut follows from ``whole``.  Without it the
    first groups (``_D2H_AHEAD`` of them) are cut and on their way
    when the future is made, on the caller's thread, and whoever lands
    the future cuts a further group as it starts on each: at most
    ``_D2H_AHEAD`` groups beside the product, which is what a product
    whose every cut program costs a pass over all of it (a LARGE
    complex64 array) can afford, and all of a smaller product, which
    is one group.  With it nothing is cut until :meth:`cut_up` (or
    the landing, if nobody called it) cuts every group at once, so
    that no program dispatched after that moment runs ahead of any of
    the cuts; only the readback of the pieces is started
    ``_D2H_AHEAD`` groups ahead of the group being taken, so the
    host holds the landing buffers it held.  Either way the product is
    let go with its last cut, and a group's pieces with its landing."""

    __slots__ = ('_axis', '_step', '_group', '_shape', '_dtype',
                 '_row', '_ahead', '_whole', '_hinted')

    def __init__(self, arr, axis, step, group, whole=False):
        super(_PieceFuture, self).__init__([arr], None)
        self._axis, self._step, self._group = axis, step, group
        self._shape, self._dtype = arr.shape, np.dtype(arr.dtype)
        self._row = 0
        #: groups that are cut: each ``[(device piece, its index in
        #: the product)]``, the first ``_hinted`` with their readback
        #: started
        self._ahead = deque()
        self._hinted = 0
        self._whole = whole
        if not whole:
            self._cut_ahead()

    def cut_up(self):
        """Cut every group now, where the product is cut up ``whole``
        (nothing otherwise, nor a second time).  For whoever owns the
        future and knows that its landing is next: the engine before
        it queues the fill, a completion thread that has claimed it
        (:meth:`TransferEngine.host_fill`, :func:`_complete_fills`)."""
        if self._whole and not self._done:
            self._cut_ahead()

    def _cut_ahead(self):
        """Cut groups until ``_D2H_AHEAD`` are cut (``whole``: every
        group) or the product is cut up, and see that the first
        ``_D2H_AHEAD`` of them are on their way."""
        while self._arrays and (self._whole or
                                len(self._ahead) < _D2H_AHEAD):
            arr, rows = self._arrays[0], self._shape[self._axis]
            full = (rows - self._row) // self._step
            step, count = (self._step, min(full, self._group)) if full \
                else (rows - self._row, 1)
            pieces = _cut(arr, self._row, self._axis, step, count,
                          _as_rows(self._shape))
            lead = (slice(None),) * self._axis
            self._ahead.append(
                [(p, lead + (slice(self._row + j * step,
                                   self._row + (j + 1) * step),))
                 for j, p in enumerate(pieces)])
            self._row += step * count
            if self._row >= rows:
                self._arrays = []
            self._start_ahead()
        self._start_ahead()

    def _start_ahead(self):
        """Start the readback of the cut groups that are among the
        next ``_D2H_AHEAD`` to be taken."""
        while self._hinted < min(len(self._ahead), _D2H_AHEAD):
            TransferEngine._start_readback(
                p for p, _where in self._ahead[self._hinted])
            self._hinted += 1

    def ready(self):
        if self._done:
            return True
        if self._arrays and not self._ahead:
            return False           # not cut yet
        try:
            return all(p.is_ready() for group in self._ahead
                       for p, _where in group)
        except Exception:
            return True            # invalid: landing it will raise

    def land(self, put):
        """Complete the transfer through ``put(group, last)``, called
        once a group with ``[(host piece, index)]``."""
        with _held(self._lock):
            if not self._done:
                try:
                    self._take_into(put)
                except Exception as exc:
                    self._error = exc
                    _counters().inc('xfer.errors')
                finally:
                    self._done = True
            if self._error is not None:
                raise self._error

    def _take_into(self, put):
        # under self._lock
        try:
            self._cut_ahead()
            while self._ahead:
                self._take_group(put)
        finally:
            self._arrays = []
            self._ahead.clear()

    def _take_group(self, put):
        """The oldest group to ``put``, what is to be ahead of it cut
        and on its way first; its pieces leave the device as soon as
        the host has them.  Real pairs are complex again by a view: no
        pass over them."""
        pieces, places = map(list, zip(*self._ahead.popleft()))
        self._hinted -= 1
        self._cut_ahead()
        shape = list(self._shape)
        shape[self._axis] = -1          # rows are pieces again
        host = _cross(pieces, sum(int(p.nbytes) for p in pieces), list)
        del pieces
        put([(h.view(self._dtype).reshape(shape), where)
             for h, where in zip(host, places)], not self._ahead)

    def _fetch(self):
        out = np.empty(self._shape, self._dtype)

        def put(group, last):
            for host, where in group:
                out[where] = host
        self._take_into(put)
        return out


class HostFill(object):
    """Deferred fill of a committed host ring span from an in-flight
    D2H transfer.

    The writing block registers the fill on the ring instead of
    blocking, and readers acquiring any overlapping span call
    :meth:`wait` first (ring.py).  A fill is CLAIMED once, by whoever
    completes it: one of the engine's completion threads (the usual
    case: they take fills oldest first, so by the time a reader needs
    the bytes they have landed), or the first :meth:`wait` to find it
    unclaimed, which then does the work itself and so never waits for
    a thread that is busy elsewhere.  Every other ``wait`` waits for
    the fill to land.  Fills of different spans complete side by side;
    the ring gates each reader on the fills overlapping its span, so
    delivery stays once and in order.

    A FAILED transfer is not swallowed: the claimant records the
    error, POISONS the target ring (waking every reader/writer with
    ``RingPoisonedError`` instead of handing them a span of garbage
    bytes) and, where it is a ``wait``, re-raises; later waits, and
    the engine's next ``drain()``, raise the same error."""

    __slots__ = ('future', 'dtype', 'out', 'nbytes', 'begin', 'nbyte',
                 '_storage', '_ring', 'done', 'error', '_lock',
                 '_claimed', '_landed')

    def __init__(self, future, dtype, out_view):
        self.future = future
        self.dtype = dtype
        self.out = out_view
        #: what the fill holds in flight: the product's bytes
        self.nbytes = int(getattr(out_view, 'nbytes', 0))
        self.begin = None
        self.nbyte = 0
        self._storage = None
        self._ring = None
        self.done = False
        self.error = None
        #: guards the claim, and ``done`` against :meth:`attach` (who
        #: of the two runs second mirrors the ghost region)
        self._lock = threading.Lock()
        self._claimed = False
        self._landed = threading.Event()

    def attach(self, ring, begin, nbyte):
        """Bind the fill to its committed byte range so ghost-region
        maintenance can run after the data lands (called by
        WriteSpan.close).  The fill may already have completed — a
        completion thread may be done with it before the span closes,
        and synchronous mode always is — in which case the deferred
        ghost mirror runs here instead (and a recorded failure poisons
        the ring here); no reader can have acquired the span yet
        (commit happens after attach)."""
        with self._lock:
            self._storage = ring._storage
            self._ring = ring
            self.begin = begin
            self.nbyte = nbyte
            if self.done and self.error is None and nbyte:
                self._storage.fill_ghost_mirror(begin, nbyte)
            failed = self.error
        if failed is not None:
            self._poison(ring, failed)

    def _claim(self):
        """True for exactly one caller: the one that completes (or
        cancels) the fill."""
        with self._lock:
            if self._claimed:
                return False
            self._claimed = True
            return True

    def cancel(self):
        """Abandon the fill without writing (its span committed no
        bytes — the reservation rolled back and the target region may
        be re-reserved; a late write would corrupt the next span).
        Where a peer has claimed it, its write is under way or over:
        returns once it is over."""
        if self._claim():
            self.done = True
            self._landed.set()
        elif not self.done:
            with _peer_wait():
                self._landed.wait()

    def wait(self):
        """Return once the span's bytes have landed: complete the fill
        here if nobody has claimed it, else wait for its claimant."""
        if not self.done:
            if self._claim():
                self.complete('xfer.fills_by_caller')
            else:
                with _peer_wait():
                    self._landed.wait()
        if self.error is not None:
            raise self.error

    def cut_up(self):
        """For whoever owns the fill (its claimant, or the engine
        before anybody can see it) and knows its landing is next: a
        product that is cut up whole is cut up now
        (:meth:`_PieceFuture.cut_up`), ahead of every program that is
        dispatched once this returns.  A cut that fails is met again,
        and reported, by the landing."""
        if isinstance(self.future, _PieceFuture):
            try:
                self.future.cut_up()
            except Exception:
                pass

    def complete(self, who, then=None):
        """The claimant's work: block on the transfer, convert into
        the span's host view (piece by piece where it crosses so),
        then redo the ghost mirror for wrapped spans (the commit-time
        mirror ran before the bytes landed).  A failure is recorded,
        not raised (an interrupt is both).  ``who`` is the counter
        that says which side did it; ``then()`` runs once the bytes
        have landed and before anybody who waits for them is told."""
        try:
            if isinstance(self.future, _PieceFuture):
                self.future.land(self._put)
            else:
                self._put([(self.future.result(), Ellipsis)], True)
            if then is not None:
                then()
        except BaseException as exc:
            with self._lock:
                self.error = exc
                self.done = True
                ring = self._ring
            _counters().inc('xfer.fill_errors')
            self._poison(ring, exc)
            if not isinstance(exc, Exception):
                raise
        finally:
            _counters().inc(who)
            self._landed.set()

    def _put(self, group, last):
        """The second pass over the product: ``[(host array, its
        index in the product)]`` into the ring span, and after the
        ``last`` of them the ghost mirror."""
        from .devrep import from_device_rep
        with _timed('d2h.fill', 'xfer', 'xfer.d2h_fill_s',
                    bytes=sum(int(h.nbytes) for h, _where in group)):
            for host, where in group:
                from_device_rep(host, self.dtype, self.out[where])
            if last:
                with self._lock:
                    if self._storage is not None and self.nbyte:
                        self._storage.fill_ghost_mirror(self.begin,
                                                        self.nbyte)
                    self.done = True

    @staticmethod
    def _poison(ring, exc):
        if ring is not None:
            try:
                ring.poison(exc)
            except Exception:
                pass


def _complete_fills(work, fills, stop):
    """Body of a completion thread: claim the oldest unclaimed fill of
    the engine's queue and complete it, until told to stop.  Once a
    fill's bytes have landed, and before it says so, it claims the
    fill it will take next and cuts that one up
    (:meth:`HostFill.cut_up`): a landing is what the next product's
    producer waits for (the byte bound of :meth:`TransferEngine
    .host_fill`, the ring span behind it), and the programs it
    dispatches the moment it is let go queue behind the cuts, not the
    cuts behind them.  It holds the engine's condition, queue and stop
    flag, not the engine, so an engine nobody refers to any more can
    be collected (and stops its threads from ``__del__``)."""
    ahead = []             # the fill claimed before the last one's news

    def claim():
        # under ``work``
        return None if stop.is_set() else \
            next((f for f in fills if f._claim()), None)

    def claim_next():
        with work:
            fill = claim()
        if fill is not None:
            ahead.append(fill)
            with _timed('d2h.cut', 'xfer', bytes=fill.nbytes):
                fill.cut_up()

    while True:
        if ahead:
            fill = ahead.pop()
        else:
            with work:
                fill = claim()
                if fill is None:
                    # the thread's rest between products: a span, so
                    # that it is nobody's unexplained stretch
                    with _timed('d2h.idle', 'wait'):
                        while fill is None:
                            if stop.is_set():
                                return
                            work.wait()
                            fill = claim()
        fill.complete('xfer.fills_by_worker', claim_next)
        del fill           # hold no product while idle


class TransferEngine(object):
    """Pipelined host↔device transfer engine (module docstring)."""

    def __init__(self, depth=None, staging=None, stage_min=None,
                 zero_copy=None):
        self.depth = depth if depth is not None \
            else _env_int('BF_XFER_DEPTH', 4)
        self.stage_min = stage_min if stage_min is not None \
            else _env_int('BF_XFER_STAGE_MIN', 1 << 14)
        self._pool = _StagingPool(staging if staging is not None
                                  else _env_int('BF_XFER_STAGING', 4))
        #: override for tests; None = detect from the backend
        self._zero_copy = zero_copy
        self._pending = deque()     # TransferFutures (to_host_async)
        self._fills = deque()       # HostFills (host_fill)
        self._lock = threading.Lock()
        #: wakes the completion threads: a fill was queued, or stop
        self._work = threading.Condition(self._lock)
        self._stop = threading.Event()
        self._workers = []
        #: spans of host rings lent to transfers in flight
        #: (:class:`_Hold`), in the order they were shipped
        self._holds = []
        self._hold_lock = threading.Lock()
        _obs()[1].watch_jax()

    def _is_zero_copy(self):
        if self._zero_copy is not None:
            return self._zero_copy
        return _zero_copy_backend()

    # -- H2D ---------------------------------------------------------------
    def _put(self, arr, device):
        """``device_put`` until it returns: the runtime's own host-side
        work (its layout conversion, on the caller's thread or its
        own) ends somewhere after."""
        import jax
        import jax.numpy as jnp
        with _timed('h2d.put', 'xfer', 'xfer.h2d_put_s'):
            if device is not None:
                return jax.device_put(arr, device)
            return jnp.asarray(arr)

    def _stage_ship(self, shape, dtype, nbytes, fill, device):
        """The ONE copy of the staging-slot ship protocol (shared by
        :meth:`_stage_real` and :meth:`to_device_batch` so the slot
        rules can never drift between them): acquire a reusable slot
        on copying backends (size/strict gated) or a fresh aligned
        buffer, let ``fill(buf)`` write the host bytes, async
        device_put, bind the slot to the resulting array for later
        recycling.  A fill/put failure returns an unused slot to the
        pool (a swallowed slot would shrink the key's capacity for the
        life of the process)."""
        c = _counters()
        slot = None
        if not self._is_zero_copy() and nbytes >= self.stage_min \
                and not strict_mode():
            slot = self._pool.acquire(shape, dtype)
        if slot is not None:
            try:
                with _timed('h2d.stage', 'xfer', 'xfer.h2d_stage_s',
                            staged=1):
                    fill(slot.buf)
                out = self._put(slot.buf, device)
            except Exception:
                # no device array ever saw the buffer: return the slot
                self._pool.release_unused(slot)
                raise
            self._pool.bind(slot, out)
            c.inc('xfer.h2d_staged')
        else:
            # the pool was exhausted (acquire never waits), or the
            # backend aliases host memory: a fresh buffer
            with _timed('h2d.stage', 'xfer', 'xfer.h2d_stage_s',
                        staged=0):
                staged = _alloc_aligned(shape, dtype)
                fill(staged)
            out = self._put(staged, device)
            c.inc('xfer.h2d_unstaged')
        c.inc('xfer.h2d_issued')
        c.inc('xfer.h2d_bytes', int(nbytes))
        return out

    # -- H2D from a ring span (docs/transfer.md, "From the ring span") ----
    def _lendable(self, arr, span):
        """Whether ``arr`` can cross from where it lies: it is the
        memory of ``span``, an open read span of a host ring, all of
        it in one stretch that does not run into the ring's ghost
        region; the span's reader is guaranteed (only its open spans
        keep a writer off the bytes) and its ring holds two such spans
        at least (one may stay lent while the block waits for the
        next); the backend copies (one that aliases host memory would
        read the ring for the array's whole life), strict mode is off
        and the gulp is worth it (``stage_min``).  Everything else is
        staged."""
        if span is None or self._is_zero_copy() or strict_mode() \
                or int(arr.nbytes) < self.stage_min:
            return False
        ring = span.ring
        if ring.space == 'tpu' or ring.nringlet != 1 \
                or not getattr(span.sequence, 'guarantee', False):
            return False
        begin, nbyte, size = span._begin, span._nbyte, ring.total_span
        return (0 < nbyte == int(arr.nbytes) and arr.flags.c_contiguous
                and begin % size + nbyte <= size and size >= 2 * nbyte
                and arr.ctypes.data == span.data.as_numpy().ctypes.data)

    def _ship_lent(self, arr, span, device):
        """Ship ``arr`` from the ring span it lies in, with no host
        copy: open the span a second time, hand ``device_put`` its
        memory, and leave the second span open until the runtime has
        let go of it (:class:`_Hold`).  A failed ``device_put``
        releases it at once: no transfer reads it."""
        hold = _Hold(span.sequence.acquire(span.frame_offset,
                                           span.nframe), arr)
        try:
            faults.fire('xfer.h2d')
            out = self._put(hold.lend(), device)
        except BaseException:
            hold.release()
            raise
        hold.shipped()
        c = _counters()
        with self._hold_lock:
            self._holds.append(hold)
            c.set_gauge('xfer.h2d_spans_held', len(self._holds))
        c.inc('xfer.h2d_direct')
        c.inc('xfer.h2d_direct_bytes', hold.nbytes)
        c.inc('xfer.h2d_issued')
        c.inc('xfer.h2d_bytes', hold.nbytes)
        return out

    def _reap(self):
        """Release every lent span the runtime has let go of, each
        reader's in the order they were shipped; returns the holds
        that are left."""
        with self._hold_lock:
            if not self._holds:
                return []
            _collect_runtime_garbage()
            stuck, left = set(), []
            for hold in self._holds:
                reader = id(hold.span.sequence)
                if reader not in stuck and hold.consumed():
                    hold.release()
                else:
                    stuck.add(reader)
                    left.append(hold)
            self._holds = left
            _counters().set_gauge('xfer.h2d_spans_held', len(left))
            return left

    def release_held(self, sequence=None, keep=0):
        """Wait until reader ``sequence`` (None: every reader) has at
        most ``keep`` spans lent to transfers, releasing them as they
        come back.  The engine calls it with one after every ship from
        a span: the transfer just issued stays in flight behind the
        caller's next ``acquire``, which a ring two spans deep can
        serve, and the one before it is waited for here, with the link
        busy meanwhile.  The block calls it with none before its
        reader moves to another sequence or closes (``CopyBlock``): a
        span outlives neither."""
        while True:
            mine = [h for h in self._reap()
                    if sequence is None or h.span.sequence is sequence]
            if len(mine) <= keep:
                return
            with _timed('h2d.hold_wait', 'wait', 'xfer.h2d_hold_wait_s'):
                mine[0].wait()

    # -- sharded H2D (mesh-resident pipelines; docs/parallel.md) ----------
    def _shard_plan(self, shape, sharding):
        """Per-device (device, index) placement plan for a sharded H2D,
        or None when the sharding cannot be staged per shard (not fully
        addressable, or a degenerate single-device layout)."""
        try:
            devices = sharding.device_set
            if len(devices) <= 1 or not sharding.is_fully_addressable:
                return None
            items = list(
                sharding.addressable_devices_indices_map(
                    tuple(shape)).items())
            if len(items) != len(devices):
                return None
            return items
        except Exception:
            return None

    def _stage_ship_sharded(self, arr, sharding, plan):
        """Per-shard variant of the ship protocol: each device's shard
        slice is staged into its OWN aligned buffer (same slot pool /
        zero-copy rules as :meth:`_stage_ship`, applied per shard),
        device_put to its device, and the shard arrays are assembled
        into one global array with
        ``jax.make_array_from_single_device_arrays`` — the host never
        materializes a monolithic device-side copy and each chip
        receives exactly its bytes.  The PR 1 staging semantics hold
        per shard: the caller may recycle ``arr`` on return, and the
        assembled array is framework-owned (donation-eligible once
        committed with ``owned=True``).

        Slot lifetime: every acquired slot is bound to the ASSEMBLED
        global array, not its per-shard wrapper — the wrappers die the
        moment this method returns (only the buffers live on inside
        the global array), so binding to them would fire the
        death-finalizer and permanently drop every slot, regressing
        copying backends to per-gulp fresh allocation.  The global
        array's ``is_ready()`` proves all shard DMAs drained, which is
        exactly the recycle condition each slot needs."""
        import jax
        c = _counters()
        use_pool = not self._is_zero_copy() and not strict_mode()
        shard_arrays = []
        slots = []
        shard_bytes = 0
        try:
            for dev, idx in plan:
                piece = arr[idx]
                nbytes = int(piece.nbytes)
                shard_bytes = nbytes
                slot = self._pool.acquire(piece.shape, piece.dtype) \
                    if use_pool and nbytes >= self.stage_min else None
                if slot is not None:
                    # track BEFORE the copy/put: a failure must settle
                    # every acquired-but-unbound slot, not just this
                    # one; the flag records whether this slot's DMA
                    # was ever issued
                    slots.append([slot, False])
                    with _timed('h2d.stage', 'xfer',
                                'xfer.h2d_stage_s', staged=1):
                        np.copyto(slot.buf, piece, casting='no')
                    shard_arrays.append(self._put(slot.buf, dev))
                    slots[-1][1] = True
                    c.inc('xfer.h2d_staged')
                else:
                    with _timed('h2d.stage', 'xfer',
                                'xfer.h2d_stage_s', staged=0):
                        staged = _alloc_aligned(piece.shape,
                                                piece.dtype)
                        np.copyto(staged, piece, casting='no')
                    shard_arrays.append(self._put(staged, dev))
                    c.inc('xfer.h2d_unstaged')
                c.inc('xfer.h2d_issued')
                c.inc('xfer.h2d_bytes', nbytes)
            out = jax.make_array_from_single_device_arrays(
                tuple(arr.shape), sharding, shard_arrays)
        except Exception:
            # settle every acquired slot: one whose device_put never
            # ran is clean and returns to the free list; one whose DMA
            # may already be in flight must never be reused — drop it
            # (the pool allocates a replacement; accounting stays
            # balanced either way)
            for slot, shipped in slots:
                if shipped:
                    self._pool._on_array_death(slot)
                else:
                    self._pool.release_unused(slot)
            raise
        for slot, _shipped in slots:
            self._pool.bind(slot, out)
        c.inc('xfer.h2d_sharded')
        c.inc('xfer.h2d_shard_bytes', shard_bytes)
        return out

    def _stage_real(self, arr, device):
        """Ship a real-valued numpy array the caller keeps for itself:
        exactly ONE host copy into an engine-owned aligned buffer,
        then an async device_put — the caller may mutate/recycle
        ``arr`` the moment this returns, on every backend.  (A gulp
        that lies in a ring span the engine may hold open is not
        copied at all: :meth:`_ship_lent`.)

        Zero-copy backends (CPU): the buffer is FRESH per transfer —
        aligned so device_put stays zero-copy (the old defensive
        ``np.array(copy=True)`` was unaligned, forcing the runtime into
        a second copy), fresh because the device array aliases the
        buffer for life (pool reuse is provably unsafe there, see
        _StagingPool).

        Copying backends (TPU): the buffer is a reusable staging slot
        (recycled once the DMA is observed complete); when the slot
        ring is exhausted, the array is tiny, or strict mode disables
        reuse, a fresh aligned buffer is used instead — never the
        caller's own memory, whose recycling would race the async
        DMA."""
        faults.fire('xfer.h2d')
        return self._stage_ship(
            arr.shape, arr.dtype, int(arr.nbytes),
            lambda buf: np.copyto(buf, arr, casting='no'), device)

    def to_device(self, arr, device=None, sharding=None, span=None):
        """numpy -> jax.Array; complex is shipped as two float planes
        and recombined on device.  Safe against the caller mutating or
        recycling ``arr`` after the call returns (the staging-pool
        contract).

        ``span`` is the open :class:`~bifrost_tpu.ring.ReadSpan` that
        ``arr`` is the memory of, from a caller that reads a host ring
        (``CopyBlock``): where :meth:`_lendable` allows, the gulp
        crosses from the span's memory with no host copy, and the
        engine keeps the span open (a second open span of its reader)
        until the transfer has consumed it.  The contract towards the
        caller is the same: it releases its own span when it likes.

        ``sharding`` (a jax Sharding spanning several devices) routes
        the transfer through the sharded H2D path: host bytes are
        staged into per-shard aligned buffers, device_put per device,
        and assembled with ``make_array_from_single_device_arrays`` —
        the gulp lands mesh-resident with no monolithic copy and no
        post-hoc reshard.  BF_MESH_H2D=0 (or an unstageable sharding)
        falls back to one whole-array device_put onto the sharding."""
        if sharding is not None:
            return self._to_device_sharded(np.asarray(arr), sharding)
        if device is None:
            # honor the block thread's BlockScope(device=N) binding
            from .device import get_bound_device
            device = get_bound_device()
        arr = np.asarray(arr)
        # host-side transfer time (staging copy + async device_put
        # issue) and transfer-size distribution
        _obs()[0].observe('xfer.h2d_nbytes', int(arr.nbytes))
        lent = False
        with _timed('h2d', 'xfer', 'xfer.h2d_s', bytes=int(arr.nbytes)):
            if np.iscomplexobj(arr):
                re, im = self._planes(arr)
                c = _counters()
                c.inc('xfer.h2d_issued')
                c.inc('xfer.h2d_bytes', int(arr.nbytes))
                out = _combine(self._put(re, device),
                               self._put(im, device))
            elif self._lendable(arr, span):
                out, lent = self._ship_lent(arr, span, device), True
            else:
                out = self._stage_real(arr, device)
                if span is not None:
                    # a ring's gulp that was staged counts, at 0, so
                    # that a reader of the share finds it
                    _counters().inc('xfer.h2d_direct_bytes', 0)
        if lent:
            # outside the call's span: waiting for the transfer before
            # this one is no work of this one's
            self.release_held(span.sequence, keep=1)
        return out

    @staticmethod
    def _planes(arr):
        """(re, im) float planes of a complex array: the extraction
        copies into fresh buffers the caller never sees -- already
        alias-safe without staging."""
        ft = np.float64 if arr.dtype == np.complex128 else np.float32
        with _timed('h2d.stage', 'xfer', 'xfer.h2d_stage_s', staged=0):
            return (np.ascontiguousarray(arr.real, dtype=ft),
                    np.ascontiguousarray(arr.imag, dtype=ft))

    def _to_device_sharded(self, arr, sharding):
        """Sharded H2D (see :meth:`to_device`).  Complex crosses as
        (re, im) planes each shipped sharded; the on-device recombine
        keeps the planes' layout, so the result is mesh-resident too.
        One transfer observation regardless of plane count (matching
        the single-device complex path), so the sharded and
        single-device arms of config 11 read comparable histograms."""
        try:
            ndev = len(sharding.device_set)
        except Exception:
            ndev = 1
        _obs()[0].observe('xfer.h2d_nbytes', int(arr.nbytes))
        # the shard count distinguishes mesh placements from
        # single-device ships in the trace (mesh observability)
        with _timed('h2d', 'xfer', 'xfer.h2d_s', bytes=int(arr.nbytes),
                    shards=ndev):
            faults.fire('xfer.h2d')
            if not np.iscomplexobj(arr):
                return self._ship_sharded_real(arr, sharding)
            re, im = self._planes(arr)
            return _combine(self._ship_sharded_real(re, sharding),
                            self._ship_sharded_real(im, sharding))

    def _ship_sharded_real(self, arr, sharding):
        """One real-valued sharded placement: per-shard staged shards
        when the sharding is stageable (and BF_MESH_H2D allows), else
        one whole-array staged copy device_put onto the sharding — the
        staging-slot ship protocol applies on BOTH routes, so neither
        regresses to per-gulp fresh allocation."""
        from .parallel.scope import mesh_h2d_enabled
        plan = self._shard_plan(arr.shape, sharding) \
            if mesh_h2d_enabled() else None
        if plan is not None:
            return self._stage_ship_sharded(arr, sharding, plan)
        # whole-array fallback: jax.device_put accepts a Sharding as
        # the placement target (the runtime scatters)
        _counters().inc('xfer.h2d_sharded_fallback')
        return self._stage_ship(
            arr.shape, arr.dtype, int(arr.nbytes),
            lambda buf: np.copyto(buf, arr, casting='no'), sharding)

    def prefetch(self, arr, device=None):
        """Issue the H2D transfer for ``arr`` now and return the device
        array immediately (device_put is asynchronous): stage gulp
        N+1..N+k while gulp N computes.  Identical to :meth:`to_device`
        — the name documents intent at call sites."""
        return self.to_device(arr, device)

    def to_device_batch(self, arrs, device=None):
        """Stage K same-shape host gulps with ONE engine call: one
        aligned staging buffer covering the whole batch, one host copy
        pass, one async ``device_put`` — K dispatch round-trips become
        one (the H2D arm of macro-gulp execution; docs/perf.md).
        Returns the stacked ``(K, *shape)`` device array; slice along
        the leading axis for per-gulp views (slices keep the parent
        alive, so per-gulp lifetime works as usual).

        Note a CopyBlock moving a macro ring span already gets this
        for free — the span is one contiguous view and
        :meth:`to_device` ships it in one call; this entry point
        serves producers holding K separate host gulps."""
        arrs = [np.asarray(a) for a in arrs]
        if not arrs:
            raise ValueError("to_device_batch needs at least one array")
        shape, dtype = arrs[0].shape, arrs[0].dtype
        for a in arrs[1:]:
            if a.shape != shape or a.dtype != dtype:
                raise ValueError(
                    "to_device_batch requires uniform shape/dtype "
                    "(got %s/%s vs %s/%s)"
                    % (a.shape, a.dtype, shape, dtype))
        if device is None:
            from .device import get_bound_device
            device = get_bound_device()
        if np.iscomplexobj(arrs[0]):
            # complex crosses the boundary as (re, im) planes; the
            # stack is the one extra copy the plane extraction would
            # make anyway, and the transfer itself stays one call
            _counters().inc('xfer.h2d_batched', len(arrs))
            return self.to_device(np.stack(arrs), device)
        faults.fire('xfer.h2d')
        k = len(arrs)
        bshape = (k,) + tuple(shape)
        nbytes = int(np.dtype(dtype).itemsize * np.prod(bshape))

        def fill(buf):
            for i, a in enumerate(arrs):
                np.copyto(buf[i], a, casting='no')

        _obs()[0].observe('xfer.h2d_nbytes', nbytes)
        with _timed('h2d', 'xfer', 'xfer.h2d_s', bytes=nbytes):
            out = self._stage_ship(bshape, dtype, nbytes, fill, device)
        _counters().inc('xfer.h2d_batched', k)
        return out

    # -- D2H ---------------------------------------------------------------
    @staticmethod
    def _start_readback(arrays):
        for a in arrays:
            try:
                a.copy_to_host_async()
            except Exception:
                pass               # optional fast-path hint only

    def _future_for(self, arr, out_view=None, convert=_first):
        """TransferFuture for a jax array, every dtype as it is.  With
        ``out_view``, the host view it is bound for, a large array
        crosses in pieces (:class:`_PieceFuture`) where the view has
        the array's shape down to the axis that is cut.  The planes
        of a complex array (``devrep.ComplexPlanes``) are cut as they
        are, and joined first where they cross whole.  ``convert`` is
        what a readback that crosses whole does with its host array."""
        from .words import ComplexWords
        if isinstance(arr, ComplexWords):
            # bound for no ring span (:meth:`host_fill` hands those
            # over as the words): the words cross, and are the int8
            # (re, im) pairs on the host by a view
            shape = arr.shape
            return self._future_for(
                arr.words,
                convert=lambda host: host[0].view(np.int8).reshape(shape))
        faults.fire('xfer.d2h')
        import jax
        from .planes import ComplexPlanes
        if hasattr(arr, 'as_numpy'):       # bifrost_tpu.ndarray
            return TransferFuture([], lambda _h: None,
                                  result=arr.as_numpy(), done=True)
        if isinstance(arr, np.ndarray):
            return TransferFuture([], lambda _h: None,
                                  result=arr, done=True)
        nbytes = int(getattr(arr, 'nbytes', 0) or 0)
        c = _counters()
        c.inc('xfer.d2h_issued')
        c.inc('xfer.d2h_bytes', nbytes)
        _obs()[0].observe('xfer.d2h_nbytes', nbytes)
        planes = isinstance(arr, ComplexPlanes)
        plan = _piece_plan(arr) if out_view is not None and \
            (planes or isinstance(arr, jax.Array)) else None
        if plan is not None:
            axis, step = plan
            if tuple(getattr(out_view, 'shape', ())[:axis + 1]) == \
                    tuple(arr.shape[:axis + 1]):
                from .memory import LARGE_SPAN_BYTES
                c.inc('xfer.d2h_piece_bytes', nbytes)
                # of those, the ones that cross as real (re, im) pairs
                # (0 too, so that a reader finds the counter)
                c.inc('xfer.d2h_pair_bytes',
                      nbytes if arr.dtype.kind == 'c' else 0)
                # and of those, the ones cut from planes
                c.inc('xfer.d2h_plane_bytes', nbytes if planes else 0)
                # a LARGE product is cut in groups, and all of them
                # when its landing starts where a cut costs its pieces
                # alone: not from a complex64 array, which every cut
                # program splits whole before it slices
                large = nbytes >= LARGE_SPAN_BYTES
                whole = large and (planes or arr.dtype.kind != 'c')
                c.inc('xfer.d2h_cutup_bytes', nbytes if whole else 0)
                return _PieceFuture(
                    arr, axis, step,
                    _D2H_GROUP if large else -(-arr.shape[axis] // step),
                    whole)
        if planes:
            arr = arr.joined()
        self._start_readback((arr,))
        return TransferFuture([arr], convert)

    def to_host(self, arr):
        """array -> numpy; blocks until the value is ready (the D2H
        sync point, reference: cudaStreamSynchronize per gulp) — but
        starts the readback asynchronously first, so the wait covers
        only the in-flight remainder."""
        return self._future_for(arr).result()

    def to_host_async(self, arr):
        """Start a non-blocking D2H readback of ``arr``; returns a
        :class:`TransferFuture`.  The engine bounds in-flight futures
        at ``depth`` — registering one past the bound retires the
        oldest first (one amortized wait per ``depth`` transfers).
        With the engine disabled (BF_XFER_ASYNC=0 / strict mode) the
        future is completed synchronously before returning."""
        fut = self._future_for(arr)
        if not async_enabled():
            fut.result()
            return fut
        _counters().inc('xfer.d2h_async')
        with self._lock:
            self._pending.append(fut)
            over = self._past_bound(self._pending)
        for old in over:
            self._retire(old, old.ready, old.result)
        return fut

    def _past_bound(self, queue):
        """Under the lock: the oldest transfers of ``queue`` that its
        newest pushes past the bound, popped.  The bound counts
        transfers (``depth``) and bytes: the unfinished ones hold at
        most ``memory.INFLIGHT_BYTES``, the newest apart, so that
        products of 268 MB are in flight four deep and products of
        2.1 GB one at a time (docs/transfer.md, "Depth by bytes")."""
        from . import memory
        over = []
        while len(queue) > self.depth or (len(queue) > 1 and sum(
                t.nbytes for t in queue if not t.done)
                > memory.INFLIGHT_BYTES):
            over.append(queue.popleft())
        return over

    @staticmethod
    def _retire(old, ready, wait):
        """Complete the transfer the depth bound pushed out (a future
        or a fill).  It is a real hard wait only where the transfer
        has not finished on its own (``ready`` tells finished-but-
        unharvested ones apart: ``done`` flips only once the result is
        taken); the closed-loop auto-tuner reads that rate as part of
        its sync-depth trigger (docs/autotune.md)."""
        if old.done:
            return wait()          # re-raises a recorded failure
        if not ready():
            _counters().inc('xfer.depth_waits')
        with _timed('d2h.depth_wait', 'wait'):
            wait()

    def host_fill(self, dev_arr, dtype, out_view):
        """A :class:`HostFill` materializing ``dev_arr`` (device
        representation of bifrost dtype ``dtype``) into ``out_view``,
        queued for the engine's completion threads.  Bounded like
        to_host_async; completed on the caller, before returning, when
        the engine is disabled.  The words of a ci8 gulp
        (``devrep.ComplexWords``) are the span's own bytes: they cross
        as the int16 array they are, in pieces where it is large, into
        the span seen as int16 words; a span that cannot be seen so (a
        ring with ringlets) gets the pairs."""
        from .words import ComplexWords, host_view
        if isinstance(dev_arr, ComplexWords):
            view = host_view(out_view)
            if view is not None:
                dev_arr, dtype, out_view = dev_arr.words, 'i16', view
            else:
                dev_arr = dev_arr.pairs()
        fill = HostFill(self._future_for(dev_arr, out_view), dtype,
                        out_view)
        if not async_enabled():
            fill.wait()
            return fill
        _counters().inc('xfer.d2h_async')
        with self._work:
            if all(f.done for f in self._fills):
                # no landing to wait for: its own starts now, and its
                # cuts go to the device before anything the caller
                # lets go by returning (else the completion thread
                # cuts it up, before it announces the one ahead of it)
                fill.cut_up()
            self._fills.append(fill)
            over = self._past_bound(self._fills)
            self._start_workers()
            self._work.notify()
        for old in over:
            self._retire(old, old.future.ready, old.wait)
        return fill

    def _start_workers(self):
        # under self._lock
        if self._workers or self._stop.is_set():
            return
        for i in range(_D2H_WORKERS):
            t = threading.Thread(
                target=_complete_fills, name='xfer-d2h-%d' % i,
                args=(self._work, self._fills, self._stop), daemon=True)
            t.start()
            self._workers.append(t)

    def close(self, timeout=None):
        """Stop the completion threads and wait for them to end (each
        for at most ``timeout`` seconds); a fill one of them has
        claimed is completed first.  Fills still queued stay with
        whoever waits for them (the caller-claims rule).  The engine
        starts no thread again."""
        self._stop.set()
        with self._work:
            self._work.notify_all()
            workers, self._workers = self._workers, []
        for t in workers:
            if t is not threading.current_thread():
                t.join(timeout)

    def __del__(self):
        # tell the threads only: a collection may run on one of them
        try:
            self._stop.set()
            with self._work:
                self._work.notify_all()
        except Exception:
            pass

    def drain(self, block=False):
        """Retire async transfers that are done; returns the number
        retired.  The pipeline's dispatch-ahead loop calls this once a
        gulp on every block thread, so it takes nobody's work and
        waits for nobody: a future that has finished on its own is
        harvested if no peer is at it, a fill is left to its claimant.
        With ``block=True`` (shutdown) every outstanding transfer is
        completed or waited for, every ring span lent to an H2D
        transfer (:class:`_Hold`) among them.  It also lets the
        staging pool take back the slots of H2D transfers that have
        landed (:meth:`_StagingPool.reclaim`).

        A failed transfer raises out of the draining thread (the
        failure is recorded on the future/fill, so the queues still
        retire it) — the block whose gulp loop drained it then applies
        its failure policy instead of the error vanishing."""
        n = 0
        error = None
        self._pool.reclaim()
        if block:
            self.release_held()
        with self._lock:
            pending = list(self._pending)
            fills = list(self._fills)
        for fut in pending:
            try:
                if block:
                    fut.result()
                else:
                    fut.poll()
            except Exception as exc:
                error = error if error is not None else exc
        for fill in fills:
            if block or fill.done:
                try:
                    fill.wait()
                except Exception as exc:
                    error = error if error is not None else exc
        with self._lock:
            for q in (self._pending, self._fills):
                while q and q[0].done:
                    q.popleft()
                    n += 1
        if error is not None:
            raise error
        return n

    @property
    def outstanding(self):
        with self._lock:
            return (sum(1 for f in self._pending if not f.done) +
                    sum(1 for f in self._fills if not f.done))


_engine = None
_engine_lock = threading.Lock()


def engine():
    """The process-wide TransferEngine (created on first use)."""
    global _engine
    if _engine is None:
        with _engine_lock:
            if _engine is None:
                _engine = TransferEngine()
    return _engine


def reset_engine():
    """Drop the process engine (tests: re-read env tunables): its
    outstanding transfers are completed, its completion threads
    joined."""
    global _engine
    with _engine_lock:
        if _engine is not None:
            try:
                _engine.drain(block=True)
            except Exception:
                pass       # failed transfers die with the engine
            _engine.close()
        _engine = None


@atexit.register
def _close_at_exit():
    """Join the completion threads before the interpreter goes: a
    daemon thread inside the runtime while it is torn down is a crash
    at exit.  Outstanding transfers die with the process, as ever,
    and so does a thread that a dead device holds past the timeout."""
    if _engine is not None:
        _engine.close(timeout=5.0)


def to_device(arr, device=None, sharding=None, span=None):
    """numpy -> jax.Array via the transfer engine (module docstring).
    Alias-safe: the caller may mutate/recycle ``arr`` immediately.
    ``sharding`` routes through the sharded H2D path (per-shard staged
    placement over a mesh — docs/parallel.md); ``span`` is the open
    read span of a host ring that ``arr`` is the memory of, which the
    engine may ship from and hold open instead of copying
    (:meth:`TransferEngine.to_device`)."""
    return engine().to_device(arr, device, sharding=sharding, span=span)


def to_host(arr):
    """array -> numpy; blocks until the value is ready.  Accepts jax
    arrays, numpy arrays, and bifrost_tpu ndarrays."""
    if hasattr(arr, 'as_numpy'):       # bifrost_tpu.ndarray
        return arr.as_numpy()
    if isinstance(arr, np.ndarray):
        return arr
    return engine().to_host(arr)


def to_host_async(arr):
    """Non-blocking D2H; returns a :class:`TransferFuture`."""
    return engine().to_host_async(arr)


def prefetch(arr, device=None):
    """Issue an H2D transfer ahead of need; returns the device array."""
    return engine().prefetch(arr, device)


def to_device_batch(arrs, device=None):
    """Stage K same-shape host gulps with ONE engine call; returns the
    stacked (K, *shape) device array (macro-gulp H2D)."""
    return engine().to_device_batch(arrs, device)
