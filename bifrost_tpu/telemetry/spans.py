"""Gulp-span tracing: one always-on recorder of per-thread event
buffers, the Chrome trace-event export, and the flight recorder.

The reference answers "where does a gulp spend its time?" with NVTX
ranges rendered by nsight (reference: src/trace.hpp ScopedTracer); this
module is the portable equivalent and the package's ONE timing
mechanism.  Every instrumented operation -- block compute and the
dispatch-ahead wait (``pipeline.py``), ring reserve/acquire blocked
time (``ring.py``, both cores), the parts of H2D and D2H
(``xfer.py``), compilations and full garbage collections (this
module) -- records one COMPLETE span (name, category, start, duration,
args, CPU time) into a bounded per-thread buffer.  Recording has no
switch: the site already takes two ``perf_counter`` stamps for its
always-on histogram, and two readings of the thread's own CPU clock
(``time.thread_time()``, 0.4 us each) and appending one tuple to the
thread's ``deque`` (no lock: the buffer is ``threading.local``) are the
whole added cost, 2-3 us.

One call feeds both sinks::

    with spans.timed('h2d.put', 'xfer', hist='xfer.h2d_put_s'):
        ...

takes the two stamps once and records the histogram and the span with
the same duration, so a site's span durations sum to its histogram's
sum.  An event is ``(name, cat, ts_us, dur_us, args, cpu_us)``:
``cpu_us`` is what the thread spent ON a CPU inside the span, so a
``wait`` span says whether its thread slept (near 0) or spun (near
``dur_us``); None where the interval is not one thread's time (an
event stamped after the fact through :func:`record`, a
:class:`interval`).  Parentage is by nesting on the thread (a child
lies inside its parent's interval; self time = duration - children);
across threads a gulp is followed by ``seq``/``gulp`` (compute spans)
and ``frame`` (ring spans: first frame of the span in its sequence).

Consumers of the buffers:

- **Chrome trace export** -- ``BF_TRACE_FILE=trace.json`` makes
  ``Pipeline.run`` write a Chrome trace-event JSON on exit (one track
  per block thread), loadable in Perfetto / ``chrome://tracing``.
- **flight recorder** -- on a stall the watchdog dumps the most recent
  spans of every thread as a text timeline next to the thread stacks
  (supervision.py); the fleet publisher ships the same
  (``flight_events``).  They always have history.
- **the benchmark** -- ``perfbench/progspans.py`` reads :func:`events`
  after a run and lays them over the device trace.

The clock is ``time.perf_counter()`` less :func:`origin_s`, in
microseconds; the export writes the origin under
``otherData.bf_clock.origin_s``, so one ``perf_counter`` stamp tied to
a ``jax.profiler`` trace (an anchor program, as ``perfbench`` does)
lays this trace over that one.

``BF_SPAN_BUFFER`` bounds events kept per thread (default 16384; the
buffer is a ring -- oldest events fall off and are counted, which is
the flight-recorder semantic).  It applies to threads that record
their first span after it is read (``Pipeline.run`` re-reads it before
it starts the block threads).
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
from collections import deque

from . import counters, histograms

__all__ = ['trace_file', 'timed', 'interval', 'record', 'now_us',
           'origin_s', 'configure', 'reconfigure', 'watch_jax',
           'export', 'export_if_configured', 'flight_record',
           'flight_events', 'prune_dead_buffers', 'reset', 'events',
           'dropped_spans', 'dropped_by_thread',
           'note_peer_clock', 'clock_info']

#: a 30 s window of the busiest benchmark cell needs about 8 k on its
#: one thread; 65536 always-on would hold hundreds of MB over many
#: threads
DEFAULT_BUFFER = 16384
#: dead-thread buffers kept for export before the oldest are pruned
MAX_BUFFERS = 512

_perf_counter = time.perf_counter
_thread_time = time.thread_time
_t0 = _perf_counter()

_config_lock = threading.Lock()
_configured = False
_trace_file = None
_buf_cap = DEFAULT_BUFFER

_tls = threading.local()
#: RLock: the gc callback below records from inside whatever the
#: collecting thread was doing, which may be a locked region here
_buffers_lock = threading.RLock()
_buffers = []            # [(threading.Thread, deque, drops:[int])]
#: drop counts inherited from PRUNED (dead-thread) buffers, so
#: ``dropped_spans`` stays monotonic across Pipeline.run's
#: prune_dead_buffers calls — it is exported as a cumulative counter
#: (Prometheus rate() breaks on a counter that decreases)
_dropped_retired = 0

#: cross-host clock correlation (docs/observability.md): bridge
#: endpoints register the sessions they participated in — and, on the
#: sender side, the ping-estimated span-clock offset to the peer —
#: so the Chrome trace export can embed them for tools/trace_merge.py
_clock_lock = threading.Lock()
_sessions = {}           # session -> {'role', 'offset_us', 'rtt_us'}


def origin_s():
    """The ``time.perf_counter()`` value at span time 0: a span's
    ``ts_us * 1e-6 + origin_s()`` is its start on the clock every
    ``perf_counter`` stamp in the process shares."""
    return _t0


def now_us():
    """Microseconds since :func:`origin_s` on the span clock."""
    return (_perf_counter() - _t0) * 1e6


def configure():
    """Read ``BF_TRACE_FILE`` / ``BF_SPAN_BUFFER`` (first call only;
    use :func:`reconfigure` to force a re-read)."""
    global _configured, _trace_file, _buf_cap
    with _config_lock:
        if _configured:
            return
        _trace_file = os.environ.get('BF_TRACE_FILE') or None
        try:
            _buf_cap = max(int(os.environ.get('BF_SPAN_BUFFER', '')
                               or DEFAULT_BUFFER), 16)
        except ValueError:
            _buf_cap = DEFAULT_BUFFER
        _configured = True


def reconfigure():
    """Re-read the environment (tests / long-lived operator processes
    pointing the export elsewhere without a restart — also reached
    via ``bifrost_tpu.trace.reset()``)."""
    global _configured
    with _config_lock:
        _configured = False
    configure()


def trace_file():
    if not _configured:
        configure()
    return _trace_file


def _buf():
    b = getattr(_tls, 'buf', None)
    if b is not None:
        return b, _tls.drops
    if not _configured:
        configure()
    b = deque(maxlen=_buf_cap)
    # a one-int list, shared by reference with the registry so the
    # owning thread bumps it lock-free and readers see it
    drops = [0]
    _tls.buf = b
    _tls.drops = drops
    t = threading.current_thread()
    with _buffers_lock:
        if len(_buffers) >= MAX_BUFFERS:
            # prune every dead thread's buffer so a long-lived
            # process running many pipelines cannot accumulate
            # unbounded RETIRED buffers.  Live threads are never
            # dropped — a process keeping > MAX_BUFFERS threads
            # simultaneously alive holds that many buffers by
            # necessity (the cap is for retirees only).
            _retire_locked(lambda e: e[0].is_alive())
        _buffers.append((t, b, drops))
    return b, drops


def _retire_locked(keep):
    """Drop registry entries failing ``keep``, folding their drop
    counts into the retired total (callers hold _buffers_lock)."""
    global _dropped_retired
    _dropped_retired += sum(e[2][0] for e in _buffers if not keep(e))
    _buffers[:] = [e for e in _buffers if keep(e)]


def _append(ev):
    """Append one event to this thread's buffer, counting the event it
    evicts when the ring is saturated: overflow used to be silent, and
    a flight record / trace that quietly lost its oldest spans reads
    as 'nothing happened before this' (the ``trace.dropped_spans``
    counter in ``telemetry.snapshot()`` says otherwise)."""
    b, drops = _buf()
    if len(b) >= b.maxlen:
        drops[0] += 1
    b.append(ev)


def dropped_spans():
    """Total spans evicted by per-thread buffer overflow across the
    process, INCLUDING threads whose buffers were since pruned — the
    count is cumulative/monotonic, as a counter export requires
    (saturation indicator: raise ``BF_SPAN_BUFFER`` or export more
    often when this grows)."""
    with _buffers_lock:
        return _dropped_retired + sum(e[2][0] for e in _buffers)


def dropped_by_thread():
    """``{thread name: spans its live buffer has evicted}``, for a
    reader that must know whether a thread's oldest history is whole
    (``perfbench/progspans.py``)."""
    with _buffers_lock:
        out = {}
        for t, _b, d in _buffers:
            out[t.name] = out.get(t.name, 0) + d[0]
        return out


def _drain(buf):
    """Copy a (possibly foreign) thread's deque.  The owning thread
    appends without a lock; deque appends are atomic but iterating
    during one raises RuntimeError — retry, then fall back to an
    item-by-item best-effort copy."""
    for _ in range(4):
        try:
            return list(buf)
        except RuntimeError:
            continue
    out = []
    try:
        for ev in buf.copy():
            out.append(ev)
    except RuntimeError:
        pass
    return out


def record(name, cat, ts_us, dur_us, args=None, cpu_us=None):
    """Record one complete span from span-clock stamps
    (:func:`now_us`): for events whose interval is known only after
    the fact (a listener's callback, a synthesized member span), which
    as a rule have no CPU time to give (``cpu_us`` None).
    Instrumentation sites use :class:`timed`."""
    _append((name, cat, ts_us, dur_us, args, cpu_us))


def prune_dead_buffers():
    """Drop retired (dead-thread) buffers — ``Pipeline.run`` calls
    this at startup so a fresh run's trace export / flight record is
    not contaminated by earlier runs' threads.  Live threads
    (including concurrently running pipelines) are untouched."""
    with _buffers_lock:
        _retire_locked(lambda e: e[0].is_alive())


# ---------------------------------------------------------------------------
# cross-host clock correlation (tools/trace_merge.py)
# ---------------------------------------------------------------------------

def note_peer_clock(session, role, offset_us=None, rtt_us=None,
                    wall_offset_ns=None):
    """Register a bridge session this process participated in.

    The SENDER side passes the ping-estimated clock offset from its
    handshake (``offset_us`` = receiver span-clock minus sender
    span-clock at the same instant, ``rtt_us`` the round trip the
    estimate rode on); the RECEIVER side registers with role only.
    The trace export embeds these under ``otherData.bf_clock`` so
    ``tools/trace_merge.py`` can shift per-host timelines onto one
    clock.  A re-registration keeps the LOWEST-rtt offset (the best
    estimate wins across stripes/reconnects)."""
    with _clock_lock:
        cur = _sessions.get(session)
        if cur is not None and offset_us is not None \
                and cur.get('rtt_us') is not None \
                and rtt_us is not None \
                and rtt_us >= cur['rtt_us']:
            return
        entry = {'role': role}
        if offset_us is not None:
            entry['offset_us'] = round(float(offset_us), 3)
        if rtt_us is not None:
            entry['rtt_us'] = round(float(rtt_us), 3)
        if wall_offset_ns is not None:
            # wall-clock (time.time) offset to the peer from the same
            # ping — what the fabric end-to-end SLO corrects by, and
            # what tools/trace_merge.py surfaces as host clock skew
            entry['wall_offset_ns'] = int(wall_offset_ns)
        if cur is not None and 'offset_us' not in entry \
                and 'offset_us' in cur:
            return                   # never downgrade an estimate
        _sessions[session] = entry


def clock_info():
    """This process's clock-correlation metadata for the trace export:
    host/pid, the span clock's origin (:func:`origin_s`) plus every
    bridge session seen (and, sender side, the offset estimate)."""
    import socket as socket_mod
    with _clock_lock:
        sessions = {k: dict(v) for k, v in _sessions.items()}
    return {'host': socket_mod.gethostname(), 'pid': os.getpid(),
            'origin_s': _t0, 'sessions': sessions}


class timed(object):
    """The site API: a with-block that takes two ``perf_counter``
    stamps and feeds both sinks with the one duration::

        with spans.timed('fft.on_data', 'compute', seq=0, gulp=3):
            ...
        with spans.timed('d2h.fill', 'xfer', hist='xfer.d2h_fill_s',
                         bytes=n):
            ...

    ``hist`` is a histogram's name or the :class:`Histogram` itself (a
    hot path caches it); None records the span alone.  Both are
    recorded on ANY exit -- exceptions from fault injection or real
    failures still produce a complete, correctly nested event, which
    is what makes the flight recorder trustworthy around crashes.
    ``args`` may be set or added to inside the block (a ring span
    learns its ``frame`` only once the call returns).  Beside each
    stamp it reads the thread's CPU clock: the event's ``cpu_us``."""

    __slots__ = ('name', 'cat', 'hist', 'args', 't0', 'c0')

    def __init__(self, name, cat='', hist=None, **args):
        self.name = name
        self.cat = cat
        self.hist = hist
        self.args = args or None

    def __enter__(self):
        self.c0 = _thread_time()
        self.t0 = _perf_counter()
        return self

    def __exit__(self, *exc):
        t0 = self.t0
        dt = _perf_counter() - t0
        c0 = self.c0
        cpu_us = None if c0 is None else (_thread_time() - c0) * 1e6
        hist = self.hist
        if hist is not None:
            if hist.__class__ is str:
                hist = histograms.get_or_create(hist, unit='s')
            hist.record(dt)
        _append((self.name, self.cat, (t0 - _t0) * 1e6, dt * 1e6,
                 self.args, cpu_us))
        return False


class interval(timed):
    """A :class:`timed` whose two ends may lie on different threads,
    or with the thread's other work between them (``h2d.hold``: from
    one transfer's ``device_put`` to the release a later call makes):
    an interval, not a thread's time, so its ``cpu_us`` is None."""

    __slots__ = ()

    def __enter__(self):
        self.c0 = None
        self.t0 = _perf_counter()
        return self


def events():
    """Snapshot of all recorded events as
    ``[(thread_name, (name, cat, ts_us, dur_us, args, cpu_us)), ...]``."""
    with _buffers_lock:
        bufs = [(t.name, b) for t, b, _d in _buffers]
    out = []
    for tname, buf in bufs:
        out.extend((tname, ev) for ev in _drain(buf))
    return out


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def export(path=None):
    """Write every buffered span as Chrome trace-event JSON (one track
    per thread; load in Perfetto or chrome://tracing).  Returns the
    path written, or None when no path is configured.

    Serialization is hand-rolled per event (one %-format through a
    cached template instead of a dict build + json.dump walk): the
    export runs inside ``Pipeline.run``'s teardown, so its cost is
    part of the observability overhead the e2e gate bounds — measured
    ~3x faster than the generic encoder at trace sizes the config-12
    bench writes.  Only ``args`` (arbitrary user payload) goes through
    ``json.dumps``."""
    if path is None:
        path = trace_file()
    if not path:
        return None
    with _buffers_lock:
        bufs = [(t.ident or 0, t.name, b) for t, b, _d in _buffers]
    pid = os.getpid()
    dumps = json.dumps
    chunks = ['{"traceEvents":[']
    first = True
    for tid, tname, buf in bufs:
        chunks.append('%s{"ph":"M","name":"thread_name","pid":%d,'
                      '"tid":%d,"args":{"name":%s}}'
                      % ('' if first else ',', pid, tid, dumps(tname)))
        first = False
        head = ',{"name":%s,"cat":%s,"ph":"X","pid":' + str(pid) + \
            ',"tid":' + str(tid) + ',"ts":%.3f,"dur":%.3f'
        for name, cat, ts, dur, args, cpu in _drain(buf):
            chunks.append(head % (dumps(name), dumps(cat or 'bf'),
                                  ts, dur))
            if cpu is not None:
                # the trace-event format's own thread-clock duration
                chunks.append(',"tdur":%.3f' % cpu)
            if args:
                chunks.append(',"args":%s}' % dumps(args))
            else:
                chunks.append('}')
    chunks.append('],"displayTimeUnit":"ms","otherData":%s}'
                  # clock-correlation metadata: lets trace_merge.py
                  # join this host's timeline with its bridge peers'
                  % dumps({'bf_clock': clock_info(),
                           'bf_dropped_spans': dropped_spans()}))
    # pid AND thread ident: two pipelines' teardown exports in one
    # process must not truncate each other's tmp file mid-write
    tmp = '%s.tmp%d.%d' % (path, pid, threading.get_ident())
    with open(tmp, 'w') as f:
        f.write(''.join(chunks))
    os.replace(tmp, path)
    return path


def export_if_configured():
    """Export when (and only when) ``BF_TRACE_FILE`` is set; errors are
    reported but never propagate into pipeline teardown (a failed
    export must not mask the pipeline's own failure in
    ``Pipeline.run``'s finally block)."""
    path = trace_file()
    if not path:
        return None
    try:
        return export(path)
    except Exception as exc:
        import sys
        sys.stderr.write('bifrost_tpu: trace export to %r failed: %s\n'
                         % (path, exc))
        return None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def flight_record(per_thread=32):
    """Text timeline of the most recent ``per_thread`` spans of every
    thread, merged and time-sorted — the watchdog appends this to its
    stall dump so a stall comes with the events LEADING UP to it."""
    merged = []
    with _buffers_lock:
        bufs = [(t.name, b) for t, b, _d in _buffers]
    for tname, buf in bufs:
        for ev in _drain(buf)[-per_thread:]:
            merged.append((ev[2], tname, ev))
    if not merged:
        return '=== flight recorder: no spans recorded ==='

    merged.sort(key=lambda e: e[0])
    lines = ['=== flight recorder: last %d span(s)/thread, '
             'oldest first ===' % per_thread]
    dropped = dropped_spans()
    if dropped:
        # saturation disclosure: the timeline below is missing its
        # oldest events — without this line a saturated recorder reads
        # as 'nothing happened before this'
        lines.append('  NOTE: %d span(s) dropped to buffer overflow '
                     '(BF_SPAN_BUFFER saturation) — the oldest '
                     'history below is incomplete' % dropped)
    for ts, tname, (name, cat, _ts, dur, args, _cpu) in merged:
        extra = ' %r' % (args,) if args else ''
        lines.append('  t=%12.3fms +%10.3fms  [%-7s] %-24s %s%s'
                     % (ts / 1e3, dur / 1e3, (cat or 'bf')[:7],
                        tname[-24:], name, extra))
    lines.append('=== end flight recorder ===')
    return '\n'.join(lines)


def flight_events(per_thread=64):
    """Structured twin of :func:`flight_record`: the most recent
    ``per_thread`` spans of every thread as ``[[thread_name, name,
    cat, ts_us, dur_us, args], ...]`` sorted by start time — what the
    fleet publisher attaches to full snapshots and flight-request
    replies (telemetry.fleet), and what incident bundles re-render as
    Chrome traces for ``tools/trace_merge.py``."""
    with _buffers_lock:
        bufs = [(t.name, b) for t, b, _d in _buffers]
    out = []
    for tname, buf in bufs:
        for name, cat, ts, dur, args, _cpu in _drain(buf)[-per_thread:]:
            out.append([tname, name, cat or 'bf',
                        round(ts, 3), round(dur, 3), args])
    out.sort(key=lambda e: e[3])
    return out


# ---------------------------------------------------------------------------
# what the process does behind the program's back: compiling, collecting
# ---------------------------------------------------------------------------

#: the jax.monitoring duration events kept, under their span names.
#: The backend-compile event brackets the whole of compile-or-load, so
#: it alone feeds ``jit.compile_s`` / ``jit.compiles``; a persistent
#: cache hit's retrieval event nests inside it as a span of its own
_JIT_EVENTS = {
    '/jax/core/compile/backend_compile_duration': 'backend_compile',
    '/jax/compilation_cache/cache_retrieval_time_sec': 'cache_retrieval',
}
_jax_watched = False


def _on_jax_duration(event, duration_secs, **kwargs):
    what = _JIT_EVENTS.get(event)
    if what is None:
        return
    # the callback runs on the compiling thread as the work ends
    dur = duration_secs * 1e6
    args = {'event': what}
    if kwargs.get('fun_name'):
        args['fun'] = str(kwargs['fun_name'])
    if what == 'backend_compile':
        histograms.get_or_create('jit.compile_s', unit='s') \
            .record(duration_secs)
        counters.inc('jit.compiles')
    _append(('jit.compile', 'jit', now_us() - dur, dur, args, None))


def watch_jax():
    """Register the one ``jax.monitoring`` listener behind the
    ``jit.compile`` spans (idempotent).  Called where the package
    first needs JAX (``Pipeline.run``, the transfer engine): importing
    the package alone must not import JAX."""
    global _jax_watched
    if _jax_watched:
        return
    # outside the lock: a full collection during the import records a
    # ``host.gc`` span, and a thread's first span takes the lock
    from jax import monitoring
    with _config_lock:
        if _jax_watched:
            return
        monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        _jax_watched = True


_gc_tls = threading.local()


def _on_gc(phase, info):
    """``gc.callbacks`` entry: a full (generation 2) collection holds
    the interpreter lock for tens of ms in a process holding many
    objects: a ``host.gc`` span on the thread that triggered it."""
    if info.get('generation') != 2:
        return
    if phase == 'start':
        _gc_tls.t0 = now_us()
    else:
        t0 = getattr(_gc_tls, 't0', None)
        if t0 is not None:
            _gc_tls.t0 = None
            _append(('host.gc', 'host', t0, now_us() - t0, {'gen': 2},
                     None))


gc.callbacks.append(_on_gc)


def reset():
    """Drop all buffered events, drop counts, clock-correlation
    registrations, and thread registrations (tests)."""
    global _tls, _dropped_retired
    with _buffers_lock:
        del _buffers[:]
        _dropped_retired = 0
    with _clock_lock:
        _sessions.clear()
    _tls = threading.local()
