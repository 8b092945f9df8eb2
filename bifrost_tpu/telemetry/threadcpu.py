"""CPU accounting by OS thread: who in this process spent the cores.

``host_cpu_s_per_gsample`` and an operator's ``top`` see one number a
process.  The spans (``telemetry/spans.py``) say what the program's
own threads did; the runtime's threads beside them (jaxlib's and
libtpu's pools, which do the host's part of every transfer) record
none.  This module reads every thread of the process from the
kernel's scheduler clock:

- :func:`read` is one reading of the whole process at one moment,
  stamped with ``time.perf_counter()`` (the span clock, less
  ``spans.origin_s()``): for every thread under ``/proc/self/task``
  its name, seconds on a CPU and seconds runnable but waiting for one
  (``schedstat``: nanoseconds, updated at every scheduler tick); the
  process's own ``os.times()`` user + system; the machine's ``steal``
  (first line of ``/proc/stat``: what a hypervisor took from all of
  this guest's CPUs); the cgroup's ``nr_throttled`` and throttled
  seconds where its ``cpu.stat`` can be found.  Where ``schedstat`` is
  absent or counts nothing the reading falls back to ``stat``'s
  ``utime + stime`` (ticks of 10 ms), says so (``clock: 'ticks'``) and
  has no run-queue seconds.  On a platform without ``/proc`` it is
  None, and nothing raises.
- Threads are grouped by :func:`family`: a thread with a Python name
  whole (``CopyBlock_0``, ``xfer-d2h-0``), the runtime's numbered
  pools folded (``tf_pjrt_3`` -> ``tf_pjrt``).
- :func:`sample` appends a reading to a bounded series (the
  ``bf-metrics`` thread of a running pipeline does, once a second:
  ``exporter.MetricsPublisher``); :func:`between` answers for a
  stretch of the span clock by linear interpolation between the
  readings that bracket its two ends.

A reading of 200 threads costs 0.2 ms: descriptors are held open and
read with ``os.pread``; the task list is listed anew each time, and
the descriptors of threads that ended are closed.

    reading = threadcpu.read()
    for name, fam in threadcpu.by_family(reading).items():
        print(name, fam['cpu_s'], fam['runq_s'])
    split = threadcpu.between(t_open, t_close)     # perf_counter stamps
"""

from __future__ import annotations

import os
import re
import threading
import time
from collections import deque

__all__ = ['family', 'by_family', 'Sampler', 'read', 'sample', 'series',
           'between', 'newest', 'reset']

#: readings kept: over an hour at one a second
SERIES_LEN = 4096

_TASKS = '/proc/self/task'
_SCHEDSTAT = _TASKS + '/%d/schedstat'
_STAT = _TASKS + '/%d/stat'
_COMM = _TASKS + '/%d/comm'
_MACHINE = '/proc/stat'
_CGROUPS = '/proc/self/cgroup'
_CGROUP_ROOT = '/sys/fs/cgroup'

_NUMBERED = re.compile(r'[-_/:]?\d+$')


def family(name, named=False):
    """The group a thread is accounted under: its own name where it
    has a Python name (``named``: ``Feed_0`` and ``xfer-d2h-0`` stay
    apart), else the kernel's name less a trailing number, so that the
    runtime's numbered pools (``tf_pjrt_3``, ``tf_pjrt_11``) are one."""
    if named:
        return name
    return _NUMBERED.sub('', name) or name


def _fold(fams, name, named, cpu_s, runq_s):
    """Add one thread's seconds to its family's entry of ``fams``."""
    fam = fams.setdefault(family(name, named),
                          {'cpu_s': 0.0, 'runq_s': None, 'threads': 0,
                           'named': named})
    fam['cpu_s'] += cpu_s
    if runq_s is not None:
        fam['runq_s'] = (fam['runq_s'] or 0.0) + runq_s
    fam['threads'] += 1
    fam['named'] = fam['named'] or named


def by_family(reading):
    """``{family: {'cpu_s', 'runq_s', 'threads', 'named'}}`` of one
    reading, cumulative seconds since each thread started; ``runq_s``
    is None on the ``ticks`` clock."""
    out = {}
    for name, named, cpu_s, runq_s in reading['threads'].values():
        _fold(out, name, named, cpu_s, runq_s)
    return out


def _pread(fd, n=512):
    return os.pread(fd, n, 0).decode('ascii', 'replace')


def _cgroup_cpu_stat():
    """Path of this process's cgroup ``cpu.stat`` (version 2, else
    version 1's ``cpu`` controller), or None."""
    try:
        with open(_CGROUPS) as f:
            lines = [line.strip().split(':', 2) for line in f]
    except OSError:
        return None
    cands = []
    for _num, ctrl, path in (p for p in lines if len(p) == 3):
        if ctrl == '':
            cands.insert(0, os.path.join(_CGROUP_ROOT, path.lstrip('/')))
        elif 'cpu' in ctrl.split(','):
            cands.append(os.path.join(_CGROUP_ROOT, ctrl, path.lstrip('/')))
            cands.append(os.path.join(_CGROUP_ROOT, 'cpu',
                                      path.lstrip('/')))
    for d in cands:
        path = os.path.join(d, 'cpu.stat')
        if os.path.exists(path):
            return path
    return None


class Sampler(object):
    """The descriptors one process holds to read itself, and the
    series of its readings.  The module keeps one (:func:`read`,
    :func:`sample`, :func:`between`); tests make their own."""

    def __init__(self, maxlen=SERIES_LEN):
        self._lock = threading.Lock()
        self._series = deque(maxlen=maxlen)
        self._fds = {}             # tid -> descriptor of its clock file
        self._comm = {}            # tid -> [kernel name, read twice]
        self._python = {}          # tid -> the Python name it was seen under
        self._unlisted = set()     # held, and not in the last listing
        self._clock = None         # decided by the first reading
        self._machine = None       # (descriptor of /proc/stat, of cpu.stat)
        self._tick = float(os.sysconf('SC_CLK_TCK')) \
            if hasattr(os, 'sysconf') else 100.0

    # -- one reading -------------------------------------------------------
    def read(self):
        """One reading (the module docstring), or None without
        ``/proc``::

            {'t': perf_counter, 'clock': 'schedstat' | 'ticks',
             'threads': {tid: (name, named, cpu_s, runq_s)},
             'process_cpu_s', 'steal_s', 'nr_throttled', 'throttled_s'}
        """
        with self._lock:
            return self._read()

    def _read(self):
        try:
            listed = set(int(t) for t in os.listdir(_TASKS))
        except (OSError, ValueError):
            return None
        # a listing taken while a thread exits can come back short (the
        # kernel walks the list unlocked): a thread known already is
        # read through its descriptor all the same, once
        for tid in self._unlisted - listed:
            self._forget(tid)
        self._unlisted = set(self._fds) - listed
        tids = listed | self._unlisted
        if self._clock is None:
            self._clock = 'schedstat' if self._counts(tids) else 'ticks'
        ticks = self._clock == 'ticks'
        # a thread keeps the Python name it was seen under: one that
        # has returned from ``run`` is off Python's list before the
        # kernel's, and its seconds are still its own
        self._python.update((t.native_id, t.name)
                            for t in threading.enumerate())
        # the stamp and the process's count are the means of before
        # and after the threads' reading
        t = time.perf_counter()
        times = os.times()
        threads = {}
        for tid in tids:
            got = self._thread(tid, ticks)
            if got is not None:
                comm, cpu_s, runq_s = got
                name = self._python.get(tid)
                threads[tid] = (name or comm, name is not None,
                                cpu_s, runq_s)
        for gone in (set(self._fds) | set(self._python)) - set(threads):
            self._forget(gone)
        after = os.times()
        t = 0.5 * (t + time.perf_counter())
        steal_s, nr_throttled, throttled_s = self._machine_counts()
        return {'t': t, 'clock': self._clock, 'threads': threads,
                'process_cpu_s': 0.5 * (times.user + times.system +
                                        after.user + after.system),
                'steal_s': steal_s, 'nr_throttled': nr_throttled,
                'throttled_s': throttled_s}

    def _counts(self, tids):
        """Whether ``schedstat`` is there and has counted anything."""
        for tid in tids:
            try:
                with open(_SCHEDSTAT % tid) as f:
                    if int(f.read().split()[0]) > 0:
                        return True
            except (OSError, ValueError, IndexError):
                continue
        return False

    def _thread(self, tid, ticks):
        """(kernel name, cpu_s, runq_s) of one thread, or None where
        it ended between the listing and here."""
        try:
            fd = self._fds.get(tid)
            if fd is None:
                fd = self._fds[tid] = os.open(
                    (_STAT if ticks else _SCHEDSTAT) % tid, os.O_RDONLY)
            text = _pread(fd)
            if ticks:
                comm = text[text.index('(') + 1:text.rindex(')')]
                rest = text[text.rindex(')') + 2:].split()
                # after the name: state is field 3, so field n is
                # rest[n - 3]; utime 14, stime 15
                if rest[0] in 'XZ':
                    # dead: where a sandbox's kernel goes on answering
                    # for a thread that has ended (gVisor does, as
                    # state X; Linux says ESRCH), it has ended
                    return None
                return comm, (int(rest[11]) + int(rest[12])) / self._tick, \
                    None
            cpu_ns, runq_ns = text.split()[:2]
            return self._name(tid), int(cpu_ns) * 1e-9, int(runq_ns) * 1e-9
        except (OSError, ValueError, IndexError):
            return None

    def _name(self, tid):
        """The kernel's name of a thread, read when it is first seen
        and once more at the next reading: a pool names its threads
        just after it starts them."""
        known = self._comm.get(tid)
        if known is None or not known[1]:
            try:
                with open(_COMM % tid) as f:
                    comm = f.read().strip()
            except OSError:
                comm = known[0] if known else '?'
            self._comm[tid] = known = [comm, known is not None]
        return known[0]

    def _forget(self, tid):
        fd = self._fds.pop(tid, None)
        if fd is not None:
            try:
                os.close(fd)
            except OSError:
                pass
        self._comm.pop(tid, None)
        self._python.pop(tid, None)
        self._unlisted.discard(tid)

    def _machine_counts(self):
        """(steal_s, nr_throttled, throttled_s), each None where its
        file is not there."""
        if self._machine is None:
            fds = []
            for path in (_MACHINE, _cgroup_cpu_stat()):
                try:
                    fds.append(os.open(path, os.O_RDONLY)
                               if path else None)
                except OSError:
                    fds.append(None)
            self._machine = tuple(fds)
        steal_s = nr_throttled = throttled_s = None
        stat_fd, cg_fd = self._machine
        try:
            if stat_fd is not None:
                # cpu user nice system idle iowait irq softirq steal
                cpu = _pread(stat_fd, 256).split('\n', 1)[0].split()
                steal_s = int(cpu[8]) / self._tick
        except (OSError, ValueError, IndexError):
            pass
        try:
            if cg_fd is not None:
                stat = dict(line.split()[:2] for line in
                            _pread(cg_fd, 1024).splitlines() if line)
                nr_throttled = int(stat.get('nr_throttled', 0))
                throttled_s = int(stat['throttled_usec']) * 1e-6 \
                    if 'throttled_usec' in stat \
                    else int(stat.get('throttled_time', 0)) * 1e-9
        except (OSError, ValueError):
            pass
        return steal_s, nr_throttled, throttled_s

    def close(self):
        """Close every descriptor and drop the series."""
        with self._lock:
            for tid in list(self._fds):
                self._forget(tid)
            for fd in self._machine or ():
                if fd is not None:
                    try:
                        os.close(fd)
                    except OSError:
                        pass
            self._machine = self._clock = None
            self._series.clear()

    # -- the series --------------------------------------------------------
    def sample(self):
        """Take a reading and append it to the series; returns it."""
        with self._lock:
            reading = self._read()
            if reading is not None:
                self._series.append(reading)
            return reading

    def series(self):
        with self._lock:
            return list(self._series)

    def newest(self, max_age_s=None):
        """The newest reading of the series; None where there is none,
        or it is older than ``max_age_s``."""
        with self._lock:
            last = self._series[-1] if self._series else None
        if last is not None and max_age_s is not None and \
                time.perf_counter() - last['t'] > max_age_s:
            return None
        return last

    def between(self, t0, t1):
        """What the process spent between two ``perf_counter`` stamps::

            {'clock', 'seconds', 'process_cpu_s', 'ended_cpu_s',
             'steal_s', 'throttled_s',
             'families': {family: {'cpu_s', 'runq_s', 'threads',
                                   'named'}}}

        by linear interpolation between the readings that bracket each
        stamp.  ``ended_cpu_s`` is the process's count less the threads
        seen at ``t1``: what threads that ended inside had spent.
        None where the series does not bracket both stamps."""
        readings = self.series()
        at0, at1 = _at(readings, t0), _at(readings, t1)
        if at0 is None or at1 is None or t1 < t0:
            return None
        fams = {}
        seen = 0.0
        for tid, (name, named, cpu1, runq1) in at1['threads'].items():
            _n, _p, cpu0, runq0 = at0['threads'].get(
                tid, (name, named, 0.0, 0.0))
            if cpu0 > cpu1:                # the number of a thread that
                cpu0, runq0 = 0.0, 0.0     # ended, given out again
            _fold(fams, name, named, cpu1 - cpu0,
                  None if runq1 is None else runq1 - (runq0 or 0.0))
            seen += cpu1 - cpu0

        def delta(key):
            a, b = at0[key], at1[key]
            return None if a is None or b is None else b - a
        process = at1['process_cpu_s'] - at0['process_cpu_s']
        return {'clock': at1['clock'], 'seconds': t1 - t0,
                'process_cpu_s': process, 'ended_cpu_s': process - seen,
                'steal_s': delta('steal_s'),
                'throttled_s': delta('throttled_s'), 'families': fams}


def _at(readings, t):
    """The reading a stamp would have given, from the two of
    ``readings`` (in order of time) that bracket it, or None.

    A thread seen in both is read off the line between them, one that
    started in between off the line from nothing.  One that ENDED in
    between has no later count: of what the process spent there beyond
    the threads still seen, it is given a share by what it spent in the
    interval before (a pipeline's block threads all end in the second
    after its last gulp, and a window that closes in that second must
    not lose them); with no earlier reading it stays where it was."""
    after = next((i for i, r in enumerate(readings) if r['t'] >= t), None)
    if after is None or (after == 0 and readings[0]['t'] > t):
        return None
    b = readings[after]
    a = readings[after - 1] if b['t'] > t else b
    f = (t - a['t']) / (b['t'] - a['t']) if b['t'] > a['t'] else 0.0

    def mix(x, y):
        return None if x is None or y is None else x + f * (y - x)
    threads = {}
    still = 0.0                    # spent in between by threads in ``b``
    for tid, new in b['threads'].items():
        old = a['threads'].get(tid)
        name, named, cpu_b, runq_b = new
        # a thread that started in between, or the number of one that
        # ended given out again, counts from nothing
        cpu_a, runq_a = old[2:] if old is not None and old[2] <= cpu_b \
            else (0.0, 0.0)
        threads[tid] = (name, named, mix(cpu_a, cpu_b),
                        mix(runq_a, runq_b))
        still += cpu_b - cpu_a
    gone = [tid for tid in a['threads'] if tid not in b['threads']]
    if gone:
        before = readings[after - 2]['threads'] if after >= 2 else {}
        pace = {tid: max(a['threads'][tid][2] - before[tid][2], 0.0)
                for tid in gone if tid in before}
        left = max(b['process_cpu_s'] - a['process_cpu_s'] - still, 0.0)
        scale = left / sum(pace.values()) if sum(pace.values()) else 0.0
        for tid in gone:
            name, named, cpu_a, runq_a = a['threads'][tid]
            threads[tid] = (name, named,
                            cpu_a + f * scale * pace.get(tid, 0.0), runq_a)
    out = {'t': t, 'clock': b['clock'], 'threads': threads}
    for key in ('process_cpu_s', 'steal_s', 'throttled_s'):
        out[key] = mix(a[key], b[key])
    return out


_default = Sampler()


def read():
    """One reading of this process (:meth:`Sampler.read`)."""
    return _default.read()


def sample():
    """Append a reading to the process's series; returns it."""
    return _default.sample()


def series():
    """The process's readings, oldest first (at most ``SERIES_LEN``)."""
    return _default.series()


def newest(max_age_s=None):
    """:meth:`Sampler.newest` of the process's series."""
    return _default.newest(max_age_s)


def between(t0, t1):
    """:meth:`Sampler.between` over the process's series."""
    return _default.between(t0, t1)


def reset():
    """Close the descriptors and drop the series (tests)."""
    _default.close()
