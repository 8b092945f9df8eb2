"""CPU accounting by OS thread (telemetry/threadcpu.py): one reading of
every thread from the scheduler's clock, families, the series and its
interpolation, the fallback to ticks, who samples, and where an
operator reads it."""

from __future__ import annotations

import hashlib
import os
import threading
import time

import pytest

from bifrost_tpu.telemetry import exporter, threadcpu

pytestmark = pytest.mark.skipif(
    not os.path.isdir('/proc/self/task'), reason='no /proc')


@pytest.fixture
def sampler():
    s = threadcpu.Sampler()
    yield s
    s.close()


@pytest.fixture
def clean_series():
    threadcpu.reset()
    yield
    threadcpu.reset()


_BLOCK = bytes(1 << 20)


def spin(until):
    """Burn CPU until ``until`` (an Event) is set, or for so many
    seconds: in native code that lets go of the interpreter lock, as
    the runtime's threads do, so that whoever takes a reading
    meanwhile is not kept waiting for the lock in the middle of it."""
    end = None if isinstance(until, threading.Event) \
        else time.perf_counter() + until
    while not until.is_set() if end is None \
            else time.perf_counter() < end:
        hashlib.sha256(_BLOCK).digest()


def gone(sampler, tid, within=5.0):
    """Whether a thread that has been joined leaves the readings (the
    kernel answers for it a little longer than Python does)."""
    end = time.monotonic() + within
    while tid in sampler.read()['threads'] and time.monotonic() < end:
        time.sleep(0.02)
    return tid not in sampler.read()['threads']


def started(target, name, *args):
    t = threading.Thread(target=target, args=args, name=name, daemon=True)
    t.start()
    return t


# -- one reading -----------------------------------------------------------

def test_a_reading_has_every_thread_and_the_machine(sampler):
    t0 = time.perf_counter()
    reading = sampler.read()
    assert t0 <= reading['t'] <= time.perf_counter()
    assert reading['clock'] in ('schedstat', 'ticks')
    me = reading['threads'][threading.get_native_id()]
    assert me[0] == threading.current_thread().name and me[1] is True
    assert me[2] > 0
    times = os.times()
    assert 0 < reading['process_cpu_s'] <= times.user + times.system
    assert reading['steal_s'] is None or reading['steal_s'] >= 0
    assert set(reading) == {'t', 'clock', 'threads', 'process_cpu_s',
                            'steal_s', 'nr_throttled', 'throttled_s'}


def test_a_spinning_thread_shows_and_a_sleeping_one_does_not(sampler):
    done = threading.Event()
    first = sampler.sample()
    threads = [started(spin, 'Spin_0', done),
               started(done.wait, 'Sleep_0', 10)]
    time.sleep(0.4)
    last = sampler.sample()        # while both are still there
    done.set()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    fams = sampler.between(first['t'], last['t'])['families']
    assert fams['Spin_0']['cpu_s'] >= 0.2
    assert fams['Sleep_0']['cpu_s'] < 0.02
    assert fams['Spin_0']['named'] and fams['Spin_0']['threads'] == 1
    if last['clock'] == 'schedstat':
        assert fams['Spin_0']['runq_s'] >= 0


@pytest.mark.parametrize('name,named,want', [
    ('tf_pjrt_3', False, 'tf_pjrt'),
    ('tf_pjrt_11', False, 'tf_pjrt'),
    ('pjrt-tpu-tasks-7', False, 'pjrt-tpu-tasks'),
    ('worker/12', False, 'worker'),
    ('EventFDAsyncWor', False, 'EventFDAsyncWor'),
    ('futex-default-S', False, 'futex-default-S'),
    ('7', False, '7'),
    ('CopyBlock_0', True, 'CopyBlock_0'),
    ('Pipeline_0/Feed_0', True, 'Pipeline_0/Feed_0'),
    ('xfer-d2h-0', True, 'xfer-d2h-0'),
], ids=lambda v: str(v))
def test_family_folds_numbered_pools_and_keeps_python_names(name, named,
                                                            want):
    assert threadcpu.family(name, named) == want


def test_by_family_adds_a_pool_up():
    reading = {'threads': {1: ('tf_pjrt_0', False, 1.0, 0.25),
                           2: ('tf_pjrt_1', False, 2.0, 0.5),
                           3: ('Feed_0', True, 4.0, 0.0)}}
    fams = threadcpu.by_family(reading)
    assert fams['tf_pjrt'] == {'cpu_s': 3.0, 'runq_s': 0.75, 'threads': 2,
                               'named': False}
    assert fams['Feed_0']['named'] and fams['Feed_0']['cpu_s'] == 4.0


# -- the series ------------------------------------------------------------

def planted(sampler, readings):
    """A series of made-up readings ``(t, process_cpu_s, {tid: (name,
    named, cpu_s, runq_s)})``."""
    for t, process, threads in readings:
        sampler._series.append(
            {'t': t, 'clock': 'schedstat', 'threads': threads,
             'process_cpu_s': process, 'steal_s': t * 0.01,
             'nr_throttled': 0, 'throttled_s': 0.0})


def test_between_interpolates_and_is_none_outside_the_series(sampler):
    planted(sampler, [
        (10.0, 100.0, {1: ('Feed_0', True, 50.0, 1.0),
                       2: ('tf_pjrt_0', False, 20.0, 2.0)}),
        (11.0, 103.0, {1: ('Feed_0', True, 51.0, 1.5),
                       2: ('tf_pjrt_0', False, 22.0, 2.0)}),
        (12.0, 105.0, {1: ('Feed_0', True, 52.0, 1.5),
                       2: ('tf_pjrt_0', False, 23.0, 4.0)}),
    ])
    got = sampler.between(10.5, 11.5)
    assert got['clock'] == 'schedstat'
    assert got['seconds'] == pytest.approx(1.0)
    assert got['process_cpu_s'] == pytest.approx(2.5)
    assert got['families']['Feed_0']['cpu_s'] == pytest.approx(1.0)
    assert got['families']['Feed_0']['runq_s'] == pytest.approx(0.25)
    assert got['families']['tf_pjrt']['cpu_s'] == pytest.approx(1.5)
    assert got['families']['tf_pjrt']['runq_s'] == pytest.approx(1.0)
    assert got['ended_cpu_s'] == pytest.approx(0.0)
    assert got['steal_s'] == pytest.approx(0.01)
    assert got['throttled_s'] == 0.0
    # the ends of the series are inside it
    assert sampler.between(10.0, 12.0)['process_cpu_s'] == \
        pytest.approx(5.0)
    for t0, t1 in ((9.9, 11.0), (10.5, 12.1), (13.0, 14.0), (11.5, 11.0)):
        assert sampler.between(t0, t1) is None
    assert threadcpu.Sampler().between(10.5, 11.5) is None


def test_threads_that_start_or_end_inside(sampler):
    """A thread that started in between counts from nothing; threads
    that ended in between share what the process spent beyond the
    threads still seen, by what each spent in the interval before."""
    planted(sampler, [
        (10.0, 10.0, {1: ('Feed_0', True, 4.0, 0.0),
                      2: ('Sink_0', True, 2.0, 0.0),
                      3: ('bf-metrics', True, 1.0, 0.0)}),
        (11.0, 13.1, {1: ('Feed_0', True, 6.0, 0.0),
                      2: ('Sink_0', True, 3.0, 0.0),
                      3: ('bf-metrics', True, 1.1, 0.0)}),
        (12.0, 15.0, {3: ('bf-metrics', True, 1.2, 0.0),
                      4: ('xfer-d2h-0', True, 0.3, 0.0)}),
    ])
    got = sampler.between(10.0, 11.5)
    fams = got['families']
    # 1.9 in the last second: 0.1 and 0.3 by those still seen, 1.5 by
    # the two that ended, 2 : 1 as in the second before; half of it
    # by 11.5
    assert fams['Feed_0']['cpu_s'] == pytest.approx(2.0 + 0.5)
    assert fams['Sink_0']['cpu_s'] == pytest.approx(1.0 + 0.25)
    assert fams['xfer-d2h-0']['cpu_s'] == pytest.approx(0.15)
    assert fams['bf-metrics']['cpu_s'] == pytest.approx(0.15)
    assert got['process_cpu_s'] == pytest.approx(3.1 + 0.95)
    assert got['ended_cpu_s'] == pytest.approx(0.0, abs=1e-9)
    # once they are gone from both readings, what they spent is the
    # process's alone
    late = sampler.between(10.0, 12.0)
    assert 'Feed_0' not in late['families']
    assert late['ended_cpu_s'] == pytest.approx(5.0 - 0.2 - 0.3)


def test_the_split_adds_up_to_the_process(sampler):
    done = threading.Event()
    first = sampler.sample()
    t = started(spin, 'Spin_1', done)
    spin(0.2)
    time.sleep(0.1)
    last = sampler.sample()
    done.set()
    t.join(10)
    got = sampler.between(first['t'], last['t'])
    seen = sum(f['cpu_s'] for f in got['families'].values())
    assert seen + got['ended_cpu_s'] == pytest.approx(got['process_cpu_s'])
    spent = last['process_cpu_s'] - first['process_cpu_s']
    assert got['process_cpu_s'] == pytest.approx(spent)
    # nobody ended: the threads seen are the process, to a few ticks
    # of ``os.times()``
    assert abs(got['ended_cpu_s']) <= max(0.05 * spent, 0.04)


def test_without_schedstat_the_reading_is_in_ticks(monkeypatch):
    monkeypatch.setattr(threadcpu, '_SCHEDSTAT',
                        '/proc/self/task/%d/no-such-file')
    s = threadcpu.Sampler()
    try:
        done = threading.Event()
        first = s.sample()
        t = started(spin, 'Spin_2', done)
        time.sleep(0.3)
        last = s.sample()
        done.set()
        t.join(10)
        assert first['clock'] == last['clock'] == 'ticks'
        assert all(v[3] is None for v in last['threads'].values())
        got = s.between(first['t'], last['t'])
        assert got['clock'] == 'ticks'
        assert got['families']['Spin_2']['cpu_s'] >= 0.2
        assert got['families']['Spin_2']['runq_s'] is None
        me = last['threads'][threading.get_native_id()]
        assert me[0] == threading.current_thread().name
    finally:
        s.close()


def test_without_proc_there_is_no_reading(monkeypatch):
    monkeypatch.setattr(threadcpu, '_TASKS', '/no-such-dir/task')
    s = threadcpu.Sampler()
    assert s.read() is None and s.sample() is None
    assert s.series() == [] and s.between(0.0, 1.0) is None


def test_descriptors_of_threads_that_ended_are_closed(sampler):
    t = started(time.sleep, 'Short_0', 0.05)
    tid = t.native_id
    sampler.read()
    assert tid in sampler._fds
    t.join(10)
    assert gone(sampler, tid)
    assert tid not in sampler._fds and sampler._fds
    sampler.close()
    assert not sampler._fds and sampler._machine is None


def test_the_series_is_bounded():
    s = threadcpu.Sampler(maxlen=4)
    try:
        for _ in range(6):
            s.sample()
        ser = s.series()
        assert len(ser) == 4
        assert [r['t'] for r in ser] == sorted(r['t'] for r in ser)
        assert s.newest() is ser[-1]
        assert s.newest(max_age_s=60.0) is ser[-1]
        time.sleep(0.02)
        assert s.newest(max_age_s=0.01) is None
    finally:
        s.close()


# -- who samples, and where an operator reads it ---------------------------

def test_the_publisher_samples_once_a_second_and_at_stop(clean_series,
                                                         monkeypatch):
    monkeypatch.setattr(exporter, 'CPU_SAMPLE_S', 0.1)
    monkeypatch.delenv('BF_METRICS_FILE', raising=False)
    pub = exporter.MetricsPublisher(interval=30.0)
    pub.start()
    time.sleep(0.55)
    before = len(threadcpu.series())
    assert 3 <= before <= 7        # one at the start, one a period
    pub.stop()
    assert not pub.is_alive()
    ser = threadcpu.series()
    assert len(ser) == before + 1  # and one at stop()
    assert 'bf-metrics' in threadcpu.by_family(ser[-2])


def test_the_publisher_still_publishes_at_its_interval(clean_series,
                                                       monkeypatch, tmp_path):
    path = tmp_path / 'm.prom'
    monkeypatch.setenv('BF_METRICS_FILE', str(path))
    pub = exporter.MetricsPublisher(interval=0.2)
    pub.start()
    try:
        deadline = time.monotonic() + 10
        while not path.exists() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert path.exists()
    finally:
        pub.stop()
    assert 'bf_thread_cpu_seconds_total{family="MainThread"}' in \
        path.read_text()


def test_snapshot_and_prometheus_carry_the_newest_reading(clean_series):
    t = started(spin, 'Spin_3', 0.2)
    t.join(10)
    keeper = started(time.sleep, 'Keep_0', 2.0)
    reading = threadcpu.sample()
    snap = exporter.snapshot()
    th = snap['threads']
    assert th['clock'] == reading['clock']
    assert th['process_cpu_s'] == reading['process_cpu_s']
    assert th['families'] == threadcpu.by_family(reading)
    assert 'Keep_0' in th['families'] and 'MainThread' in th['families']
    text = exporter.prometheus_text(snap)
    assert '# TYPE bf_thread_cpu_seconds_total counter' in text
    assert 'bf_thread_cpu_seconds_total{family="Keep_0"}' in text
    main = [line for line in text.splitlines() if line.startswith(
        'bf_thread_cpu_seconds_total{family="MainThread"}')]
    assert len(main) == 1 and float(main[0].split()[-1]) == \
        pytest.approx(th['families']['MainThread']['cpu_s'], abs=1e-5)
    if reading['clock'] == 'schedstat':
        assert 'bf_thread_runq_seconds_total{family="MainThread"}' in text
    if reading['steal_s'] is not None:
        assert 'bf_cpu_steal_seconds_total' in text
    # a metric's lines stand together, as the text format asks
    names = [line.split('{')[0].split()[0] for line in text.splitlines()
             if line.startswith('bf_thread_')]
    assert names == sorted(names)
    del keeper


def test_a_snapshot_with_no_fresh_sample_reads_now(clean_series):
    assert threadcpu.series() == []
    snap = exporter.snapshot()
    assert snap['threads']['families']['MainThread']['cpu_s'] > 0
    assert threadcpu.series() == []          # read, not appended


def test_a_thread_keeps_its_python_name_on_its_way_out(sampler,
                                                       monkeypatch):
    """A thread that has returned from ``run`` is off Python's list
    before the kernel's: a reading in between files its seconds under
    the name it was seen with, not under the kernel's."""
    done = threading.Event()
    t = started(done.wait, 'Pipeline_0/Feed_0', 10)
    tid = t.native_id
    assert sampler.read()['threads'][tid][:2] == ('Pipeline_0/Feed_0', True)
    everybody = threading.enumerate
    monkeypatch.setattr(threading, 'enumerate',
                        lambda: [x for x in everybody() if x is not t])
    assert sampler.read()['threads'][tid][:2] == ('Pipeline_0/Feed_0', True)
    monkeypatch.undo()
    done.set()
    t.join(10)
    assert gone(sampler, tid)
    assert tid not in sampler._python and tid not in sampler._fds


def test_a_short_listing_does_not_lose_a_known_thread(sampler, monkeypatch):
    """The kernel's listing can come back short while a thread exits:
    a thread read before is read through its descriptor all the same,
    for one reading; absent from two listings it is let go."""
    done = threading.Event()
    t = started(done.wait, 'Known_0', 10)
    tid = t.native_id
    assert tid in sampler.read()['threads']
    listdir = os.listdir
    monkeypatch.setattr(os, 'listdir', lambda path: [
        e for e in listdir(path) if e != str(tid)])
    assert sampler.read()['threads'][tid][0] == 'Known_0'
    assert tid not in sampler.read()['threads']
    monkeypatch.undo()
    assert sampler.read()['threads'][tid][0] == 'Known_0'
    done.set()
    t.join(10)


def test_a_kernel_that_answers_for_a_dead_thread_is_not_believed(
        monkeypatch):
    """gVisor goes on answering a held descriptor of ``stat`` after
    its thread has ended, with state X: such a thread has ended."""
    monkeypatch.setattr(threadcpu, '_SCHEDSTAT',
                        '/proc/self/task/%d/no-such-file')
    s = threadcpu.Sampler()
    try:
        tid = threading.get_native_id()
        assert tid in s.read()['threads']
        pread = os.pread
        dead = b'0 (python3) X 289 286 1 0 0 0 0 0 0 0 43 0 0 0 20 0 1 0 0'
        monkeypatch.setattr(os, 'pread', lambda fd, n, off: dead
                            if fd == s._fds.get(tid) else pread(fd, n, off))
        reading = s.read()
        assert tid not in reading['threads'] and tid not in s._fds
        assert reading['threads']          # the others are read as ever
    finally:
        s.close()
