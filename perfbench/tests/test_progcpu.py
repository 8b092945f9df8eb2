"""``progcpu``: the window's CPU seconds by role from the program's
``threadcpu`` series, the CPU burnt inside waiting spans from the
spans' ``cpu_us``, and the readers that report them; nothing
where the program has no such module, no series, a series that does
not bracket the window, or a split that does not add up."""

import types

import pytest

import progcpu
import progspans
import run as harness
from util import rehearse

SERVED = ['gpuspec-replay', 'xcorr-replay', 'gpuspec-hsr-replay',
          'beamform-tab-replay']
ROLE_OF = {'host.cpu_bench_s_per_gsample': 'bench',
           'xfer.cpu_s_per_gsample': 'transfer',
           'xfer.runtime_cpu_s_per_gsample': 'runtime',
           'dispatch.cpu_s_per_gsample': 'chain'}
BLOCKS = [('Pipeline_0/CopyBlock_0', ['ring_0'], ['ring_1']),
          ('Pipeline_0/FusedBlock_0', ['ring_1'], ['ring_2']),
          ('Pipeline_0/CopyBlock_1', ['ring_2'], ['ring_3'])]
#: a window's families as ``threadcpu.between`` gives them: 89.4 CPU-s
#: seen and 0.4 by threads that ended
FAMILIES = {
    'Pipeline_0/Feed_0': (28.6, 0.30, True),
    'Pipeline_0/Sink_0': (1.3, 0.05, True),
    'MainThread': (0.07, 0.0, True),
    'bench-opener': (0.0, 0.0, True),
    'pjrt-tpu-tasks': (25.7, 1.0, False),
    'futex-default-S': (17.3, 0.8, False),
    'EventFDAsyncWor': (3.2, 0.1, False),
    'tf_pjrt': (0.33, 0.0, False),
    'xfer-d2h-0': (5.5, 0.2, True),
    'Pipeline_0/CopyBlock_0': (4.2, 0.1, True),
    'Pipeline_0/CopyBlock_1': (1.7, 0.05, True),
    'Pipeline_0/FusedBlock_0': (1.4, 0.05, True),
    'bf-health': (0.09, 0.0, True),
    'bf-metrics': (0.01, 0.0, True),
    'Thread-7 (worker)': (0.0, 0.0, True),
}


def between(cpu_scale=1.0, clock='schedstat', ended=0.4):
    fams = {name: {'cpu_s': cpu * cpu_scale,
                   'runq_s': None if clock == 'ticks' else runq,
                   'threads': 1, 'named': named}
            for name, (cpu, runq, named) in FAMILIES.items()}
    seen = sum(f['cpu_s'] for f in fams.values())
    return {'clock': clock, 'seconds': 30.0, 'families': fams,
            'process_cpu_s': seen + ended, 'ended_cpu_s': ended,
            'steal_s': 0.02, 'throttled_s': 0.0}


class Run(object):
    """What the readers read of a run: a window of 30 s, 100 Gsamples,
    89.8 CPU-s by the harness's own count."""

    def __init__(self, cpu_seconds=89.8, samples=100e9):
        self.win = types.SimpleNamespace(t_open=1000.0, t_close=1030.0,
                                         seconds=30.0, blocks=BLOCKS)
        self._cpu, self._samples = cpu_seconds, samples
        self.notes = []

    def note(self, line):
        self.notes.append(line)

    def cpu_seconds(self):
        return self._cpu

    def samples(self):
        return self._samples

    def hist_seconds(self, name):
        return {'xfer.h2d_hold_wait_s': 3.0}.get(name)


def read(name, run):
    return harness.reader('per_layer', name).read(run)


def test_every_family_has_one_role_and_they_partition_the_process(
        monkeypatch):
    monkeypatch.setattr(progcpu, 'window_cpu', lambda t0, t1: between())
    run = Run()
    split = progcpu.families(run)
    blocks = {b[0] for b in BLOCKS}
    roles = {name: progcpu.role(name, named, blocks)
             for name, (_c, _q, named) in FAMILIES.items()}
    assert set(roles.values()) == set(progcpu.ROLES)
    assert [n for n, r in roles.items() if r == 'bench'] == [
        'Pipeline_0/Feed_0', 'Pipeline_0/Sink_0', 'MainThread',
        'bench-opener']
    assert [n for n, r in roles.items() if r == 'runtime'] == [
        'pjrt-tpu-tasks', 'futex-default-S', 'EventFDAsyncWor', 'tf_pjrt']
    assert [n for n, r in roles.items() if r == 'transfer'] == [
        'xfer-d2h-0', 'Pipeline_0/CopyBlock_0', 'Pipeline_0/CopyBlock_1']
    assert [n for n, r in roles.items() if r == 'chain'] == [
        'Pipeline_0/FusedBlock_0', 'bf-health', 'bf-metrics',
        'Thread-7 (worker)']
    for r in progcpu.ROLES:
        assert split[r] == pytest.approx(
            sum(FAMILIES[n][0] for n in roles if roles[n] == r))
    assert sum(split.values()) + 0.4 == pytest.approx(run.cpu_seconds())
    # a block of that name that is not the program's is no transfer
    assert progcpu.role('Pipeline_0/CopyBlock_9', True, blocks) == 'chain'
    # the four readers, per 10^9 samples
    for name, r in ROLE_OF.items():
        assert read(name, run) == pytest.approx(split[r] / 100.0)
    assert read('host.cpu_bench_s_per_gsample', run) == \
        pytest.approx(0.2997)
    assert read('xfer.runtime_cpu_s_per_gsample', run) == \
        pytest.approx(0.4653)
    # the account goes to the notes: every family of 0.05 CPU-s or
    # more by name, the rest, ended, the residual, the clock
    notes = '\n'.join(run.notes)
    for name, (cpu, _q, _n) in FAMILIES.items():
        assert (('cpu: %s ' % name) in notes) == (cpu >= 0.05)
    assert '(3 families under 0.05)' in notes
    assert 'clock schedstat' in notes and 'ended_cpu_s 0.400' in notes
    assert 'residual' in notes and 'steal_s 0.02' in notes


@pytest.mark.parametrize('scale,short', [
    (1.0, False), (0.97, False), (1.04, False), (0.90, True), (1.10, True),
], ids=['adds_up', '3_short', '4_over', '10_short', '10_over'])
def test_a_split_that_does_not_add_up_reads_nothing(scale, short,
                                                    monkeypatch):
    monkeypatch.setattr(progcpu, 'window_cpu',
                        lambda t0, t1: between(cpu_scale=scale))
    run = Run()
    values = [read(name, run) for name in ROLE_OF]
    assert all(v is None for v in values) if short \
        else all(v is not None and v >= 0 for v in values)
    assert any('no reading' in line for line in run.notes) == short
    # the run-queue share is a ratio of the reading's own: it stands
    assert read('host.runq_wait_share.replay', run) is not None


@pytest.mark.parametrize('got', [None, 'no_module'],
                         ids=['no_series_or_not_bracketed', 'no_module'])
def test_nothing_to_read_reads_nothing(got, monkeypatch):
    if got == 'no_module':
        import bifrost_tpu.telemetry as telemetry
        monkeypatch.setitem(__import__('sys').modules,
                            'bifrost_tpu.telemetry.threadcpu', None)
        monkeypatch.delattr(telemetry, 'threadcpu', raising=False)
    else:
        from bifrost_tpu.telemetry import threadcpu
        threadcpu.reset()
    run = Run()
    assert progcpu.window_cpu(run.win.t_open, run.win.t_close) is None
    for name in list(ROLE_OF) + ['host.runq_wait_share.replay',
                                 'host.runq_wait_share.resident',
                                 'dispatch.wait_cpu_s_per_gsample']:
        assert read(name, run) is None
    assert progcpu.families(run) is None


def test_a_series_that_does_not_bracket_the_window_reads_nothing():
    from bifrost_tpu.telemetry import threadcpu
    threadcpu.reset()
    try:
        first = threadcpu.sample()
        last = threadcpu.sample()
        assert progcpu.window_cpu(first['t'], last['t']) is not None
        assert progcpu.window_cpu(first['t'] - 1.0, last['t']) is None
        assert progcpu.window_cpu(first['t'], last['t'] + 1.0) is None
    finally:
        threadcpu.reset()


def test_the_run_queue_share(monkeypatch):
    monkeypatch.setattr(progcpu, 'window_cpu', lambda t0, t1: between())
    want = 100.0 * sum(q for _c, q, _n in FAMILIES.values()) / \
        sum(c for c, _q, _n in FAMILIES.values())
    for name in ('host.runq_wait_share.replay',
                 'host.runq_wait_share.resident'):
        assert read(name, Run()) == pytest.approx(want)
    monkeypatch.setattr(progcpu, 'window_cpu',
                        lambda t0, t1: between(clock='ticks'))
    assert read('host.runq_wait_share.replay', Run()) is None


def ev(name, cat, t0, t1, cpu_ms, args=None):
    """An event on a span clock whose origin is 1000 s."""
    return (name, cat, t0 * 1e6, (t1 - t0) * 1e6, args,
            None if cpu_ms is None else cpu_ms * 1e3)


def test_cpu_burnt_inside_waiting_spans():
    copy, fused = 'Pipeline_0/CopyBlock_0', 'Pipeline_0/FusedBlock_0'
    events = [
        # a wait of 4 s that burnt 40 ms, 10 of them in a child's work
        (copy, ev('h2d.hold_wait', 'wait', 1.0, 5.0, 40.0)),
        (copy, ev('proclog.write', 'host', 2.0, 2.5, 10.0)),
        # work that holds a wait: the wait's own 2 ms count
        (copy, ev('h2d', 'xfer', 6.0, 8.0, 500.0)),
        (copy, ev('ring_1.reserve', 'ring', 6.5, 7.5, 2.0)),
        # an interval across calls: left out, and nesting survives it
        (copy, ev('h2d.hold', 'wait', 7.0, 12.0, None)),
        (copy, ev('ring_0.acquire', 'ring', 9.0, 10.0, 1.0)),
        # straddles the window's close at 30 s: half of its 8 ms
        (copy, ev('ring_0.acquire', 'ring', 28.0, 32.0, 8.0)),
        # straddles its opening at 0 s: a quarter of its 4 ms
        (fused, ev('Pipeline_0/FusedBlock_0.sync_wait', 'wait',
                   -3.0, 1.0, 4.0)),
        # recorded after the fact: no CPU time, counts as nothing
        (fused, ev('jit.compile', 'jit', 2.0, 3.0, None)),
        # a synthesized member span and another thread's wait: left out
        (fused, ev('m.on_data', 'wait', 4.0, 5.0, 9.0,
                   {'synthesized': True})),
        ('Pipeline_0/Feed_0', ev('ring_0.reserve', 'ring', 1.0, 2.0, 7.0)),
    ]
    got = progcpu.wait_cpu(events, 1000.0, 1000.0, 1030.0, {copy, fused})
    assert got == pytest.approx({
        'h2d.hold_wait': 0.030, 'ring_1.reserve': 0.002,
        'ring_0.acquire': 0.001 + 0.004,
        'Pipeline_0/FusedBlock_0.sync_wait': 0.001})
    # events of a parent from before spans carried CPU time
    old = [(t, e[:5]) for t, e in events]
    assert progcpu.wait_cpu(old, 1000.0, 1000.0, 1030.0,
                            {copy, fused}) is None


def test_the_wait_reader_over_a_run(monkeypatch):
    copy = 'Pipeline_0/CopyBlock_0'
    events = [(copy, ev('h2d.hold_wait', 'wait', 1.0, 5.0, 1900.0)),
              ('Pipeline_0/Sink_0', ev('ring_3.acquire', 'ring',
                                       1.0, 2.0, 500.0))]
    monkeypatch.setattr(progcpu, 'window_cpu', lambda t0, t1: between())
    monkeypatch.setattr(progspans, 'program_events',
                        lambda: (events, 1000.0, {}))
    run = Run()
    # the sink's thread is the harness's: not counted
    assert read('dispatch.wait_cpu_s_per_gsample', run) == \
        pytest.approx(1.9 / 100.0)
    assert any('h2d.hold_wait' in line and '1.9000' in line
               for line in run.notes)
    # a buffer that evicted spans of the window: no reading
    monkeypatch.setattr(progspans, 'program_events',
                        lambda: (events, 1000.0, {copy: 3}))
    assert read('dispatch.wait_cpu_s_per_gsample', Run()) is None


def test_the_hold_wait_share():
    assert read('xfer.h2d_hold_wait_share', Run()) == pytest.approx(10.0)
    run = Run()
    run.hist_seconds = lambda name: None
    assert read('xfer.h2d_hold_wait_share', run) is None


def test_the_benchmark_lists_the_six_that_read_something_on_the_chip():
    """``host.runq_wait_share`` has its reader and is listed in no
    cell: the machine the benchmark runs on keeps no ``schedstat``
    (gVisor), so it would read nothing in every run (PERF.md section
    6, PR 37)."""
    want = {
        'host.cpu_bench_s_per_gsample':
            ('CPU-s/Gsample', 'program_counter', 'sink',
             'host_cpu_s_per_gsample', SERVED),
        'xfer.cpu_s_per_gsample':
            ('CPU-s/Gsample', 'program_counter', 'H2D and D2H',
             'host_cpu_s_per_gsample', SERVED),
        'xfer.runtime_cpu_s_per_gsample':
            ('CPU-s/Gsample', 'program_counter', 'H2D and D2H',
             'host_cpu_s_per_gsample', SERVED),
        'dispatch.cpu_s_per_gsample':
            ('CPU-s/Gsample', 'program_counter', 'dispatch',
             'host_cpu_s_per_gsample', SERVED),
        'dispatch.wait_cpu_s_per_gsample':
            ('CPU-s/Gsample', 'program_span', 'dispatch',
             'host_cpu_s_per_gsample', SERVED),
        'xfer.h2d_hold_wait_share':
            ('%', 'program_span', 'H2D and D2H', 'sustained_msps', SERVED),
    }
    bench = harness.load_cell('gpuspec-replay')[0]
    listed = {m['name']: m for m in bench['per_layer']}
    assert [m['name'] for m in bench['per_layer'][-6:]] == list(want)
    assert not [n for n in listed if n.startswith('host.runq_wait_share')]
    for name in ('host.runq_wait_share.replay',
                 'host.runq_wait_share.resident'):
        harness.reader('per_layer', name)          # the reader waits
    for name, (unit, source, layer, moves, cells) in want.items():
        m = listed[name]
        assert (m['unit'], m['source'], m['layer'], m['moves'],
                m['workloads'], m['better']) == \
            (unit, source, layer, moves, cells, 'lower')
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
        harness.reader('per_layer', name)          # it has a reader
    for cell in SERVED + ['gpuspec-resident']:
        b, c, _cfg, _mod = harness.load_cell(cell)
        mine = {m['name'] for m in harness.metrics_of(b, c, 'per_layer')}
        assert (mine & set(want)) == {
            n for n, w in want.items() if cell in w[4]}


def test_a_rehearsed_run_adds_up():
    """One run on the CPU through the real series and the real spans:
    the roles are disjoint, cover every family, and add up (with what
    threads that ended had spent) to the harness's own count."""
    from bifrost_tpu.telemetry import threadcpu
    import drive
    threadcpu.reset()
    # a rehearsal's window opens within milliseconds of the pipeline's
    # start, before its housekeeping thread has read once: a reading
    # from before stands in for the seconds of warm-up a real run has
    threadcpu.sample()
    kept = []
    run_window = drive.run_window

    def keeping(*args, **kwargs):
        kept.append(run_window(*args, **kwargs))
        return kept[-1]
    drive.run_window = keeping
    try:
        res = rehearse('gpuspec-replay', seed=3, seconds=2.0)
    finally:
        drive.run_window = run_window
    assert res['correct'] is True
    win = kept[0]
    got = progcpu.window_cpu(win.t_open, win.t_close)
    assert got is not None and got['clock'] in ('schedstat', 'ticks')
    run = Run(cpu_seconds=win.cpu_close - win.cpu_open, samples=1e9)
    run.win = win
    split = progcpu.families(run)
    assert set(split) == set(progcpu.ROLES)
    blocks = {b[0] for b in win.blocks}
    roles = {n: progcpu.role(n, f['named'], blocks)
             for n, f in got['families'].items()}
    assert set(roles.values()) <= set(progcpu.ROLES)
    for r in progcpu.ROLES:
        assert split[r] == pytest.approx(sum(
            f['cpu_s'] for n, f in got['families'].items()
            if roles[n] == r))
    assert split['bench'] > 0 and split['transfer'] > 0 and \
        split['chain'] > 0
    assert sum(split.values()) + got['ended_cpu_s'] == pytest.approx(
        run.cpu_seconds(), rel=0.05)
    # the spans of the run carry their thread's CPU time
    spent = progcpu.wait_cpu_per_gsample(run)
    assert spent is not None and spent >= 0
    assert any(line.startswith('cpu: waiting in ') for line in run.notes)
