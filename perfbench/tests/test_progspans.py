"""``progspans.py`` on a hand-made event list, its intervals through the
trace reduction on the recorded fixture, and every reader that rests
on the program's spans and new histograms on a rehearsal of each cell
(``util.rehearse`` runs ``--trace 0`` and calls no per-layer reader, so
the window is driven here and a ``run.Run`` built over it)."""

import json
import math
import os

import numpy as np
import pytest

import progspans
import run as harness
import tracered

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ORIGIN = 100.0


def ev(name, cat, t0, t1, **args):
    """One event as the program records it: (name, cat, ts_us, dur_us,
    args) on the span clock, from ``perf_counter`` seconds."""
    return (name, cat, (t0 - ORIGIN) * 1e6, (t1 - t0) * 1e6, args or None)


#: thread A, a gulp loop: acquire 0-2, on_data 2-8 holding h2d 3-7,
#: itself holding stage 3-5 and put 5-6.5; reserve 8-9; nothing 9-10;
#: then a second acquire 10-14 that runs past the window's end at 12
EVENTS = [
    ('A', ev('r.acquire', 'ring', 100., 102., frame=0)),
    ('A', ev('h2d.stage', 'xfer', 103., 105.)),
    ('A', ev('h2d.put', 'xfer', 105., 106.5)),
    ('A', ev('h2d', 'xfer', 103., 107.)),
    ('A', ev('blk.on_data', 'compute', 102., 108.)),
    ('A', ev('r.reserve', 'ring', 108., 109.)),
    ('A', ev('r.acquire', 'ring', 110., 114.)),
    ('B', ev('d2h.asarray', 'xfer', 101., 104.)),
    ('B', ev('d2h', 'xfer', 101., 104.)),
    ('B', ev('d2h.depth_wait', 'wait', 100.5, 104.5)),
    ('B', ev('member.on_data', 'compute', 100., 112., synthesized=1)),
]


def test_nesting_self_time_and_clipping():
    per = progspans.by_thread(EVENTS, ORIGIN, 101., 112.)
    a = per['A']
    # covered: acquire 101-102, on_data 102-108, reserve 108-109,
    # acquire 110-112 (clipped) = 10 of 11 s; waiting: the ring spans'
    # self time 1 + 1 + 2
    assert a['uncovered'] == pytest.approx(1.0)
    assert a['wait'] == pytest.approx(4.0)
    assert a['work'] == pytest.approx(6.0)
    assert a['self']['blk.on_data'] == pytest.approx(2.0)    # 6 - h2d's 4
    assert a['self']['h2d'] == pytest.approx(0.5)            # 4 - 2 - 1.5
    assert a['self']['h2d.stage'] == pytest.approx(2.0)
    assert a['self']['h2d.put'] == pytest.approx(1.5)
    assert a['self']['r.acquire'] == pytest.approx(3.0)
    # B: a wait span that holds work waits only for its own self time
    # (0.5 before + 0.5 after the transfer it retires); the synthesized
    # span is left out
    b = per['B']
    assert b['wait'] == pytest.approx(1.0 - 0.5)     # 100.5-101 is clipped
    assert b['work'] == pytest.approx(3.0)
    assert b['self']['d2h'] == pytest.approx(0.0)
    assert 'member.on_data' not in b['self']
    assert b['uncovered'] == pytest.approx(11.0 - 3.5)


def test_refuses_where_a_buffer_dropped_events_of_the_window():
    notes = []
    # A's oldest kept span ends at 102, after the window opened at 101,
    # and A has evicted spans: some of the window may be missing
    assert progspans.by_thread(EVENTS, ORIGIN, 101., 112., {'A': 3},
                               notes.append) is None
    assert len(notes) == 1 and 'A' in notes[0] and '3' in notes[0]
    # evictions that all ended before the window opened cost nothing
    assert progspans.by_thread(EVENTS, ORIGIN, 103., 112., {'A': 3},
                               notes.append) is not None
    assert progspans.by_thread(EVENTS, ORIGIN, 101., 112., {'A': 0})


def test_intervals_go_through_the_trace_reduction_as_they_are():
    with open(os.path.join(HERE, 'fixtures', 'small_trace.json')) as f:
        fx = json.load(f)
    lo, hi = fx['t_open'], fx['t_close']
    mid = 0.5 * (lo + hi)
    origin = lo - 5.0
    events = [
        ('t', ('first.half', 'compute', (lo - origin) * 1e6,
               (mid - lo) * 1e6, None)),
        ('t', ('second.half', 'wait', (mid - origin) * 1e6,
               (hi - mid) * 1e6, None)),
        ('t', ('drawn', 'compute', (lo - origin) * 1e6, (hi - lo) * 1e6,
               {'synthesized': 1}))]
    iv = progspans.intervals(events, origin)
    assert sorted(iv) == ['first.half', 'second.half']
    assert iv['first.half'] == [[pytest.approx(lo), pytest.approx(mid)]]
    to_ns, _ = tracered.clock(fx['trace'], fx['anchor_stamps'])
    window = (float(to_ns(lo)), float(to_ns(hi)))
    spans = {n: to_ns(np.array(v)) for n, v in iv.items()}
    got = tracered.reduce(fx['trace'], window, spans)
    idle = dict(got['idle_gaps'])
    # the two halves cover the window, so every idle second is theirs
    assert 'unattributed' not in idle
    assert idle['first.half'] + idle['second.half'] == pytest.approx(
        got['window_s'] - got['busy_s'], rel=1e-9)
    # and with the bench's own spans beside them, as drive.py would
    both = dict(spans)
    both.update({n: to_ns(np.array(v)) for n, v in fx['spans'].items()})
    assert 'unattributed' not in dict(
        tracered.reduce(fx['trace'], window, both)['idle_gaps'])


#: the readers that rest on the program's spans and new histograms
NEW = ['xfer.h2d_stage_share', 'xfer.h2d_put_share', 'xfer.d2h_ready_share',
       'xfer.d2h_asarray_share', 'xfer.d2h_fill_share',
       'dispatch.sync_wait_share', 'dispatch.bottleneck_work_share',
       'dispatch.uncovered_share', 'dispatch.compile_s_setup']


def new_metrics(bench, cell):
    return [m for m in harness.metrics_of(bench, cell, 'per_layer')
            if any(m['name'] == n or m['name'].startswith(n + '.')
                   for n in NEW)]


@pytest.mark.parametrize('workload', ['gpuspec-replay', 'gpuspec-resident'])
def test_every_new_reader_reads_a_rehearsal(workload):
    import sys
    bench, cell, cfg, mod = harness.load_cell(workload)
    cfg = harness.merge(cfg, cfg.get('rehearse', {}))
    harness.set_environment(None)
    sys.path.insert(0, os.path.dirname(HERE))
    import bifrost_tpu as bf
    import drive
    import traffic
    seed = 2600000007
    mix = traffic.load(cell['traffic'])
    pool = traffic.make_pool(cfg, mix, seed)
    win = drive.run_window(bf, mod, cfg, mix, pool,
                           traffic.replay_order(mix, seed),
                           traffic.Sampler(cfg, mix, seed, mod.pick), 0.5)
    assert win.t_close is not None
    r = harness.Run(win, cfg, mod, None, None, 0)
    mine = new_metrics(bench, cell)
    assert len(mine) == {'gpuspec-replay': 9, 'gpuspec-resident': 4}[workload]
    for m in mine:
        value = harness.reader('per_layer', m['name']).read(r)
        assert value is not None and math.isfinite(value) and value > 0, \
            m['name']
    per = progspans.threads(r)
    assert set(name for name, _i, _o in win.blocks) <= set(per)
    name, split = progspans.bottleneck(r)
    assert split['work'] + split['wait'] + split['uncovered'] == \
        pytest.approx(win.seconds)
    text = '\n'.join(r.notes)
    assert 'the most worked thread is ' + name in text
    assert 'none inside the window' in text
    if workload == 'gpuspec-replay':
        # the parts lie inside the whole they split
        h = r.hist_seconds
        assert h('xfer.d2h_ready_s') + h('xfer.d2h_asarray_s') <= \
            h('xfer.d2h_wait_s')
        assert h('xfer.h2d_stage_s') + h('xfer.h2d_put_s') <= h('xfer.h2d_s')
        # someone ran the ring fill, and the notes can say who
        assert any('d2h.fill' in t['self'] for t in per.values())


def test_a_program_that_recorded_nothing_reads_as_nothing(monkeypatch):
    """The parent commit's recorder is off unless asked: no events, and
    no ``origin_s``.  Every reader then returns None and raises
    nothing."""
    from bifrost_tpu.telemetry import spans
    monkeypatch.setattr(spans, 'events', lambda: [])
    assert progspans.program_events() is None
    monkeypatch.delattr(spans, 'origin_s')
    assert progspans.program_events() is None

    class Win(object):
        t_open, t_close, seconds = 1.0, 2.0, 1.0
        hists = [{}, {'xfer.h2d_s': {'sum': 1.0, 'count': 1}}]
        blocks = [('b', ['r0'], ['r1'])]

    class Run(object):
        win = Win()
        notes = []

        def note(self, line):
            self.notes.append(line)

        def hist_seconds(self, name):
            return None

        def gulps(self):
            return 1

    bench, cell, _cfg, _mod = harness.load_cell('gpuspec-replay')
    for m in new_metrics(bench, cell):
        assert harness.reader('per_layer', m['name']).read(Run()) is None
