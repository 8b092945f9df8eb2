"""``xfer.d2h_worker_fill_share`` on a hand-made event list: fills by
count, inside the window only, by the name of the thread they ran on;
nothing where the program has no completion threads."""

import pytest

import run as harness

ORIGIN = 100.0


def ev(name, t0, t1):
    return (name, 'xfer', (t0 - ORIGIN) * 1e6, (t1 - t0) * 1e6, None)


class Win(object):
    t_open, t_close, seconds = 101.0, 111.0, 10.0


class Run(object):
    win = Win()

    def __init__(self, events):
        self._progspans_events = (events, ORIGIN, {}) if events else None


def share(events):
    return harness.reader('per_layer', 'xfer.d2h_worker_fill_share').read(
        Run(events))


def test_fills_are_counted_by_the_thread_they_ran_on():
    events = [
        ('xfer-d2h-0', ev('d2h.fill', 102., 103.)),
        ('xfer-d2h-1', ev('d2h.fill', 102.5, 103.5)),
        ('xfer-d2h-0', ev('d2h.fill', 104., 105.)),
        ('Sink_0', ev('d2h.fill', 106., 107.)),          # a caller's
        ('xfer-d2h-0', ev('d2h.asarray', 101., 102.)),   # not a fill
        ('xfer-d2h-1', ev('d2h.fill', 100., 101.5)),     # began before
        ('CopyBlock_1', ev('d2h.fill', 111., 112.)),     # began after
    ]
    assert share(events) == pytest.approx(75.0)
    assert share(events[:3]) == pytest.approx(100.0)


def test_a_program_without_completion_threads_reads_as_nothing():
    assert share([('CopyBlock_1', ev('d2h.fill', 102., 103.)),
                  ('Sink_0', ev('d2h.fill', 104., 105.))]) is None
    assert share([]) is None


def test_the_benchmark_lists_it_for_the_served_cell_only():
    bench, cell, _cfg, _mod = harness.load_cell('gpuspec-replay')
    (m,) = [m for m in harness.metrics_of(bench, cell, 'per_layer')
            if m['name'] == 'xfer.d2h_worker_fill_share']
    assert m['moves'] == 'sustained_msps' and m['layer'] == 'H2D and D2H'
    bench, cell, _cfg, _mod = harness.load_cell('gpuspec-resident')
    assert not [m for m in harness.metrics_of(bench, cell, 'per_layer')
                if m['name'] == 'xfer.d2h_worker_fill_share']
