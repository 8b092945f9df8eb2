"""``xfer.h2d_word_share`` and ``ops.word_gulp_share.resident`` over
the program's counters; nothing where the program does not count them
(a parent from before it did) or did none of the work they count."""

import pytest

import progcounters
import run as harness


def share(name, counts, monkeypatch):
    monkeypatch.setattr(progcounters, 'counters', lambda: counts)
    return harness.reader('per_layer', name).read(None)


@pytest.mark.parametrize('counts,want', [
    ({'xfer.h2d_bytes': 8 << 30, 'xfer.h2d_word_bytes': 8 << 30}, 100.0),
    ({'xfer.h2d_bytes': 4 << 30, 'xfer.h2d_word_bytes': 1 << 30}, 25.0),
    ({'xfer.h2d_bytes': 4 << 30, 'xfer.h2d_word_bytes': 0}, 0.0),
    ({'xfer.h2d_bytes': 4 << 30, 'xfer.h2d_staged': 16}, None),
    ({'xfer.h2d_bytes': 0, 'xfer.h2d_word_bytes': 0}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'none_counted_as_0', 'counter_absent',
        'nothing_sent', 'no_counters', 'no_module'])
def test_share_of_the_bytes_sent_as_words(counts, want, monkeypatch):
    got = share('xfer.h2d_word_share', counts, monkeypatch)
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize('counts,want', [
    ({'spectrometer.gulps': 1880, 'spectrometer.word_gulps': 1880}, 100.0),
    ({'spectrometer.gulps': 800, 'spectrometer.word_gulps': 200}, 25.0),
    ({'spectrometer.gulps': 700, 'spectrometer.word_gulps': 0}, 0.0),
    ({'spectrometer.gulps': 700, 'spectrometer.long_gulps': 700}, None),
    ({'spectrometer.gulps': 0, 'spectrometer.word_gulps': 0}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'none_counted_as_0', 'counter_absent',
        'nothing_transformed', 'no_counters', 'no_module'])
def test_share_of_gulps_whose_program_started_from_words(counts, want,
                                                         monkeypatch):
    got = share('ops.word_gulp_share.resident', counts, monkeypatch)
    assert got == (want if want is None else pytest.approx(want))


def test_the_benchmark_lists_them_where_there_is_something_to_read():
    served = {'gpuspec-replay', 'xcorr-replay', 'gpuspec-hsr-replay'}
    want = {'xfer.h2d_word_share':
            (served, 'sustained_msps', 'H2D and D2H'),
            'ops.word_gulp_share.resident':
            ({'gpuspec-resident'}, 'resident_msps', 'kernels')}
    for cell in served | {'gpuspec-resident'}:
        bench, c, _cfg, _mod = harness.load_cell(cell)
        listed = {m['name']: m
                  for m in harness.metrics_of(bench, c, 'per_layer')}
        for name, (cells, moves, layer) in want.items():
            assert (name in listed) == (cell in cells), (name, cell)
            if name in listed:
                m = listed[name]
                assert m['moves'] == moves and m['layer'] == layer
                assert m['source'] == 'program_counter'
                assert m['unit'] == '%' and m['better'] == 'higher'
    # new entries stand at the end of the list
    assert [m['name'] for m in bench['per_layer'][-2:]] == list(want)


def test_a_rehearsed_run_counts_what_the_readers_read():
    """The served cell's rehearsal on the CPU: every byte sent and
    every gulp transformed is counted as words (a count, not a
    time)."""
    from bifrost_tpu.telemetry import counters
    from util import rehearse
    counters.reset()              # process-wide: other tests' gulps
    res = rehearse('gpuspec-replay')
    assert res['correct'] is True, res['checks']
    counts = progcounters.counters()
    assert counts['xfer.h2d_word_bytes'] == counts['xfer.h2d_bytes'] > 0
    assert counts['spectrometer.word_gulps'] == \
        counts['spectrometer.gulps'] > 0
    assert harness.reader('per_layer', 'xfer.h2d_word_share') \
        .read(None) == pytest.approx(100.0)
    assert harness.reader('per_layer', 'ops.word_gulp_share.resident') \
        .read(None) == pytest.approx(100.0)
