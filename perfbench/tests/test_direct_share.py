"""``xfer.h2d_direct_share`` over the program's counters; nothing where
the program does not count them (a parent from before it did) or sent
nothing."""

import pytest

import progcounters
import run as harness

SERVED = {'gpuspec-replay', 'xcorr-replay', 'gpuspec-hsr-replay',
          'beamform-tab-replay'}


@pytest.mark.parametrize('counts,want', [
    ({'xfer.h2d_bytes': 8 << 30, 'xfer.h2d_direct_bytes': 8 << 30}, 100.0),
    ({'xfer.h2d_bytes': 4 << 30, 'xfer.h2d_direct_bytes': 1 << 30}, 25.0),
    ({'xfer.h2d_bytes': 4 << 30, 'xfer.h2d_direct_bytes': 0}, 0.0),
    ({'xfer.h2d_bytes': 4 << 30, 'xfer.h2d_staged': 16}, None),
    ({'xfer.h2d_bytes': 0, 'xfer.h2d_direct_bytes': 0}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'none_counted_as_0', 'counter_absent',
        'nothing_sent', 'no_counters', 'no_module'])
def test_share_of_the_bytes_sent_from_the_ring_span(counts, want,
                                                    monkeypatch):
    monkeypatch.setattr(progcounters, 'counters', lambda: counts)
    got = harness.reader('per_layer', 'xfer.h2d_direct_share').read(None)
    assert got == (want if want is None else pytest.approx(want))


def test_the_benchmark_lists_it_in_the_served_cells():
    for cell in SERVED | {'gpuspec-resident'}:
        bench, c, _cfg, _mod = harness.load_cell(cell)
        listed = {m['name']: m
                  for m in harness.metrics_of(bench, c, 'per_layer')}
        assert ('xfer.h2d_direct_share' in listed) == (cell in SERVED)
        if cell in SERVED:
            m = listed['xfer.h2d_direct_share']
            assert m['moves'] == 'host_cpu_s_per_gsample'
            assert m['layer'] == 'H2D and D2H'
            assert m['source'] == 'program_counter'
            assert m['unit'] == '%' and m['better'] == 'higher'
