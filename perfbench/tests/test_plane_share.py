"""``xfer.d2h_plane_share`` over the program's counters: the bytes cut
from planes over the bytes brought across; nothing where the program
does not count them (a parent from before it did) or moved nothing."""

import pytest

import progcounters
import run as harness


def share(counts, monkeypatch):
    monkeypatch.setattr(progcounters, 'counters', lambda: counts)
    return harness.reader('per_layer', 'xfer.d2h_plane_share').read(None)


@pytest.mark.parametrize('counts,want', [
    ({'xfer.d2h_bytes': 8 << 30, 'xfer.d2h_plane_bytes': 8 << 30}, 100.0),
    ({'xfer.d2h_bytes': 4 << 30, 'xfer.d2h_plane_bytes': 1 << 30}, 25.0),
    ({'xfer.d2h_bytes': 4 << 30, 'xfer.d2h_plane_bytes': 0}, 0.0),
    ({'xfer.d2h_bytes': 4 << 30, 'xfer.d2h_pair_bytes': 4 << 30}, None),
    ({'xfer.d2h_bytes': 0, 'xfer.d2h_plane_bytes': 0}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'none_counted_as_0', 'counter_absent',
        'nothing_moved', 'no_counters', 'no_module'])
def test_share_of_the_bytes_cut_from_planes(counts, want, monkeypatch):
    got = share(counts, monkeypatch)
    assert got == (want if want is None else pytest.approx(want))


def test_the_benchmark_lists_it_for_the_served_cells_only():
    for name, listed in (('xcorr-replay', True), ('gpuspec-replay', True),
                         ('gpuspec-resident', False)):
        bench, cell, _cfg, _mod = harness.load_cell(name)
        found = [m for m in harness.metrics_of(bench, cell, 'per_layer')
                 if m['name'] == 'xfer.d2h_plane_share']
        assert bool(found) == listed
        for m in found:
            assert m['moves'] == 'sustained_msps'
            assert m['layer'] == 'H2D and D2H'
            assert m['source'] == 'program_counter'
