"""``ops.long_transform_share`` and ``ops.acc_in_place_share`` over the
program's counters; nothing where the program does not count them (a
parent from before it did) or did none of the work they count."""

import pytest

import progcounters
import run as harness


def share(name, counts, monkeypatch):
    monkeypatch.setattr(progcounters, 'counters', lambda: counts)
    return harness.reader('per_layer', name).read(None)


@pytest.mark.parametrize('counts,want', [
    ({'spectrometer.gulps': 867, 'spectrometer.long_gulps': 867}, 100.0),
    ({'spectrometer.gulps': 800, 'spectrometer.long_gulps': 200}, 25.0),
    ({'spectrometer.gulps': 700}, 0.0),
    ({'spectrometer.gulps': 0, 'spectrometer.long_gulps': 0}, None),
    ({'xfer.d2h_bytes': 4 << 30}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'none_long_counts_as_0', 'nothing_transformed',
        'counters_absent', 'no_counters', 'no_module'])
def test_share_of_gulps_through_the_long_transform(counts, want, monkeypatch):
    got = share('ops.long_transform_share', counts, monkeypatch)
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize('counts,want', [
    ({'accumulate.gulps': 867, 'accumulate.integrations': 17,
      'accumulate.acc_in_place': 850}, 100.0),
    ({'accumulate.gulps': 102, 'accumulate.integrations': 2,
      'accumulate.acc_in_place': 25}, 25.0),
    ({'accumulate.gulps': 102, 'accumulate.integrations': 2,
      'accumulate.acc_in_place': 0}, 0.0),
    ({'accumulate.gulps': 102, 'accumulate.integrations': 2}, None),
    ({'accumulate.gulps': 0, 'accumulate.acc_in_place': 0}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'fresh_sums', 'counter_absent',
        'nothing_integrated', 'no_counters', 'no_module'])
def test_share_of_gulps_added_in_place(counts, want, monkeypatch):
    got = share('ops.acc_in_place_share', counts, monkeypatch)
    assert got == (want if want is None else pytest.approx(want))


def test_the_benchmark_lists_them_where_there_is_something_to_read():
    want = {'ops.long_transform_share': {'gpuspec-hsr-replay',
                                         'gpuspec-replay'},
            'ops.acc_in_place_share': {'gpuspec-hsr-replay'}}
    for cell in ('gpuspec-hsr-replay', 'gpuspec-replay',
                 'gpuspec-resident', 'xcorr-replay'):
        bench, c, _cfg, _mod = harness.load_cell(cell)
        listed = {m['name']: m
                  for m in harness.metrics_of(bench, c, 'per_layer')}
        for name, cells in want.items():
            assert (name in listed) == (cell in cells), (name, cell)
            if name in listed:
                m = listed[name]
                assert m['moves'] == 'sustained_msps'
                assert m['layer'] == 'kernels'
                assert m['source'] == 'program_counter'


def test_the_new_cell_reports_what_the_served_cells_report():
    bench, cell, cfg, _mod = harness.load_cell('gpuspec-hsr-replay')
    assert cell == {'name': 'gpuspec-hsr-replay', 'config': 'gpuspec-hsr',
                    'traffic': 'replay-host-warm2', 'chips': 1,
                    'why': cell['why']}
    assert {m['name'] for m in harness.metrics_of(bench, cell, 'end_to_end')} \
        == {'sustained_msps', 'host_cpu_s_per_gsample', 'setup_s'}
    _b, other, _c, _m = harness.load_cell('xcorr-replay')
    mine = {m['name'] for m in harness.metrics_of(bench, cell, 'per_layer')}
    theirs = {m['name'] for m in harness.metrics_of(bench, other, 'per_layer')}
    assert mine - theirs == {'ops.long_transform_share',
                             'ops.acc_in_place_share'}
    assert theirs - mine == set()
    entry = [c for c in bench['configs'] if c['name'] == 'gpuspec-hsr'][0]
    assert entry['reduced'] == [] and cfg['reduced'] == []
    assert entry['source'] == cfg['source'] and len(entry['source']) <= 200
