"""``ops.dedisp_level_order_share`` over the program's counters: the
gulps whose two transforms passed the spectrum in the levels' order
(ops.fft.long_fft, ``natural`` off both ways), listed in the one cell
that dedisperses; nothing where the program does not count them (a
parent from before it did) or dedispersed nothing."""

import pytest

from util import rehearse

import progcounters
import run as harness

NAME = 'ops.dedisp_level_order_share'
CELL = 'guppi-fold-replay'


@pytest.mark.parametrize('counts,want', [
    ({'dedisp.gulps': 867, 'dedisp.level_order_gulps': 867}, 100.0),
    ({'dedisp.gulps': 800, 'dedisp.level_order_gulps': 200}, 25.0),
    ({'dedisp.gulps': 700, 'dedisp.level_order_gulps': 0}, 0.0),
    ({'dedisp.gulps': 867, 'dedisp.fused_gulps': 867,
      'dedisp.long_gulps': 867}, None),
    ({'dedisp.gulps': 0, 'dedisp.level_order_gulps': 0}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'none_counted_as_0', 'counter_absent',
        'nothing_dedispersed', 'no_counters', 'no_module'])
def test_share_of_the_gulps_in_level_order(counts, want, monkeypatch):
    monkeypatch.setattr(progcounters, 'counters', lambda: counts)
    got = harness.reader('per_layer', NAME).read(None)
    assert got == (want if want is None else pytest.approx(want))


def test_the_benchmark_lists_it_in_the_fold_cell_alone():
    bench = harness.load_cell(CELL)[0]
    for cell in [c['name'] for c in bench['workloads']]:
        bench, c, _cfg, _mod = harness.load_cell(cell)
        listed = {m['name']: m
                  for m in harness.metrics_of(bench, c, 'per_layer')}
        assert (NAME in listed) == (cell == CELL), cell
    m = bench['per_layer'][-1]
    assert (m['name'], m['unit'], m['better'], m['source'], m['layer'],
            m['moves'], m['workloads']) == (NAME, '%', 'higher',
                                            'program_counter', 'kernels',
                                            'sustained_msps', [CELL])


def test_every_gulp_of_the_rehearsal_is_in_level_order():
    """The cell at its rehearsal size (a 32768-point overlap-save, the
    long path both ways): every gulp counted, the share 100, the path
    record saying so in both directions."""
    from bifrost_tpu.telemetry import counters
    counters.reset()
    res = rehearse(CELL, seed=2 ** 31 + 7)
    assert res['correct'] is True, res['checks']
    fft = res['window']['impl']['fft']
    assert fft['order'] == fft['inverse']['order'] == 'levels'
    counts = progcounters.counters()
    assert counts['dedisp.level_order_gulps'] == counts['dedisp.gulps'] > 0
    assert harness.reader('per_layer', NAME).read(None) == \
        pytest.approx(100.0)
