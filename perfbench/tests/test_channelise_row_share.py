"""``ops.channelise_row_share`` over the program's counters: the gulps
of a chain that opens with a short transform that ran the row
channeliser (stages.match_channeliser), listed in the one cell whose
front is such a chain; nothing where the program does not count them
(a parent from before it did) or ran no such chain."""

import pytest

from util import rehearse

import progcounters
import run as harness

NAME = 'ops.channelise_row_share'
CELL = 'bl-frb-search-replay'


@pytest.mark.parametrize('counts,want', [
    ({'channelise.gulps': 867, 'channelise.row_gulps': 867}, 100.0),
    ({'channelise.gulps': 800, 'channelise.row_gulps': 200}, 25.0),
    ({'channelise.gulps': 700, 'channelise.row_gulps': 0}, 0.0),
    ({'channelise.gulps': 700, 'fdmt.gulps': 700}, None),
    ({'channelise.gulps': 0, 'channelise.row_gulps': 0}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'none_counted_as_0', 'counter_absent',
        'nothing_channelised', 'no_counters', 'no_module'])
def test_share_of_the_gulps_on_the_rows(counts, want, monkeypatch):
    monkeypatch.setattr(progcounters, 'counters', lambda: counts)
    got = harness.reader('per_layer', NAME).read(None)
    assert got == (want if want is None else pytest.approx(want))


def test_the_benchmark_lists_it_in_the_frb_cell_alone():
    bench = harness.load_cell(CELL)[0]
    for cell in [c['name'] for c in bench['workloads']]:
        bench, c, _cfg, _mod = harness.load_cell(cell)
        listed = {m['name']: m
                  for m in harness.metrics_of(bench, c, 'per_layer')}
        assert (NAME in listed) == (cell == CELL), cell
    m = next(m for m in bench['per_layer'] if m['name'] == NAME)
    assert (m['unit'], m['better'], m['source'], m['layer'], m['moves'],
            m['workloads']) == ('%', 'higher', 'program_counter',
                                'kernels', 'sustained_msps', [CELL])


def test_every_gulp_of_the_rehearsal_runs_on_the_rows():
    """The cell at its rehearsal size (frames of 4 x 2 x 8 words: a row
    is a frame of 64): every gulp counted and channelised on the rows,
    the share 100, `correct`; the 8-point transform is counted among
    the spectrometer's gulps, not the long path's."""
    from bifrost_tpu.telemetry import counters
    counters.reset()
    res = rehearse(CELL, seed=2 ** 31 + 11)
    assert res['correct'] is True, res['checks']
    counts = progcounters.counters()
    assert counts['channelise.row_gulps'] == counts['channelise.gulps'] \
        == counts['fdmt.gulps'] > 0
    assert counts['spectrometer.gulps'] == counts['channelise.gulps']
    assert harness.reader('per_layer', NAME).read(None) == \
        pytest.approx(100.0)
    assert harness.reader('per_layer', 'ops.long_transform_share') \
        .read(None) == 0.0
