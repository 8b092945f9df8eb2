"""``ops.fold_kernel_share`` over the program's counters: the gulps of
a chain that dedisperses and folds whose plan cut the rotations with
the kernel that reads the Stokes series once (ops.fold.cut_path),
listed in the one cell that folds; nothing where the program does not
count them (a parent from before it did) or folded nothing."""

import pytest

from util import rehearse

import progcounters
import run as harness

NAME = 'ops.fold_kernel_share'
CELL = 'guppi-fold-replay'


@pytest.mark.parametrize('counts,want', [
    ({'fold.gulps': 544, 'fold.kernel_gulps': 544}, 100.0),
    ({'fold.gulps': 800, 'fold.kernel_gulps': 200}, 25.0),
    ({'fold.gulps': 700, 'fold.kernel_gulps': 0}, 0.0),
    ({'dedisp.gulps': 544, 'fold.samples': 10 ** 9}, None),
    ({'fold.gulps': 0, 'fold.kernel_gulps': 0}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'none_counted_as_0', 'counter_absent',
        'nothing_folded', 'no_counters', 'no_module'])
def test_share_of_the_gulps_cut_by_the_kernel(counts, want, monkeypatch):
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
    m = next(m for m in bench['per_layer'] if m['name'] == NAME)
    assert (m['unit'], m['better'], m['source'], m['layer'], m['moves'],
            m['workloads']) == ('%', 'higher', 'program_counter',
                                'kernels', 'sustained_msps', [CELL])


def test_every_gulp_of_the_rehearsal_is_counted():
    """The cell at its rehearsal size: every gulp counted among the
    fold's, `correct`; off the chip the plan cuts with XLA, so the
    share is 0 and the record says so."""
    from bifrost_tpu.telemetry import counters
    counters.reset()
    res = rehearse(CELL, seed=2 ** 31 + 13)
    assert res['correct'] is True, res['checks']
    assert res['window']['impl']['fold'] == 'xla'
    counts = progcounters.counters()
    assert counts['fold.gulps'] == counts['dedisp.gulps'] > 0
    assert counts['fold.kernel_gulps'] == 0
    assert harness.reader('per_layer', NAME).read(None) == 0.0
