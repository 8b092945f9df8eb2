"""The control of each configuration comes out NOT correct, and the
program as it is comes out correct, at a size a test run can hold.

The control is the plain reference put in the program's place in the
nearest precision below the configuration's: bfloat16 for gpuspec's
float32.  (On the chip gpuspec's control is the program's own bf16
path; see PERF.md for those readings.)
"""

import pytest

from util import rehearse

CELLS = ['gpuspec-replay', 'gpuspec-resident']


@pytest.mark.parametrize('workload', CELLS)
def test_program_is_correct(workload):
    res = rehearse(workload, seed=3)
    assert res['correct'] is True, res['checks']
    assert res['failed'] == 0 and res['attempted'] > 0


@pytest.mark.parametrize('workload', CELLS)
@pytest.mark.parametrize('seed', [1, 2, 2 ** 31 + 5])
def test_control_is_not_correct(workload, seed):
    res = rehearse(workload, seed=seed, control=True)
    assert res['control'] == 'reference'
    assert res['correct'] is False, res['checks']
    name, check = next(iter(res['checks'].items()))
    assert check['value'] > 3 * max(check['limit'], 1e-12), (name, check)
