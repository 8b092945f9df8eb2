"""``run.py --selfcheck``: the yardstick checked against itself.

- the trace reduction on a small recorded trace (``fixtures/``: a few
  gulps cut from a traced run on the chip, with the numbers counted by
  hand beside it);
- the interval arithmetic, and the leaving out of the bench's own
  device programs, on cases small enough to do in the head;
- the work counts on hand-counted shapes, and at the cells' own;
- the peak table: which peak bounds, and that an unknown device is an
  error.

Touches no device.  Exit code 0 and ``selfcheck: ok`` when all hold.
"""

import json
import math
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-300)


def check_intervals(t):
    iv = np.array([[0., 4.], [2., 6.], [10., 12.]])
    u = t.union(iv)
    assert u.tolist() == [[0., 6.], [10., 12.]], u
    assert t.covered(u) == 8.0
    g = t.gaps(u, 0., 20.)
    assert g.tolist() == [[6., 10.], [12., 20.]], g
    spans = {'a': np.array([[5., 9.]]), 'b': np.array([[9., 15.]])}
    by = t.attribute(g, spans)
    # gap [6,10): a covers 3, b covers 1; gap [12,20): b covers 3;
    # nothing covers [15,20)
    assert close(by['a'], 3e-9) and close(by['b'], 4e-9) and \
        close(by['unattributed'], 5e-9), by
    by = t.attribute(g, {'a': np.zeros((0, 2))})
    assert list(by) == ['unattributed'] and \
        close(by['unattributed'], 12e-9), by


def check_own_programs_left_out(t):
    """The bench's anchor and its sampling program, with the
    operations inside them, are neither programs nor busy time."""
    trace = {'/device:TPU:0': {
        t.MODULES_LINE: [['jit_bench_anchor(1)', 0., 10.],
                         ['jit_chain(2)', 100., 50.],
                         ['jit_bench_take(3)', 160., 20.],
                         ['jit_chain(2)', 200., 50.]],
        t.OPS_LINE: [['add', 1., 8.], ['fft', 100., 30.], ['sum', 130., 20.],
                     ['gather', 161., 18.], ['fft', 200., 30.],
                     ['sum', 230., 20.]]}}
    got = t.reduce(trace, (50., 300.), {})
    assert got['programs'] == 2, got
    assert close(got['busy_s'], 100e-9), got
    assert [n for n, _ in got['device_ops']] == ['fft', 'sum'], got
    assert close(dict(got['idle_gaps'])['unattributed'], 150e-9), got


def check_recorded_trace(t):
    """A cut of a traced run on the chip (fixtures/README.md says from
    where, and how the expected numbers were counted)."""
    with open(os.path.join(HERE, 'fixtures', 'small_trace.json')) as f:
        fx = json.load(f)
    want = fx['expected']
    to_ns, residual = t.clock(fx['trace'], fx['anchor_stamps'])
    assert abs(residual) < 1e-3, residual
    spans = {n: to_ns(np.array(iv)) for n, iv in fx['spans'].items()}
    window = (float(to_ns(fx['t_open'])), float(to_ns(fx['t_close'])))
    got = t.reduce(fx['trace'], window, spans)
    assert got is not None, 'no device operation in the fixture\'s window'
    for key in ('window_s', 'busy_s', 'programs'):
        assert close(got[key], want[key], 1e-6), (key, got[key], want[key])
    assert [n for n, _ in got['device_ops']][:3] == want['top_ops'], \
        got['device_ops']
    assert close(dict(got['device_ops'])[want['top_ops'][0]],
                 want['top_op_s'], 1e-6)
    assert set(dict(got['idle_gaps'])) == set(want['idle_names']), \
        got['idle_gaps']
    assert close(dict(got['idle_gaps'])['unattributed'],
                 want['unattributed_s'], 1e-6)


def check_work(load_module):
    g = load_module(os.path.join(HERE, 'configs', 'gpuspec.py'), 'g')
    # 2 frames x 2 pol x 8 fine, reduce 4: in 2*2*8*2 B = 64, out
    # 2*4*2*4 B = 64; 4 transforms of 5*8*3 = 120 flops
    small = {'gulp_nframe': 2, 'rfactor': 4,
             'input': {'frame_shape': [2, 8]}}
    assert g.work(small) == {'samples': 32, 'bytes': 128,
                             'flops': 480.0, 'int8_ops': 0.0}
    with open(os.path.join(HERE, 'configs', 'gpuspec.json')) as f:
        w = g.work(json.load(f))
    assert w['samples'] == 16384 * 2 * 4096 == 134217728
    assert w['bytes'] == 2 * 268435456
    assert w['flops'] == 5 * 4096 * 12 * 32768


def check_peaks():
    import peaks
    v5e = peaks.for_device('TPU v5 lite')
    assert (v5e['bf16_flops_per_s'], v5e['int8_ops_per_s'],
            v5e['hbm_bytes_per_s']) == (197e12, 393e12, 819e9)
    s, bound = peaks.least_seconds(
        {'bytes': 819e9, 'flops': 197e12 / 2, 'int8_ops': 0.0}, v5e)
    assert bound == 'bytes' and close(s, 1.0)
    s, bound = peaks.least_seconds(
        {'bytes': 819e9, 'flops': 0.0, 'int8_ops': 2 * 393e12}, v5e)
    assert bound == 'int8_ops' and close(s, 2.0)
    try:
        peaks.for_device('TPU v99')
    except KeyError:
        pass
    else:
        raise AssertionError('an unknown device got peaks')


def check_references(load_module):
    """The reference against sums written out term by term."""
    g = load_module(os.path.join(HERE, 'configs', 'gpuspec.py'), 'g')
    ci8 = np.dtype([('re', np.int8), ('im', np.int8)])
    rng = np.random.default_rng(0)
    cfg = {'gulp_nframe': 3, 'rfactor': 2,
           'input': {'frame_shape': [2, 4]}}
    gulp = rng.integers(-64, 64, (3, 2, 4, 2), dtype=np.int8) \
        .view(ci8).reshape(3, 2, 4)
    got = g.reference([gulp], np.array([1]), cfg)[0]
    v = gulp[1]['re'].astype(float) + 1j * gulp[1]['im']
    spec = np.array([[sum(v[p, n] * np.exp(-2j * math.pi * k * n / 4)
                          for n in range(4)) for k in range(4)]
                     for p in range(2)])
    i = abs(spec[0]) ** 2 + abs(spec[1]) ** 2
    assert np.allclose(got[0], [i[0] + i[1], i[2] + i[3]])
    v_ = 2 * (spec[0] * spec[1].conj()).imag
    assert np.allclose(got[3], [-(v_[0] + v_[1]), -(v_[2] + v_[3])])


def main():
    sys.path.insert(0, HERE)
    import tracered
    from run import load_module
    check_intervals(tracered)
    check_own_programs_left_out(tracered)
    check_recorded_trace(tracered)
    check_work(load_module)
    check_peaks()
    check_references(load_module)
    print('selfcheck: ok')
    return 0
