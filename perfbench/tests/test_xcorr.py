"""The xcorr configuration at its rehearsal size: the program is
correct, the control is not, and each fault an integrating correlator
can have, planted under the timed path, ends not correct.

The faults: one gulp of an integration's four left out; the
accumulator carried over into the next product; two products delivered
in each other's place; the matrix's upper triangle conjugated wrongly
(v[i, j] for i < j left as v[j, i], not its conjugate).
"""

from copy import deepcopy

import numpy as np
import pytest

from util import rehearse

CELL = 'xcorr-replay'


def tamper(where_wanted, fn):
    """wrap_chain that puts a device block applying ``fn(k, array)``
    to the k-th span at the chain's input or output."""
    from bifrost_tpu.pipeline import TransformBlock

    class Tamper(TransformBlock):
        def __init__(self, iring):
            super(Tamper, self).__init__(iring)
            self.k = 0
            self.last = None

        def define_valid_input_spaces(self):
            return ('tpu',)

        def on_sequence(self, iseq):
            return deepcopy(iseq.header)

        def on_data(self, ispan, ospan):
            ospan.set(fn(self, self.k, ispan.data))
            self.k += 1

    def wrap(where, block):
        return Tamper(block) if where == where_wanted else block
    return wrap


def test_program_is_correct():
    res = rehearse(CELL, seed=3)
    assert res['correct'] is True, res['checks']
    assert res['checks']['max_abs_err'] == {'value': 0.0, 'limit': 0}
    assert res['failed'] == 0 and res['attempted'] > 4
    assert set(res['metrics']) == {'sustained_msps',
                                   'host_cpu_s_per_gsample', 'setup_s'}


@pytest.mark.parametrize('seed', [1, 2, 2 ** 31 + 5])
def test_control_is_not_correct(seed):
    res = rehearse(CELL, seed=seed, control=True)
    assert res['control'] == 'reference'
    assert res['correct'] is False, res['checks']
    assert res['checks']['max_abs_err']['value'] >= 1.0


def test_reference_is_the_int64_sum_of_every_frame():
    """The benchmark's reference against numpy's own int64 loops, and
    its pick: four channels, one a quarter, the band's edges in
    product 0."""
    import run
    import traffic
    _, cell, cfg, mod = run.load_cell(CELL)
    cfg = run.merge(cfg, cfg['rehearse'])
    mix = traffic.load(cell['traffic'])
    pool = traffic.make_pool(cfg, mix, 5)
    idx = np.array([0, 3, 4, 7])
    got = mod.reference(pool, idx, cfg)
    x = np.concatenate(pool)[:, idx]
    r = x['re'].astype(np.int64).reshape(x.shape[0], len(idx), -1)
    i = x['im'].astype(np.int64).reshape(x.shape[0], len(idx), -1)
    re = np.einsum('tfa,tfb->fab', r, r) + np.einsum('tfa,tfb->fab', i, i)
    im = np.einsum('tfa,tfb->fab', i, r) - np.einsum('tfa,tfb->fab', r, i)
    assert got.dtype == np.complex64 and got.shape == (4, 4, 2, 4, 2)
    assert np.array_equal(got.reshape(4, 8, 8), re + 1j * im)
    assert np.array_equal(got.reshape(4, 8, 8),
                          np.conj(got.reshape(4, 8, 8)
                                  .transpose(0, 2, 1)))
    sampler = traffic.Sampler(cfg, mix, 5, mod.pick)
    assert list(sampler.where(0)[[0, -1]]) == [0, 7]
    for k in range(1, 40):
        assert list(sampler.where(k) // 2) == [0, 1, 2, 3]
    assert any(sampler.where(k)[0] != 0 for k in range(1, 40))
    work = mod.work(cfg)
    assert work['samples'] == 8 * 8 * 8 and work['flops'] == 0
    assert work['int8_ops'] == 8 * (8 * 8) * (8 * 9 // 2)
    assert work['bytes'] == 8 * 8 * 8 * 2 + 8 * 8 * 8 * 8 // 4


def test_one_gulp_of_four_left_out():
    """The third gulp of every integration reaches the chain as
    zeros: three gulps are integrated where four were offered."""
    def third_gulp_zero(self, k, x):
        return x * 0 if k % 4 == 2 else x
    res = rehearse(CELL, wrap_chain=tamper('input', third_gulp_zero))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0


def test_accumulator_carried_over():
    """Every product holds the one before it as well: the accumulator
    was never started afresh."""
    def carried(self, k, x):
        self.last = x if self.last is None else self.last + x
        return self.last
    res = rehearse(CELL, wrap_chain=tamper('output', carried))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_over_limit']['value'] >= \
        res['attempted'] - 1


def test_products_delivered_in_exchanged_order():
    """The gulps of two neighbouring products that differ change
    places on their way into the chain: every product arrives, two of
    them in each other's place."""
    import run
    import traffic
    from bifrost_tpu.devrep import to_device_rep
    seed = 1
    _, cell, cfg, mod = run.load_cell(CELL)
    cfg = run.merge(cfg, cfg['rehearse'])
    mix = traffic.load(cell['traffic'])
    pool = traffic.make_pool(cfg, mix, seed)
    order = traffic.replay_order(mix, seed)
    gpp = mod.gulps_per_product(cfg)

    def gulps_of(p):
        return sorted(order[p * gpp:(p + 1) * gpp])
    first = next(p for p in range(2, 16) if gulps_of(p) != gulps_of(p + 1))

    def exchange(self, k, x):
        if k // gpp == first:
            k += gpp
        elif k // gpp == first + 1:
            k -= gpp
        else:
            return x
        return to_device_rep(pool[order[k]], cfg['input']['dtype'])
    res = rehearse(CELL, seed=seed, wrap_chain=tamper('input', exchange))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] == 2


def test_upper_triangle_conjugated_wrongly():
    """v[i, j] above the diagonal is left as v[j, i] where it should
    be its conjugate: the real parts all agree, the imaginary parts of
    one triangle have the wrong sign."""
    def mirrored(self, k, x):
        import jax.numpy as jnp
        f = x.shape[1]
        n = x.shape[2] * x.shape[3]
        m = x.reshape(f, n, n)
        lower = jnp.tril(m)
        strict = jnp.tril(m, -1)
        return (lower + jnp.swapaxes(strict, 1, 2)).reshape(x.shape)
    res = rehearse(CELL, wrap_chain=tamper('output', mirrored))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']
