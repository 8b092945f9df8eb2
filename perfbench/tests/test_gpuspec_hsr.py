"""The gpuspec-hsr configuration at its rehearsal size (a 32768-point
transform, which takes the program's long path, four coarse channels,
three gulps a product): the program is correct, the control is not,
and each fault an integrating spectrometer can have, planted under
the timed path, ends not correct.

The faults: one gulp of an integration left out; one repeated in
another's place; an integration one gulp short (the product made of
the gulps before, as an integration of 50 is to one of 51).
"""

import numpy as np
import pytest

from test_xcorr import tamper
from util import rehearse

CELL = 'gpuspec-hsr-replay'


def loaded(seed):
    import run
    import traffic
    _, cell, cfg, mod = run.load_cell(CELL)
    cfg = run.merge(cfg, cfg['rehearse'])
    mix = traffic.load(cell['traffic'])
    return cfg, mod, mix, traffic.make_pool(cfg, mix, seed)


def test_program_is_correct():
    res = rehearse(CELL, seed=3)
    assert res['correct'] is True, res['checks']
    assert res['checks']['rel_err']['value'] < 2e-6
    assert res['failed'] == 0 and res['attempted'] > 3
    assert set(res['metrics']) == {'sustained_msps',
                                   'host_cpu_s_per_gsample', 'setup_s'}
    assert res['window']['impl']['fft']['path'] == 'long'
    assert res['window']['impl']['accumulate'] == 3


@pytest.mark.parametrize('seed', [1, 2, 2 ** 31 + 5])
def test_control_is_not_correct(seed):
    res = rehearse(CELL, seed=seed, control=True)
    assert res['control'] == 'reference'
    assert res['correct'] is False, res['checks']
    assert res['checks']['rel_err']['value'] >= \
        100 * res['checks']['rel_err']['limit']
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']


def test_reference_is_the_float64_sum_of_every_spectrum():
    """The benchmark's reference against numpy's transform of every
    gulp one by one (no memo), its pick, its control's transform
    against the exact one, and its work count."""
    import traffic
    cfg, mod, mix, pool = loaded(5)
    gulps = [pool[i] for i in (2, 0, 2)]
    idx = np.array([1, 3])
    got = mod.reference(gulps, idx, cfg)
    want = 0.0
    for g in gulps:
        v = g[0, idx]['re'].astype(np.float64) + 1j * g[0, idx]['im']
        s = np.fft.fft(v, axis=-1)
        x, y = s[:, 0], s[:, 1]
        xy = x * np.conj(y)
        want = want + np.stack([abs(x) ** 2 + abs(y) ** 2,
                                abs(x) ** 2 - abs(y) ** 2,
                                2 * xy.real, -2 * xy.imag], axis=1)
    assert got.shape == (2, 4, 32768) and got.dtype == np.float64
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-9 * want.max())
    # the control's levels, with nothing rounded, are the transform
    v = np.random.default_rng(0).standard_normal((2, 2, 1 << 15)) * (1 + 1j)
    assert mod._split(1 << 20) == [64, 128, 128]
    assert mod._split(1 << 15) == [2, 128, 128]
    exact = np.fft.fft(v, axis=-1)
    keep, mod._bf16 = mod._bf16, lambda x: x
    try:
        levels = mod._rounded_fft(v, mod._split(1 << 15))
    finally:
        mod._bf16 = keep
    assert np.max(np.abs(levels - exact)) < 1e-9 * np.max(np.abs(exact))
    rounded = mod._rounded_fft(v, mod._split(1 << 15))
    assert 1e-4 < np.max(np.abs(rounded - exact)) / np.max(np.abs(exact)) \
        < 1e-1
    sampler = traffic.Sampler(cfg, mix, 5, mod.pick)
    assert list(sampler.where(0)) == [0, 3]
    for k in range(1, 40):
        assert list(sampler.where(k) // 2) == [0, 1]
    assert any(sampler.where(k)[0] != 0 for k in range(1, 40))
    work = mod.work(cfg)
    assert work['samples'] == 4 * 2 * 32768 and work['int8_ops'] == 0
    assert work['bytes'] == 4 * 2 * 32768 * 2 + 4 * 4 * 32768 * 4 / 3
    assert work['flops'] == 5.0 * 32768 * 15 * 8


def test_work_at_the_deployment_size():
    import run
    _, _cell, cfg, mod = run.load_cell(CELL)
    work = mod.work(cfg)
    assert work['samples'] == 134217728
    assert work['bytes'] == 268435456 + 1073741824 / 51
    assert work['flops'] == 5.0 * 2 ** 20 * 20 * 128
    assert mod.gulps_per_product(cfg) == 51
    assert cfg['reduced'] == [] and cfg['limits']['rel_err'] <= 1e-5


def test_one_gulp_of_an_integration_left_out():
    """The second gulp of every integration reaches the chain as
    zeros: two spectra are summed where three were offered."""
    def second_gulp_zero(self, k, x):
        return x * 0 if k % 3 == 1 else x
    res = rehearse(CELL, wrap_chain=tamper('input', second_gulp_zero))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']


def test_one_gulp_repeated_in_anothers_place():
    """The chain is given every integration's first gulp again where
    its second was due, wherever the two differ."""
    import traffic
    seed = 1
    _cfg, _mod, mix, _pool = loaded(seed)
    order = traffic.replay_order(mix, seed)

    def repeated(self, k, x):
        if k % 3 == 0:
            self.last = x
        return self.last if k % 3 == 1 else x
    res = rehearse(CELL, seed=seed, wrap_chain=tamper('input', repeated))
    differ = sum(order[3 * p] != order[3 * p + 1]
                 for p in range(res['attempted']))
    assert differ >= 2
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_over_limit']['value'] == differ


def test_an_integration_one_gulp_short():
    """Every product is delivered without its last gulp's spectrum:
    an integration of two where three were asked for."""
    import jax.numpy as jnp
    from bifrost_tpu.devrep import to_device_rep
    seed = 2
    cfg, mod, mix, pool = loaded(seed)
    import traffic
    order = traffic.replay_order(mix, seed)

    def short(self, k, x):
        last = to_device_rep(pool[order[3 * k + 2]], cfg['input']['dtype'])
        v = last[..., 0].astype(jnp.float32) + 1j * last[..., 1]
        s = jnp.fft.fft(v, axis=-1)
        a, b = s[:, :, 0], s[:, :, 1]
        ab = a * jnp.conj(b)
        stokes = jnp.stack([jnp.abs(a) ** 2 + jnp.abs(b) ** 2,
                            jnp.abs(a) ** 2 - jnp.abs(b) ** 2,
                            2 * ab.real, -2 * ab.imag], axis=2)
        return x - stokes.astype(x.dtype)
    res = rehearse(CELL, seed=seed, wrap_chain=tamper('output', short))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']
