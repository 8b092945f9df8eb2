"""The guppi-fold configuration at its rehearsal size (a 32768-point
overlap-save, which takes the program's long path both ways, four
channels, three gulps a product): the program is correct, the control
is not, and each fault a folding backend can have, planted under the
timed path, ends not correct.

The faults: one gulp of a sub-integration left out; one repeated in
another's place; a
sub-integration one gulp short (the product made of the gulps before,
as a sub-integration of 16 is to one of 17).
"""

import numpy as np
import pytest

from test_xcorr import tamper
from util import rehearse

import progcounters
import run as harness

CELL = 'guppi-fold-replay'
SERVED = ['gpuspec-replay', 'xcorr-replay', 'gpuspec-hsr-replay',
          'beamform-tab-replay']
SHARES = ('ops.dedisp_fused_share', 'ops.dedisp_long_share')
#: the shares of the D2H bytes that crossed in pieces, which read
#: nothing where every product crosses whole
PIECE_SHARES = ('xfer.d2h_piece_share', 'xfer.d2h_pair_share',
                'xfer.d2h_plane_share', 'xfer.d2h_flat_share')


def loaded(seed):
    import traffic
    _, cell, cfg, mod = harness.load_cell(CELL)
    cfg = harness.merge(cfg, cfg['rehearse'])
    mix = traffic.load(cell['traffic'])
    return cfg, mod, mix, traffic.make_pool(cfg, mix, seed)


def test_program_is_correct():
    from bifrost_tpu.telemetry import counters
    counters.reset()
    res = rehearse(CELL, seed=3)
    assert res['correct'] is True, res['checks']
    assert res['checks']['rel_err']['value'] < 1e-6
    assert res['failed'] == 0 and res['attempted'] > 3
    assert set(res['metrics']) == {'sustained_msps',
                                   'host_cpu_s_per_gsample', 'setup_s'}
    impl = res['window']['impl']
    assert impl['impl'] == 'coherent-fold' and impl['input'] == 'words'
    assert impl['fft']['path'] == 'long' and impl['accumulate'] == 3
    counts = progcounters.counters()
    gulps = counts['dedisp.gulps']
    assert gulps == res['window']['offered'] > 0
    assert counts['dedisp.fused_gulps'] == counts['dedisp.long_gulps'] \
        == gulps
    # every new sample of every channel and polarisation, once
    assert counts['fold.samples'] == gulps * 4 * 2 * 2 * 28672
    # ops.long_transform_share reads the forward transform's path of
    # every chain with a transform; the dedispersion's share both
    for name in SHARES + ('ops.long_transform_share',):
        assert harness.reader('per_layer', name).read(None) == \
            pytest.approx(100.0)
    # every product crossed whole: the cell is on none of their lists
    assert 'xfer.d2h_piece_bytes' not in counts
    for name in PIECE_SHARES:
        assert harness.reader('per_layer', name).read(None) is None


@pytest.mark.parametrize('seed', [1, 2 ** 31 + 5])
def test_control_is_not_correct(seed):
    res = rehearse(CELL, seed=seed, control=True)
    assert res['control'] == 'reference'
    assert res['correct'] is False, res['checks']
    assert res['checks']['rel_err']['value'] >= \
        100 * res['checks']['rel_err']['limit']
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']


def test_reference_folds_each_gulp_at_its_own_phase():
    """The benchmark's reference against numpy's transforms of every
    gulp one by one (no memo), the bin rule written out, its pick, and
    its work count at the rehearsal size."""
    import traffic
    cfg, mod, mix, pool = loaded(5)
    gulps = [pool[i] for i in (2, 0, 2)]
    idx = np.array([4, 1, 3])              # product 4: frames 12, 13, 14
    got = mod.reference(gulps, idx, cfg)
    assert got.shape == (2, 4, 2048) and got.dtype == np.float64
    nfft, overlap, step = 32768, 4096, 28672
    want = 0.0
    for i, g in enumerate(gulps):
        prof = []
        for c in (1, 3):
            v = g[0, c]['re'].astype(np.float64) + 1j * g[0, c]['im']
            w = np.stack([v[:, k * step:k * step + nfft] for k in (0, 1)], 1)
            fc = 1100.78125 + 1.5625 * c
            f = np.fft.fftfreq(nfft, d=1 / 1.5625)
            h = np.exp(-2j * np.pi * 1e6 * 4.148808e3 * 297.5 * f ** 2
                       / (fc ** 2 * (fc + f)))
            z = np.fft.ifft(np.fft.fft(w, axis=-1) * h, axis=-1) * nfft
            # half the overlap dropped at each end of a window
            x, y = z[..., overlap // 2:overlap // 2 + step] \
                .reshape(2, 2 * step)
            xy = x * np.conj(y)
            s = [abs(x) ** 2 + abs(y) ** 2, abs(x) ** 2 - abs(y) ** 2,
                 2 * xy.real, -2 * xy.imag]
            # from the first new sample: a window's kept outputs start
            # half an overlap before it
            n = (12 + i) * 2 * step - overlap // 2 + \
                np.arange(2 * step, dtype=np.int64)
            b = (2048 * ((100 * n) % 335923)) // 335923
            prof.append([np.bincount(b, weights=q, minlength=2048)
                         for q in s])
        want = want + np.array(prof)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-9 * want.max())
    sampler = traffic.Sampler(cfg, mix, 5, mod.pick)
    assert list(sampler.where(0)) == [0, 0, 3]
    for k in range(1, 40):
        where = sampler.where(k)
        assert where[0] == k and list(where[1:] // 2) == [0, 1]
    work = mod.work(cfg)
    assert work['samples'] == 4 * 2 * 2 * step and work['int8_ops'] == 0
    assert work['bytes'] == 4 * 2 * 61440 * 2 + 4 * 4 * 2048 * 4 / 3
    assert work['flops'] == 2 * 5.0 * nfft * 15 * 16 + 6.0 * nfft * 16


def test_work_at_the_deployment_size():
    _, _cell, cfg, mod = harness.load_cell(CELL)
    work = mod.work(cfg)
    assert work['samples'] == 917504 * 128 == 117440512
    assert work['bytes'] == 236978176 + 2097152 / 17
    assert work['flops'] == 2 * 5.0 * 65536 * 16 * 2048 + 6.0 * 65536 * 2048
    assert 22.2e9 < work['flops'] < 22.4e9
    assert mod.gulps_per_product(cfg) == 17
    assert cfg['reduced'] == ['nchan'] and cfg['limits']['rel_err'] <= 1e-5
    assert mod.shapes(cfg) == (1, 64, 2, 925696, 65536, 8192, 16, 917504)
    assert mod.control_env(cfg) == {'BF_FFT_DFT_DTYPE': 'bf16'}


def test_the_product_crosses_whole_at_the_deployment_size():
    """A 2 MiB product is no larger than two D2H pieces, so the program
    counts no pieces in the cell and the piece shares read nothing."""
    import jax.numpy as jnp
    from bifrost_tpu import xfer
    _, _cell, cfg, _mod = harness.load_cell(CELL)
    product = jnp.zeros((1, cfg['nchan'], 4, cfg['fold']['nbin']),
                        jnp.float32)
    assert product.nbytes == 2097152
    assert xfer._piece_plan(product) is None


def test_one_gulp_of_a_subintegration_left_out():
    """The second gulp of every sub-integration reaches the chain as
    zeros: two profiles are summed where three were offered."""
    def second_gulp_zero(self, k, x):
        return x * 0 if k % 3 == 1 else x
    res = rehearse(CELL, wrap_chain=tamper('input', second_gulp_zero))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']


def test_one_gulp_repeated_in_anothers_place():
    """The chain is given every sub-integration's first gulp again
    where its second was due, wherever the two differ (where the pool
    gave the same gulp twice, the repeat is folded at the second's
    phase and is right)."""
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


def test_a_subintegration_one_gulp_short():
    """Every product is delivered without its last gulp's profile (the
    reference's own, at that gulp's phase): a sub-integration of two
    where three were asked for."""
    import traffic
    seed = 2
    cfg, mod, mix, pool = loaded(seed)
    order = traffic.replay_order(mix, seed)
    chans = np.arange(4)

    def short(self, k, x):
        g = 3 * k + 2
        last = _folded(mod, cfg, pool[order[g]], g, chans)
        return x - last[None].astype(np.float32)
    res = rehearse(CELL, seed=seed, wrap_chain=tamper('output', short))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']


def _folded(mod, cfg, gulp, frame, chans):
    """One gulp's folded Stokes (nchan, 4, nbin) at stream frame
    ``frame``, from the configuration's reference."""
    b = mod.bins(frame, cfg)
    out = np.zeros((len(chans), 4, cfg['fold']['nbin']))
    for n, c in enumerate(chans):
        s = mod._stokes(gulp[0], c, cfg, 'float64')
        for q in range(4):
            out[n, q] = np.bincount(b, weights=s[q],
                                    minlength=cfg['fold']['nbin'])
    return out


@pytest.mark.parametrize('counts,want', [
    ({'dedisp.gulps': 867, 'dedisp.fused_gulps': 867,
      'dedisp.long_gulps': 867}, (100.0, 100.0)),
    ({'dedisp.gulps': 800, 'dedisp.fused_gulps': 200,
      'dedisp.long_gulps': 600}, (25.0, 75.0)),
    ({'dedisp.gulps': 700, 'dedisp.fused_gulps': 0,
      'dedisp.long_gulps': 0}, (0.0, 0.0)),
    ({'dedisp.gulps': 0, 'dedisp.fused_gulps': 0,
      'dedisp.long_gulps': 0}, (None, None)),
    ({'spectrometer.gulps': 700}, (None, None)),
    ({}, (None, None)),
    (None, (None, None)),
], ids=['all', 'some', 'none', 'nothing_dedispersed', 'counters_absent',
        'no_counters', 'no_module'])
def test_the_two_readers_on_made_up_counters(counts, want, monkeypatch):
    """Nothing where the program does not count them: the parent."""
    monkeypatch.setattr(progcounters, 'counters', lambda: counts)
    for name, w in zip(SHARES, want):
        got = harness.reader('per_layer', name).read(None)
        assert got == (w if w is None else pytest.approx(w)), name


def test_the_benchmark_lists_the_cell_and_its_metrics():
    bench, cell, cfg, _mod = harness.load_cell(CELL)
    assert cell == {'name': CELL, 'config': 'guppi-fold',
                    'traffic': 'replay-host-warm2', 'chips': 1,
                    'why': cell['why']}
    assert len(cell['why']) <= 200
    assert {m['name'] for m in harness.metrics_of(bench, cell,
                                                  'end_to_end')} == \
        {'sustained_msps', 'host_cpu_s_per_gsample', 'setup_s'}
    mine = {m['name'] for m in harness.metrics_of(bench, cell, 'per_layer')}
    every = set.intersection(*[
        {m['name'] for m in harness.metrics_of(*harness.load_cell(c)[:2],
                                               'per_layer')}
        for c in SERVED])
    assert mine - every == set(SHARES) | {'ops.acc_in_place_share',
                                          'ops.long_transform_share'}
    # nothing staged since the gulp crosses from its ring span, and a
    # product that crosses whole counts no pieces
    assert every - mine == {'xfer.h2d_stage_share'} | set(PIECE_SHARES)
    listed = {m['name']: m for m in bench['per_layer']}
    for name in SHARES:
        m = listed[name]
        assert (m['unit'], m['source'], m['layer'], m['moves'],
                m['workloads']) == ('%', 'program_counter', 'kernels',
                                    'sustained_msps', [CELL])
    assert [m['name'] for m in bench['per_layer'][-2:]] == list(SHARES)
    entry = [c for c in bench['configs'] if c['name'] == 'guppi-fold'][0]
    assert entry['reduced'] == cfg['reduced'] == ['nchan']
    assert entry['source'] == cfg['source'] and len(entry['source']) <= 200
    assert bench['workloads'][-1]['name'] == CELL
