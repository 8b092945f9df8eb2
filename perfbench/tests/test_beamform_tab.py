"""The beamform-tab configuration at its rehearsal size (4 channels x
8 antennas x 2 pol x 16 beams, 1024 frames: two time tiles of the
fused kernel): the program is correct, the control is not, the
reference is a direct evaluation, and each fault a tied-array
beamformer can have, planted under the timed path, ends not correct.

The faults: a gulp left out (every later product is its successor's);
a gulp repeated (every later product is its predecessor's); the
weights rolled by one channel; two beams exchanged; the sum over 15
frames where 16 were asked for.
"""

from copy import deepcopy

import numpy as np
import pytest

import progcounters
import run as harness
from util import rehearse

CELL = 'beamform-tab-replay'


def tamper(where_wanted, fn):
    """wrap_chain that puts a device block applying ``fn(k, array)``
    to the k-th span at the chain's input or output."""
    from bifrost_tpu.pipeline import TransformBlock

    class Tamper(TransformBlock):
        def __init__(self, iring):
            super(Tamper, self).__init__(iring)
            self.k = 0

        def define_valid_input_spaces(self):
            return ('tpu',)

        def on_sequence(self, iseq):
            return deepcopy(iseq.header)

        def on_data(self, ispan, ospan):
            ospan.set(fn(self.k, ispan.data))
            self.k += 1

    def wrap(where, block):
        return Tamper(block) if where == where_wanted else block
    return wrap


def rehearsal_cell(seed=1):
    import traffic
    _, cell, cfg, mod = harness.load_cell(CELL)
    cfg = harness.merge(cfg, cfg['rehearse'])
    mix = traffic.load(cell['traffic'])
    return cfg, mod, mix, traffic.make_pool(cfg, mix, seed), \
        traffic.replay_order(mix, seed)


def test_program_is_correct():
    res = rehearse(CELL, seed=3)
    assert res['correct'] is True, res['checks']
    check = res['checks']['max_lsb_err']
    assert 0.4 < check['value'] <= check['limit'] < 0.51
    assert res['failed'] == 0 and res['attempted'] > 12
    assert set(res['metrics']) == {'sustained_msps',
                                   'host_cpu_s_per_gsample', 'setup_s'}
    impl = res['window']['impl']
    assert impl['impl'] == 'pallas-beamform-detect'
    assert impl['input'] == 'words' and impl['weights'] == 'per channel'
    assert impl['time_tile'] == 512       # two tiles a rehearsal gulp


@pytest.mark.parametrize('seed', [1, 2, 2 ** 31 + 5])
def test_control_is_not_correct(seed):
    """The reference on voltages cut to their four leading bits."""
    res = rehearse(CELL, seed=seed, control=True)
    assert res['control'] == 'reference'
    assert res['correct'] is False, res['checks']
    assert res['checks']['max_lsb_err']['value'] >= 1.0
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']


def test_reference_is_the_direct_evaluation():
    """The reference against numpy's own integer loops, unrounded, and
    the sampler's picks: every channel, two beams, 64 output times;
    the band's edges in product 0; the seed riding along."""
    import traffic
    cfg, mod, mix, pool, _ = rehearsal_cell(seed=5)
    nout = cfg['gulp_nframe'] // cfg['tscrunch']
    idx = (5, np.array([0, 7, nout - 1]), np.array([0, 9, 15]))
    got = mod.reference(pool[:1], idx, cfg)
    wr, wi = (w.astype(np.int64) for w in mod.weights(cfg, 5))
    assert wr.shape == (4, 2, 16, 8) and wr.dtype == np.int64
    assert np.all(np.abs(np.hypot(wr, wi) - 127) < 1)
    x = pool[0]
    xr, xi = x['re'].astype(np.int64), x['im'].astype(np.int64)
    br = np.einsum('tfsp,fpbs->tfpb', xr, wr) - \
        np.einsum('tfsp,fpbs->tfpb', xi, wi)
    bi = np.einsum('tfsp,fpbs->tfpb', xr, wi) + \
        np.einsum('tfsp,fpbs->tfpb', xi, wr)
    power = (br ** 2 + bi ** 2).sum(axis=2)               # (T, F, B)
    power = power.reshape(nout, 16, 4, 16).sum(axis=1)
    want = power[idx[1]][:, :, idx[2]] * mod.out_scale(cfg) / 127. ** 2
    assert got.shape == (3, 4, 3) and got.dtype == np.float64
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert 50 < got.mean() < 80 and got.max() < 255   # a quarter of u8
    assert np.any(np.abs(got - np.rint(got)) > 0.1)   # not rounded
    # another seed steers other beams
    assert not np.allclose(mod.reference(pool[:1], (6,) + idx[1:], cfg),
                           got)
    sampler = traffic.Sampler(cfg, mix, 5, mod.pick)
    seed, times, beams = sampler.where(0)
    assert seed == 5 and list(beams) == [0, 15]
    assert (times[0], times[-1], len(times)) == (0, nout - 1, 64)
    for k in range(1, 40):
        seed, times, beams = sampler.where(k)
        assert seed == 5 and len(times) == 64 and len(beams) == 2
        assert beams[0] < 8 <= beams[1] and np.all(np.diff(times) > 0)
    assert any(sampler.where(k)[2][0] != 0 for k in range(1, 40))
    product = np.arange(nout * 4 * 16, dtype=np.uint8) \
        .reshape(nout, 4, 1, 16)
    assert mod.take(product, idx).shape == (3, 4, 3)
    assert mod.compare(got, got) == ('max_lsb_err', 0.0)
    assert mod.compare(got[:2], got)[1] == float('inf')


@pytest.mark.parametrize('rehearsal', [False, True],
                         ids=['published', 'rehearsal'])
def test_work_counts_from_the_shapes(rehearsal):
    _, _, cfg, mod = harness.load_cell(CELL)
    if rehearsal:
        cfg = harness.merge(cfg, cfg['rehearse'])
        samples, out, weights, beams = 1024 * 4 * 8 * 2, 64 * 4 * 16, \
            4 * 2 * 16 * 8 * 2, 16
    else:
        samples, out, weights, beams = 134217728, 56623104, 14155776, 864
        assert cfg['reduced'] == ['nchan'] and cfg['nchan'] == 64
        assert cfg['published']['nchan'] == 4096
        assert cfg['published']['workers'] == 64
        assert cfg['published']['share_realtime_msps'] == pytest.approx(
            64 * 208984.375 * 128 / 1e6, rel=1e-4)
    work = mod.work(cfg)
    assert work['samples'] == samples and work['flops'] == 0
    assert work['bytes'] == 2 * samples + out + weights
    assert work['int8_ops'] == 8 * beams * samples
    assert mod.gulps_per_product(cfg) == 1 and mod.control_env(cfg) == {}
    assert cfg['nchan'] == cfg['input']['frame_shape'][0]


def test_a_gulp_left_out():
    """From the sixth gulp on the chain is fed the gulp after: every
    product arrives, each one its successor's."""
    from bifrost_tpu.devrep import to_device_rep
    cfg, mod, mix, pool, order = rehearsal_cell(seed=1)

    def skipped(k, x):
        return x if k < 5 else \
            to_device_rep(pool[order[k + 1]], cfg['input']['dtype'])
    res = rehearse(CELL, seed=1, wrap_chain=tamper('input', skipped))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] >= \
        (res['attempted'] - 5) // 2


def test_a_gulp_repeated():
    """From the sixth gulp on the chain is fed the gulp before."""
    from bifrost_tpu.devrep import to_device_rep
    cfg, mod, mix, pool, order = rehearsal_cell(seed=1)

    def repeated(k, x):
        return x if k < 5 else \
            to_device_rep(pool[order[k - 1]], cfg['input']['dtype'])
    res = rehearse(CELL, seed=1, wrap_chain=tamper('input', repeated))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] >= \
        (res['attempted'] - 5) // 2


def test_weights_rolled_by_one_channel(monkeypatch):
    """Every channel is steered with its neighbour's phases."""
    load = harness.load_cell

    def load_cell(name):
        bench, cell, cfg, mod = load(name)
        chain, weights = mod.chain, mod.weights

        def rolled_chain(bf, upstream, cfg):
            seed = mod._run_seed()
            mod.weights = lambda c, s: tuple(
                np.roll(w, 1, axis=0) for w in weights(c, s))
            try:
                return chain(bf, upstream, cfg, seed=seed)
            finally:
                mod.weights = weights
        mod.chain = rolled_chain
        return bench, cell, cfg, mod
    monkeypatch.setattr(harness, 'load_cell', load_cell)
    res = rehearse(CELL, seed=2)
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']


def test_two_beams_exchanged():
    """Beams 3 and 12 leave in each other's place: the products that
    compare either are over the limit, the others not."""
    def exchanged(k, x):
        return x.at[..., 3].set(x[..., 12]).at[..., 12].set(x[..., 3])
    res = rehearse(CELL, seed=4, wrap_chain=tamper('output', exchanged))
    assert res['correct'] is False, res['checks']
    over = res['checks']['products_over_limit']['value']
    assert 1 <= over < res['attempted']


def test_sum_over_15_frames_for_16():
    """The last frame of every sixteen reaches the chain as zeros."""
    def fifteen(k, x):
        return x.at[15::16].set(0)
    res = rehearse(CELL, seed=3, wrap_chain=tamper('input', fifteen))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']


@pytest.mark.parametrize('counts,fused,words', [
    ({'beamform.gulps': 700, 'beamform.fused_gulps': 700,
      'beamform.word_gulps': 700, 'beamform.int8_ops': 7 << 40},
     100.0, 100.0),
    ({'beamform.gulps': 800, 'beamform.fused_gulps': 200,
      'beamform.word_gulps': 0}, 25.0, 0.0),
    ({'beamform.gulps': 800}, None, None),
    ({'beamform.gulps': 0, 'beamform.fused_gulps': 0,
      'beamform.word_gulps': 0}, None, None),
    ({}, None, None),
    (None, None, None),
], ids=['all', 'a_quarter_and_none', 'counters_absent', 'no_gulps',
        'no_counters', 'no_module'])
def test_the_two_readers_on_made_up_counters(counts, fused, words,
                                             monkeypatch):
    monkeypatch.setattr(progcounters, 'counters', lambda: counts)
    for name, want in (('ops.beam_fused_share', fused),
                       ('ops.beam_word_share', words)):
        got = harness.reader('per_layer', name).read(None)
        assert got == (want if want is None else pytest.approx(want))


def test_the_benchmark_lists_the_cell_and_its_metrics():
    bench, cell, cfg, _ = harness.load_cell(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == \
        ('beamform-tab', 'replay-host', 1)
    entry = {c['name']: c for c in bench['configs']}['beamform-tab']
    assert entry['reduced'] == cfg['reduced'] == ['nchan']
    assert entry['source'] == cfg['source'] and len(entry['source']) <= 200
    e2e = {m['name'] for m in harness.metrics_of(bench, cell,
                                                 'end_to_end')}
    assert e2e == {'sustained_msps', 'host_cpu_s_per_gsample', 'setup_s'}
    listed = {m['name']: m
              for m in harness.metrics_of(bench, cell, 'per_layer')}
    for name in ('ops.beam_fused_share', 'ops.beam_word_share'):
        m = listed[name]
        assert m['workloads'] == [CELL] and m['layer'] == 'kernels'
        assert (m['moves'], m['source'], m['unit'], m['better']) == \
            ('sustained_msps', 'program_counter', '%', 'higher')
    # behind what was there (never "last": the next PR appends too;
    # test_word_shares.py pins PR 34's two as the last and fails since)
    names = [m['name'] for m in bench['per_layer']]
    assert names.index('ops.beam_fused_share') > \
        names.index('ops.word_gulp_share.resident')
    for name in ('ops.chain_roofline.replay', 'xfer.h2d_word_share',
                 'device.peak_hbm_gb.replay', 'sink.exit_age_p90_s',
                 'dispatch.programs_per_gulp.replay'):
        assert CELL in listed[name]['workloads'][1:]
    for name in ('ops.long_transform_share', 'ops.acc_in_place_share',
                 'ops.word_gulp_share.resident'):
        assert name not in listed


def test_a_rehearsed_run_counts_what_the_readers_read():
    from bifrost_tpu.telemetry import counters
    counters.reset()              # process-wide: other tests' gulps
    res = rehearse(CELL)
    assert res['correct'] is True, res['checks']
    counts = progcounters.counters()
    assert counts['beamform.gulps'] == counts['beamform.fused_gulps'] \
        == counts['beamform.word_gulps'] > 12
    assert counts['beamform.int8_ops'] == \
        counts['beamform.gulps'] * 8 * 16 * 1024 * 4 * 8 * 2
    assert counts['xfer.h2d_word_bytes'] == counts['xfer.h2d_bytes'] > 0
    for name in ('ops.beam_fused_share', 'ops.beam_word_share'):
        assert harness.reader('per_layer', name).read(None) == \
            pytest.approx(100.0)
