"""The bl-frb-search configuration at its rehearsal size (4 coarse
channels of 8 fine, 16 spectra summed, 64 delays over gulps of 32
filterbank samples, so that a product's history spans the two gulps
before it): the program is correct, the control is not, the faults an
incoherent-dedispersion search can have, planted under the timed path,
end not correct, and ``ops.fdmt_history_share`` reads the program's
counters.

The faults: a gulp of the history left out (zeros where gulp k - 1
was); each product one gulp late (the product of gulp k - 1 delivered
as gulp k's); a history taken from the ring's lookahead (positive
delays in the program's place: the sweep's other sign).
"""

import numpy as np
import pytest

from test_xcorr import tamper
from util import rehearse

import progcounters
import run as harness

CELL = 'bl-frb-search-replay'
NAME = 'ops.fdmt_history_share'
#: the D2H piece shares, which read nothing where every product
#: crosses whole; and the shares of chains this one does not run
SILENT = {'xfer.h2d_stage_share', 'xfer.d2h_piece_share',
          'xfer.d2h_pair_share', 'xfer.d2h_plane_share',
          'xfer.d2h_flat_share', 'ops.acc_in_place_share',
          'ops.beam_fused_share', 'ops.beam_word_share',
          'ops.dedisp_fused_share', 'ops.dedisp_long_share',
          'ops.dedisp_level_order_share'}


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
    impl = res['window']['impl']['fdmt']
    assert impl['history'] == 'device' and impl['max_delay'] == 64
    assert impl['core'] in ('xla', 'rolls', 'pallas')
    counts = progcounters.counters()
    gulps = counts['fdmt.gulps']
    assert gulps == res['window']['offered'] > 0
    assert counts['fdmt.device_history_gulps'] == gulps
    # the front ran once a voltage frame: 32 filterbank samples a gulp
    assert counts['fdmt.front_frames'] == 32 * gulps
    assert harness.reader('per_layer', NAME).read(None) == \
        pytest.approx(100.0)
    # the 8-point transform took XLA's path, not the long one
    assert harness.reader('per_layer', 'ops.long_transform_share') \
        .read(None) == 0.0


@pytest.mark.parametrize('seed', [1, 2 ** 31 + 5])
def test_control_is_not_correct(seed):
    res = rehearse(CELL, seed=seed, control=True)
    assert res['control'] == 'reference'
    assert res['correct'] is False, res['checks']
    assert res['checks']['rel_err']['value'] >= \
        3 * res['checks']['rel_err']['limit']
    assert res['checks']['products_over_limit']['value'] == \
        res['attempted']


def test_reference_against_the_programs_oracle():
    """The reference's rows, its history rebuilt from the seed, against
    the program's own float64 FDMT (``Fdmt._core_numpy``, negative
    delays) over filterbanks computed gulp by gulp with numpy's
    transform; and its pick."""
    import traffic
    from bifrost_tpu.ops.fdmt import Fdmt
    seed = 5
    cfg, mod, mix, pool = loaded(seed)
    order = traffic.replay_order(mix, seed)

    def filterbank(g):
        v = g.view(np.int8).reshape(512, 4, 2, 8, 2).astype(np.float64)
        spec = np.fft.fftshift(np.fft.fft(v[..., 0] + 1j * v[..., 1]),
                               axes=-1)
        power = (np.abs(spec) ** 2).sum(axis=2)      # (512, 4, 8)
        return power.reshape(32, 16, 32).sum(axis=1).T
    plan = Fdmt().init(32, 64, 1100.0, 2.9296875 / 8)
    for k in (0, 1, 2, 7):
        idx = np.array([k, seed, 3, 60])
        got = mod.reference([pool[order[k]]], idx, cfg)
        x = np.concatenate([filterbank(pool[order[g]]) if g >= 0
                            else np.zeros((32, 32))
                            for g in (k - 2, k - 1, k)], axis=1)
        want = plan._core_numpy(x, negative_delays=True)[[3, 60], 64:]
        assert got.shape == (2, 32)
        np.testing.assert_allclose(got, want, rtol=1e-12,
                                   atol=1e-12 * want.max())
    sampler = traffic.Sampler(cfg, mix, seed, mod.pick)
    assert list(sampler.where(0)) == [0, seed, 0, 63]
    for k in range(1, 40):
        where = sampler.where(k)
        assert list(where[:2]) == [k, seed]
        assert list(where[2:] // 32) == [0, 1]


def test_work_at_the_deployment_size():
    _, _cell, cfg, mod = harness.load_cell(CELL)
    assert mod.shapes(cfg) == (262144, 64, 2, 8, 128, 512, 2048, 4096)
    assert mod.fine_band(cfg) == (1100.0, 0.3662109375)
    work = mod.work(cfg)
    assert work['samples'] == 2 ** 28 and work['int8_ops'] == 0
    fdmt = mod.fdmt_work(cfg)
    # nine levels over the channels' first; each about 4096 x 2048
    # float32 written and twice that read: 0.9-1.2 GB, ~1.3 ms at the
    # v5e's 819 GB/s
    assert 0.9e9 < fdmt['bytes'] < 1.2e9
    assert work['bytes'] == 2 * 2 ** 28 + fdmt['bytes']
    assert work['flops'] == 5.0 * 3 * 2 ** 28 + 4.0 * 2 ** 28 + \
        fdmt['flops']
    lv = mod.levels(512, 4096, *mod.fine_band(cfg))
    assert len(lv) == 10 and lv[-1][1] == [4096]
    assert [len(s) for s, _nd in lv] == [512 >> i for i in range(10)]
    assert mod.gulps_per_product(cfg) == 1
    assert cfg['reduced'] == ['nchan'] and cfg['limits']['rel_err'] <= 1e-5
    assert mod.control_env(cfg) == {'BF_FFT_IMPL': 'dftmm',
                                    'BF_FFT_DFT_DTYPE': 'bf16'}


def test_the_product_crosses_whole_at_the_deployment_size():
    """A 33.5 MB product is no larger than two D2H pieces, so the
    program counts no pieces in the cell and the piece shares read
    nothing."""
    import jax.numpy as jnp
    from bifrost_tpu import xfer
    product = jnp.zeros((4096, 2048), jnp.float32)
    assert product.nbytes == 33554432
    assert xfer._piece_plan(product) is None


def test_a_gulp_of_the_history_left_out():
    """Every third gulp reaches the chain as zeros after it has been
    the product of its own: the two products after it, whose history
    it is, are wrong, and the harness sees them."""
    res = rehearse(CELL, seed=4, wrap_chain=tamper(
        'input', lambda self, k, x: x * 0 if k % 3 == 1 else x))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] >= \
        res['attempted'] // 2


def test_each_product_one_gulp_late():
    """The product of gulp k - 1 delivered as gulp k's (the first as
    zeros): one product is due a gulp and none is missing, but each is
    another gulp's, wherever the pool gulps of the two products' runs
    differ (where the replay order repeats a run of three, the late
    product is the right one)."""
    import traffic
    seed = 6
    _cfg, _mod, mix, _pool = loaded(seed)
    order = traffic.replay_order(mix, seed)

    def late(self, k, x):
        out = x * 0 if self.last is None else self.last
        self.last = x
        return out
    res = rehearse(CELL, seed=seed, wrap_chain=tamper('output', late))

    def run(k):
        return tuple(order[g] if g >= 0 else -1
                     for g in range(k - 2, k + 1))
    differ = 1 + sum(run(k - 1) != run(k)
                     for k in range(1, res['attempted']))
    assert differ >= res['attempted'] // 2
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] == differ


def test_a_history_of_lookahead(monkeypatch):
    """The FDMT block with positive delays, its history the ring's
    lookahead (the chain's guard taken away for the fault): the sweep
    of the other sign, which the reference does not sum."""
    import bifrost_tpu as bf
    from bifrost_tpu.blocks.fdmt import FdmtBlock
    fdmt = bf.blocks.fdmt

    def positive(iring, **kwargs):
        kwargs['negative_delays'] = False
        blk = fdmt(iring, **kwargs)
        blk.define_input_overlap_nframe = \
            lambda iseq, _b=blk: 0 if iseq is None \
            else FdmtBlock.define_input_overlap_nframe(_b, iseq)
        return blk
    monkeypatch.setattr(bf.blocks, 'fdmt', positive)
    res = rehearse(CELL, seed=7)
    assert res['correct'] is False, res['checks']
    assert res['window']['impl']['fdmt']['history'] == 'ring overlap'


def test_a_program_whose_fdmt_holds_no_history_fails_at_chain_build(
        monkeypatch):
    """The benchmark's chain on a program whose FDMT block asks its
    ring for lookahead, sized per sequence (its parent commit), raises
    at chain build, before anything runs."""
    import bifrost_tpu as bf
    from bifrost_tpu.blocks.fdmt import FdmtBlock
    _, _cell, cfg, mod = harness.load_cell(CELL)
    monkeypatch.setattr(FdmtBlock, 'define_input_overlap_nframe',
                        lambda self, iseq: self.max_delay)
    with bf.Pipeline():
        src = bf.blocks.copy(_Source(mod.header(cfg)), space='tpu')
        with pytest.raises(RuntimeError, match='no history'):
            mod.chain(bf, src, cfg)


class _Source(object):
    """Stands for a source block: what the chain's construction reads."""

    def __new__(cls, header):
        from bifrost_tpu.pipeline import SourceBlock

        class Source(SourceBlock):
            def create_reader(self, name):
                import contextlib
                return contextlib.nullcontext()

            def on_sequence(self, reader, name):
                return [header]

            def on_data(self, reader, ospans):
                return [0]
        return Source(['x'], 1)


@pytest.mark.parametrize('counts,want', [
    ({'fdmt.gulps': 867, 'fdmt.device_history_gulps': 867}, 100.0),
    ({'fdmt.gulps': 800, 'fdmt.device_history_gulps': 200}, 25.0),
    ({'fdmt.gulps': 700, 'fdmt.device_history_gulps': 0}, 0.0),
    ({'fdmt.gulps': 700}, None),
    ({'fdmt.gulps': 0, 'fdmt.device_history_gulps': 0}, None),
    ({}, None),
    (None, None),
], ids=['all', 'a_quarter', 'none', 'counter_absent',
        'nothing_dedispersed', 'no_counters', 'no_module'])
def test_the_reader_on_made_up_counters(counts, want, monkeypatch):
    """Nothing where the program does not count them: the parent."""
    monkeypatch.setattr(progcounters, 'counters', lambda: counts)
    got = harness.reader('per_layer', NAME).read(None)
    assert got == (want if want is None else pytest.approx(want))


def test_the_benchmark_lists_the_cell_and_its_metrics():
    bench, cell, cfg, _mod = harness.load_cell(CELL)
    assert cell == {'name': CELL, 'config': 'bl-frb-search',
                    'traffic': 'replay-host', 'chips': 1,
                    'why': cell['why']}
    assert len(cell['why']) <= 200
    assert bench['workloads'][-1]['name'] == CELL
    assert {m['name'] for m in harness.metrics_of(bench, cell,
                                                  'end_to_end')} == \
        {'sustained_msps', 'host_cpu_s_per_gsample', 'setup_s'}
    mine = {m['name'] for m in harness.metrics_of(bench, cell, 'per_layer')}
    replay = {m['name'] for m in bench['per_layer']
              if m['moves'] != 'resident_msps'} - {'host.runq_wait_share'}
    assert NAME in mine and not mine & SILENT
    assert replay - mine <= SILENT | {'sink.exit_age_p90_s'}
    m = bench['per_layer'][-1]
    assert (m['name'], m['unit'], m['better'], m['source'], m['layer'],
            m['moves'], m['workloads']) == (NAME, '%', 'higher',
                                            'program_counter', 'kernels',
                                            'sustained_msps', [CELL])
    entry = bench['configs'][-1]
    assert entry['name'] == 'bl-frb-search'
    assert entry['reduced'] == cfg['reduced'] == ['nchan']
    assert entry['source'] == cfg['source'] and len(entry['source']) <= 200
