"""The fast-radio-burst search chain (perfbench's bl-frb-search) at a
small size on the CPU: ci8 voltages -> copy('tpu') -> fused [8-point
transform, Stokes I, sum of 16, corner turn] -> the fine channels as
one [freq, time] axis by header views -> FDMT with negative delays over
64 delays, its history held on the device -> copy('system').

Gulps of 32 filterbank samples are shorter than the 64-sample history,
so a product's history spans two gulps before it.  The reference is
the benchmark's own (float64 numpy, importing nothing of the program);
a dispersed burst placed in the voltages by the dispersion law guards
what a reference that shares the program's equations cannot see: the
sweep's sign and the product's time origin."""

import importlib.util
import json
import math
import os

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu.telemetry import counters

from util import GatherSink, NumpySourceBlock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, 'perfbench', 'configs', 'bl_frb_search')

#: the dispersion constant, s MHz^2 / (pc cm^-3)
KDM = 4.148808e3
#: voltage frames a gulp and gulps a stream: 32 filterbank samples a
#: gulp at 16 spectra a sample
NFRAME, NGULP = 512, 6

#: Tolerance of the program against float64, as the worst |got - want|
#: over the reference's peak.  The transform is float32 (on the chip at
#: float32's precision too, PERF.md section 6); a DM-time sample sums
#: nonnegative powers of a few hundred spectra, so the rounding of the
#: sums is relative, about 1e-7 of the whole.  The program reads about
#: 1e-7 here; a DFT matrix rounded to bfloat16 (the control) 6e-5.
TOL = 1e-5


def _module():
    spec = importlib.util.spec_from_file_location('cfg_bl_frb_search',
                                                  CONFIG + '.py')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope='module')
def setup():
    """(the configuration module, its rehearsal-size configuration)."""
    mod = _module()
    with open(CONFIG + '.json') as f:
        cfg = json.load(f)
    for key, val in cfg['rehearse'].items():
        cfg[key] = dict(cfg[key], **val) if isinstance(val, dict) else val
    assert mod.shapes(cfg) == (NFRAME, 4, 2, 8, 16, 32, 32, 64)
    return mod, cfg


CI8 = np.dtype([('re', np.int8), ('im', np.int8)])


def _voltages(seed, lo=-64, hi=64):
    rng = np.random.default_rng(seed)
    raw = rng.integers(lo, hi, size=(NGULP * NFRAME, 4, 2, 8, 2),
                       dtype=np.int8)
    return raw.view(CI8)[..., 0]


def _serve(mod, cfg, volts):
    """The chain on ``volts`` (frames, 4, 2, 8) ci8, gulp by gulp: the
    products concatenated in time (delays, samples), the sink's header
    and the FDMT block."""
    gulps = [volts[i:i + NFRAME] for i in range(0, len(volts), NFRAME)]
    counters.reset()
    with bf.Pipeline() as p:
        src = NumpySourceBlock(gulps, mod.header(cfg), NFRAME)
        fdmt = mod.chain(bf, bf.blocks.copy(src, space='tpu'), cfg)
        sink = GatherSink(bf.blocks.copy(fdmt, space='system'))
        p.run()
    return np.concatenate(sink.gulps, axis=-1), sink.headers[0], fdmt


def _filterbank(mod, cfg, volts):
    """The reference's filterbank of the whole stream (32, samples)."""
    return np.concatenate([mod._filterbank(volts[i:i + NFRAME], cfg,
                                           'float64')
                           for i in range(0, len(volts), NFRAME)], axis=1)


def test_served_chain_against_the_reference(setup):
    """Every product, whole, within float32 accuracy of the float64
    reference: frames before the stream's first are zeros, and each
    later product looks back across the two gulps before it."""
    mod, cfg = setup
    volts = _voltages(7)
    got, hdr, fdmt = _serve(mod, cfg, volts)
    assert got.shape == (64, NGULP * 32)
    f0, df = mod.fine_band(cfg)
    want = mod.fdmt_rows(_filterbank(mod, cfg, volts), range(64), 64,
                         f0, df)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < TOL
    assert fdmt.impl_info == {'fdmt': {'core': fdmt.fdmt.chosen_core,
                                       'history': 'device',
                                       'max_delay': 64}}
    t = hdr['_tensor']
    assert t['labels'] == ['dispersion', 'time'] and t['shape'] == [64, -1]
    # the fine channels ascend from the band's bottom edge
    assert (hdr['reffreq'], hdr['reffreq_units']) == (1100.0, 'MHz')
    assert t['scales'][1] == [0, 16 * 8 / 2.9296875e6]


def test_streamed_output_is_one_fdmt_of_the_whole_stream(setup):
    """The products, gulp after gulp, are one FDMT (the program's own,
    float32) over the concatenated filterbank; the front ran once a
    voltage frame and the FDMT's history came from the device."""
    from bifrost_tpu.ops.fdmt import Fdmt
    mod, cfg = setup
    volts = _voltages(8)
    got, _hdr, _fdmt = _serve(mod, cfg, volts)
    x = _filterbank(mod, cfg, volts).astype(np.float32)
    whole = np.asarray(Fdmt().init(32, 64, *mod.fine_band(cfg)).execute(
        x, negative_delays=True))
    np.testing.assert_allclose(got, whole, rtol=0,
                               atol=TOL * np.abs(whole).max())
    assert counters.get('fdmt.gulps') == NGULP
    assert counters.get('fdmt.device_history_gulps') == NGULP
    assert counters.get('fdmt.front_frames') == NGULP * NFRAME // 16


def _burst(mod, cfg, dm, t_bottom, amp=40):
    """Voltages with one burst of dispersion measure ``dm``: in fine
    channel i (of [f0 + i df, f0 + (i + 1) df]) a tone at the bin's
    frequency, in polarisation 0, through the filterbank sample at
    which the dispersion law puts the channel's middle, the burst
    reaching the band's bottom edge at sample ``t_bottom``."""
    f0, df = mod.fine_band(cfg)
    tsamp = 16 * 8 / (cfg['band']['chan_mhz'] * 1e6)
    volts = _voltages(9, -4, 4).copy()
    n = np.arange(8)
    for i in range(32):
        f = f0 + (i + 0.5) * df
        at = t_bottom - KDM * dm * (f0 ** -2 - f ** -2) / tsamp
        s = int(round(at))
        c, j = divmod(i, 8)
        tone = amp * np.exp(2j * np.pi * (j - 4) * n / 8)
        frames = volts[s * 16:(s + 1) * 16, c, 0]
        frames['re'] = np.round(tone.real).astype(np.int8)
        frames['im'] = np.round(tone.imag).astype(np.int8)
    return volts


@pytest.mark.parametrize('dm,t_bottom', [(24.0, 120), (12.5, 70)])
def test_dispersed_burst_found_where_the_law_puts_it(setup, dm, t_bottom):
    """The product's peak lies at the burst's DM row (the header's DM
    step) and at the filterbank sample of its arrival at the band's
    bottom edge (the header's time origin), each within one: the
    higher channels, which it reached first, are read earlier."""
    mod, cfg = setup
    got, hdr, _fdmt = _serve(mod, cfg, _burst(mod, cfg, dm, t_bottom))
    dm0, dm_step = hdr['_tensor']['scales'][0]
    assert hdr['_tensor']['units'][0] == 'pc cm^-3' and dm_step > 0
    row, t = np.unravel_index(np.argmax(got), got.shape)
    assert abs(dm0 + row * dm_step - dm) <= dm_step
    assert abs(t - t_bottom) <= 1
    # the sweep gathers most channels' share of the burst, 16 spectra
    # of |8 x 40|^2 each (the tree's rounded tracks miss a few by a
    # sample at the lower DM), where one channel holds 1/32 of it
    assert got[row, t] > 0.5 * 32 * 16 * (8 * 40) ** 2
    # the DM step is the band's: max_delay samples across it
    f0, df = mod.fine_band(cfg)
    tsamp = 16 * 8 / (cfg['band']['chan_mhz'] * 1e6)
    band = f0 ** -2 - (f0 + 32 * df) ** -2
    assert dm_step == pytest.approx(tsamp / (KDM * band), rel=1e-4)
    assert math.isclose(hdr['max_dm'], 64 * dm_step)
