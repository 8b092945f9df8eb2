"""beamform-tab: the chain, its plain reference and its work count.

MeerKAT's tied-array beamformer FBFUSE at one worker's share of the
band: 64 dishes x 2 polarisations of 8-bit complex voltages, 864
coherent beams, each steered by a phase per antenna per channel held
as int8 weights, Stokes I, 16 samples summed, 8-bit filterbanks out.
The chain is the program's fused block over four stages, built through
its public API at its defaults.  The reference below imports nothing
of the program: whole-number beam sums (exact: every partial sum is a
whole number below 2^22) and float64 powers, scaled and clipped, NOT
rounded.

The harness hands ``--seed`` to the pool, the replay order and the
sampler, and not to ``chain``; the weights are drawn from it all the
same (``_run_seed``), and the reference learns it from the sampler's
generator, so that both sides steer the same beams.
"""

import sys

import numpy as np

_weights = {}


def shapes(cfg):
    nchan, nstation, npol = cfg['input']['frame_shape']
    return cfg['gulp_nframe'], nchan, nstation, npol


def header(cfg):
    _, nchan, nstation, npol = shapes(cfg)
    return {'name': 'perfbench-beamform-tab', 'time_tag': 0,
            '_tensor': {'shape': [-1, nchan, nstation, npol],
                        'dtype': 'ci8',
                        'labels': ['time', 'freq', 'station', 'pol'],
                        'scales': [[0, 1]] * 4, 'units': [None] * 4}}


def weights(cfg, seed):
    """(re, im): the int8 weights (nchan, npol, nbeam, nstation) of
    the run ``seed``: a phase uniform in [0, 2 pi) per (channel, pol,
    beam, antenna), times 127, rounded."""
    _, nchan, nstation, npol = shapes(cfg)
    key = (int(seed), nchan, npol, cfg['nbeam'], nstation)
    if key not in _weights:
        rng = np.random.default_rng([int(seed), 4])
        phase = rng.random(key[1:], dtype=np.float32) * (2 * np.pi)
        _weights.clear()
        _weights[key] = (np.rint(127 * np.cos(phase)).astype(np.int8),
                         np.rint(127 * np.sin(phase)).astype(np.int8))
    return _weights[key]


def out_scale(cfg):
    """What a beam's summed power is multiplied by before it is cut to
    8 bits, in units of weights of modulus 1: the mean of the stated
    voltage distribution lands at ``output.mean`` of the 256 steps."""
    _, _, nstation, npol = shapes(cfg)
    lo, hi = cfg['input']['range']
    power = 2.0 * sum(k * k for k in range(lo, hi)) / (hi - lo)
    return cfg['output']['mean'] / (cfg['tscrunch'] * npol * nstation
                                    * power)


def _run_seed():
    """The run's ``--seed``, from the caller that holds it: run.py's
    ``main`` (its ``args``) or drive.py's ``run_window`` (its
    ``sampler``).  ``chain(bf, upstream, cfg)`` is not handed it
    (PERF.md section 7 asks a ``benchmark`` PR for that argument)."""
    frame = sys._getframe(1)
    while frame is not None:
        for name in ('sampler', 'args'):
            seed = getattr(frame.f_locals.get(name), 'seed', None)
            if isinstance(seed, int):
                return seed
        frame = frame.f_back
    raise RuntimeError('beamform-tab draws its weights from --seed, '
                       'and no caller of chain() holds one')


def chain(bf, upstream, cfg, seed=None):
    """The device chain, downstream of a 'tpu'-space ring, at the
    program's defaults.  The weights reach the stage as w8 / 127, so
    that its quantisation (one scale: the largest modulus over 127)
    gives w8 back bit for bit."""
    from bifrost_tpu.stages import (BeamformStage, DetectStage,
                                    ReduceStage, QuantizeStage)
    re, im = weights(cfg, _run_seed() if seed is None else seed)
    w = np.empty(re.shape, np.complex64)
    w.real = re
    w.imag = im
    w *= np.float32(1.0 / 127.0)
    return bf.blocks.fused(upstream, [
        BeamformStage(w, accuracy='int8'),
        DetectStage('stokes_i'),
        ReduceStage('time', cfg['tscrunch']),
        QuantizeStage(cfg['output']['dtype'], out_scale(cfg))])


def gulps_per_product(cfg):
    return 1


def control_env(cfg):
    """Nothing: ``--control`` puts the reference's ``precision='int4'``
    form (voltages cut to their four leading bits) in the program's
    place, on the chip as in a rehearsal.  The program's own lossy
    path, the fused kernel with one bfloat16 pass of the float weights
    (``BF_BEAM_IMPL=pallas_bf16``), is the second control:
    ``tools/beam_bf16_control.py`` runs the cell with it."""
    return {}


def _sampler_seed(rng):
    """(seed, k) the sampler made ``rng`` for: traffic.Sampler.where
    seeds it with [seed, 3, k]."""
    entropy = rng.bit_generator.seed_seq.entropy
    return int(entropy[0]), int(entropy[2])


def pick(rng, cfg, full):
    """(seed, times, beams) of one product that are compared, every
    channel of each: one seeded output time from each of
    ``times_per_product`` equal stretches of the product, and one
    seeded beam from each of ``beams_per_product`` equal stretches of
    the beams; of product 0 the first and the last beam and the first
    and the last time.  The seed rides along for the reference's
    weights."""
    seed, k = _sampler_seed(rng)
    nout = cfg['gulp_nframe'] // cfg['tscrunch']

    def one_of_each(n, length):
        n = min(n, length)
        edges = np.arange(n + 1) * length // n
        idx = rng.integers(edges[:-1], edges[1:])
        if k == 0:
            idx[0], idx[-1] = 0, length - 1
        return idx
    times = one_of_each(cfg['sample']['times_per_product'], nout)
    beams = one_of_each(cfg['sample']['beams_per_product'], cfg['nbeam'])
    return seed, times, beams


def take(product, idx):
    """The compared part of one product (nout, nchan, 1, nbeam): a
    copy, (times, nchan, beams)."""
    _, times, beams = idx
    return product[times][:, :, 0][:, :, beams]


def reference(gulps, idx, cfg, precision='int64'):
    """What ``take`` should hold for the product of ``gulps`` (one
    host gulp in ci8 storage): for every picked output time, channel
    and beam, sum over ``tscrunch`` frames and both polarisations of
    |sum_s w8[f,p,b,s] x[t,f,s,p]|^2 (re and im apart, whole numbers),
    times the output's scale over 127^2, clipped to the output's
    range and NOT rounded.  ``precision='int4'`` is the control: the
    voltages cut to their four leading bits."""
    if precision not in ('int64', 'int4'):
        raise ValueError('unknown precision %r' % precision)
    seed, times, beams = idx
    (gulp,) = gulps
    r, nb = cfg['tscrunch'], len(beams)
    _, nchan, nstation, npol = shapes(cfg)
    frames = (np.asarray(times)[:, None] * r + np.arange(r)).reshape(-1)
    x = gulp[frames]                                 # (n r, F, S, P)
    v = x.view(np.int8).reshape(len(frames), nchan, nstation * npol * 2)
    if precision == 'int4':
        v = (v >> 4) << 4
    # a frame's bytes of one channel, (station, pol, re/im) as they
    # lie, against that channel's weights laid out to match: columns
    # (pol, re/im of the beam sum, beam), a row of the other
    # polarisation zero.  Whole numbers in float32: every product is
    # below 2^14 and every partial sum below 2^22, so BLAS adds them
    # exactly in any order (the test compares with int64 loops)
    wr, wi = (w[:, :, beams].astype(np.float32)
              for w in weights(cfg, seed))           # (F, P, nb, S)
    wide = np.zeros((nchan, nstation, npol, 2, npol, 2, nb), np.float32)
    for p in range(npol):
        wrt, wit = wr[:, p].swapaxes(1, 2), wi[:, p].swapaxes(1, 2)
        wide[:, :, p, 0, p, 0] = wrt                 # re x wr -> re
        wide[:, :, p, 1, p, 0] = -wit                # im x wi -> re
        wide[:, :, p, 0, p, 1] = wit
        wide[:, :, p, 1, p, 1] = wrt
    wide = wide.reshape(nchan, nstation * npol * 2, npol * 2 * nb)
    sums = np.matmul(v.astype(np.float32).transpose(1, 0, 2), wide)
    power = (sums.astype(np.float64) ** 2) \
        .reshape(nchan, len(times), r, npol * 2, nb).sum(axis=(2, 3))
    lo, hi = cfg['output']['clip']
    return np.clip(power.transpose(1, 0, 2) * (out_scale(cfg) / 127.0 ** 2),
                   lo, hi)


def compare(got, want):
    """('max_lsb_err', value): the largest |got - want| of any
    compared value, in steps of the 8-bit output, against the
    unrounded reference: half a step is the rounding's own."""
    got = np.asarray(got)
    if got.shape != want.shape:
        return 'max_lsb_err', float('inf')
    return 'max_lsb_err', float(np.max(np.abs(
        got.astype(np.float64) - want)))


def work(cfg):
    """Per gulp, from the shapes alone, whatever implements them:
    every sample read once, the 8-bit product and the weights once;
    8 int8 operations a complex multiply-add, a sample meeting every
    beam once."""
    ntime, nchan, nstation, npol = shapes(cfg)
    samples = ntime * nchan * nstation * npol
    return {'samples': samples,
            'bytes': samples * 2
            + (ntime // cfg['tscrunch']) * nchan * cfg['nbeam']
            + nchan * npol * cfg['nbeam'] * nstation * 2,
            'flops': 0.0,
            'int8_ops': 8.0 * cfg['nbeam'] * samples}
