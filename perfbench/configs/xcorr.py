"""xcorr: the chain, its plain reference and its work count.

The chain is the program's X step alone, ``bf.blocks.correlate``, on
ci8 voltages labelled (time, freq, station, pol): one full
station-pol by station-pol matrix a channel, integrated over
``nframe_per_integration`` frames, which is several gulps.  The
reference below imports nothing of the program; its sums are whole
numbers held in int64.
"""

import numpy as np


def shapes(cfg):
    nchan, nstation, npol = cfg['input']['frame_shape']
    return cfg['gulp_nframe'], nchan, nstation, npol


def header(cfg):
    _, nchan, nstation, npol = shapes(cfg)
    return {'name': 'perfbench-xcorr', 'time_tag': 0,
            '_tensor': {'shape': [-1, nchan, nstation, npol],
                        'dtype': 'ci8',
                        'labels': ['time', 'freq', 'station', 'pol'],
                        'scales': [[0, 1]] * 4, 'units': [None] * 4}}


def chain(bf, upstream, cfg):
    """The device chain, downstream of a 'tpu'-space ring: nothing but
    the block and its integration length.  A program that sizes its
    rings without looking at a span's bytes passes the machine's host
    memory with this configuration's 2.1 GB products and is killed
    (PERF.md section 6, PR 25 and 28), so such a program is refused
    here, by what it lacks and before it allocates anything."""
    if not hasattr(bf.memory, 'span_depth'):
        raise RuntimeError(
            'xcorr needs a program that sizes its rings and its '
            'transfers in flight by bytes (bifrost_tpu.memory.'
            'span_depth); this one does not')
    return bf.blocks.correlate(
        upstream, nframe_per_integration=cfg['nframe_per_integration'])


def gulps_per_product(cfg):
    return cfg['nframe_per_integration'] // cfg['gulp_nframe']


def control_env(cfg):
    """Nothing: the program has no path that is lossy on these
    voltages.  Its lowest-precision candidate, the one-pass bfloat16
    X-engine (``BF_XCORR_IMPL=planar_bf16``), is exact here, since an
    int8 operand has eight significant bits and bfloat16 holds eight,
    every product is below 2^14 and every sum below 2^24, which the
    MXU's float32 accumulator holds (PERF.md section 6, PR 28, has the
    reading).  With no variable to set, the harness puts the
    reference's ``precision='int4'`` form in the program's place, on
    the chip as in a rehearsal."""
    return {}


def _product_index(rng):
    """Index of the product ``rng`` was made for: the sampler
    (traffic.Sampler.where) seeds it with [seed, 3, k]."""
    try:
        return int(rng.bit_generator.seed_seq.entropy[2])
    except (AttributeError, IndexError, TypeError):
        return None


def pick(rng, cfg, full):
    """Channels of one product that are compared, each with its whole
    station-pol by station-pol matrix: one seeded channel from each of
    ``channels_per_product`` equal stretches of the band, so four
    whole matrices, one from each quarter; of product 0 the first and
    the last of these are channels 0 and nchan - 1."""
    nchan = cfg['input']['frame_shape'][0]
    n = min(cfg['sample']['channels_per_product'], nchan)
    edges = np.arange(n + 1) * nchan // n
    idx = rng.integers(edges[:-1], edges[1:])
    if _product_index(rng) == 0:
        idx[0], idx[-1] = 0, nchan - 1
    return idx


def take(product, idx):
    """The compared part of one product (1, nchan, S, P, S, P): a copy
    of the picked channels' matrices."""
    return product[0][idx]


def _gram(a, b):
    """a^T b over the frames, for whole numbers in float64 operands:
    BLAS computes it fifty times faster than numpy's integer loops, and
    exactly, every partial sum being a whole number below 2^53."""
    return np.rint(a.T @ b).astype(np.int64)


def reference(gulps, idx, cfg, precision='int64'):
    """What ``take`` should hold for the product of ``gulps`` (host
    gulps in ci8 storage, in order): v[f] = sum_t x[t,f,:] conj(x[t,f,:])^T
    over all their frames, as int64 whole numbers cast to complex64.
    ``precision='int4'`` is the control: the same sums with the
    operands rounded to their four leading bits."""
    if precision not in ('int64', 'int4'):
        raise ValueError('unknown precision %r' % precision)
    _, nchan, nstation, npol = shapes(cfg)
    n = nstation * npol
    re = np.zeros((len(idx), n, n), np.int64)
    im = np.zeros((len(idx), n, n), np.int64)
    for gulp in gulps:
        x = gulp[:, idx]                         # (T, channels, S, P)
        v = x.view(np.int8).reshape(x.shape[0], len(idx), n, 2)
        if precision == 'int4':
            v = (v >> 4) << 4
        # (channels, re/im, T, n) planes that lie in one piece each:
        # numpy hands only such operands to BLAS
        v = np.ascontiguousarray(v.transpose(1, 3, 0, 2),
                                 dtype=np.float64)
        for c in range(len(idx)):
            r, i = v[c, 0], v[c, 1]
            k = _gram(i, r)
            re[c] += _gram(r, r) + _gram(i, i)
            im[c] += k - k.T
    return (re + 1j * im).astype(np.complex64) \
        .reshape(len(idx), nstation, npol, nstation, npol)


def compare(got, want):
    """('max_abs_err', value): the largest |got - want| of any element
    of the compared matrices; the limit is 0."""
    got = np.asarray(got)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return 'max_abs_err', float('inf')
    return 'max_abs_err', float(np.max(np.abs(
        got.astype(np.complex128) - want)))


def work(cfg):
    """Per gulp, from the shapes alone, what any implementation must
    do: every sample read once and a gulp's share of the product
    written once; the Hermitian half of the matrix, diagonal included,
    at 8 int8 operations a complex multiply-add."""
    ntime, nchan, nstation, npol = shapes(cfg)
    n = nstation * npol
    return {'samples': ntime * nchan * n,
            'bytes': ntime * nchan * n * 2
            + nchan * n * n * 8 // gulps_per_product(cfg),
            'flops': 0.0,
            'int8_ops': 8.0 * ntime * nchan * n * (n + 1) / 2}
