"""gpuspec: the chain, its plain reference and its work count.

The chain is the repo's flagship (copied from bench.py's
flagship_header / flagship_stages, which a later PR may delete):
FftStage('fine_time') -> DetectStage('stokes') -> ReduceStage('freq', r)
on ci8 dual-polarisation voltages.  The reference below imports
nothing of the program and is float64 numpy throughout.
"""

import math

import numpy as np


def shapes(cfg):
    npol, nfine = cfg['input']['frame_shape']
    return cfg['gulp_nframe'], npol, nfine, cfg['rfactor']


def header(cfg):
    _, npol, nfine, _ = shapes(cfg)
    return {'name': 'perfbench-gpuspec', 'time_tag': 0,
            '_tensor': {'shape': [-1, npol, nfine], 'dtype': 'ci8',
                        'labels': ['time', 'pol', 'fine_time'],
                        'scales': [[0, 1]] * 3, 'units': [None] * 3}}


def chain(bf, upstream, cfg):
    """The device chain, downstream of a 'tpu'-space ring."""
    from bifrost_tpu.stages import FftStage, DetectStage, ReduceStage
    return bf.blocks.fused(upstream, [
        FftStage('fine_time', axis_labels='freq'),
        DetectStage('stokes', axis='pol'),
        ReduceStage('freq', cfg['rfactor'])])


def gulps_per_product(cfg):
    return 1


def control_env(cfg):
    """The program's own nearest-lower-precision path (the control on
    the chip): the Pallas spectrometer forced at its default precision,
    one bf16 pass on the MXU."""
    return {'BF_SPEC_IMPL': 'pallas', 'BF_SPEC_PREC': ''}


def pick(rng, cfg, full):
    """Frames of one product that are compared: one seeded frame from
    each of n equal stretches of the gulp.  In full, n is
    ``frames_per_product``, so the picks cover the kernel's grid of
    16-frame tiles end to end at every position within a tile, and the
    first and the last frame are among them; otherwise
    ``frames_otherwise``, enough to tell one product from another."""
    ntime = cfg['gulp_nframe']
    n = min(cfg['sample']['frames_per_product' if full
                          else 'frames_otherwise'], ntime)
    edges = np.arange(n + 1) * ntime // n
    idx = rng.integers(edges[:-1], edges[1:])
    if full:
        idx[0], idx[-1] = 0, ntime - 1
    return idx


def take(product, idx):
    """The compared part of one product: a copy, on the host for a
    numpy product and on the device (one small program, which the
    trace reduction knows by its name and leaves out) for a jax one."""
    if isinstance(product, np.ndarray):
        return product[idx]
    global _take_jit
    if _take_jit is None:
        import jax
        import jax.numpy as jnp

        def bench_take(p, i):
            return jnp.take(p, i, axis=0)
        _take_jit = jax.jit(bench_take)
    return _take_jit(product, idx)


_take_jit = None


def _bf16(x):
    import ml_dtypes
    return x.astype(np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def reference(gulps, idx, cfg, precision='float64'):
    """What ``take`` should hold for the product of ``gulps`` (one
    host gulp, ci8 storage).  ``precision='bfloat16'`` is the control:
    the same mathematics with the transform's operands rounded to
    bfloat16 (a DFT by matrix product, as one MXU pass computes it)."""
    frames = gulps[0][idx]
    # (re, im) int8 pairs -> complex128, by bytes: reading the two
    # fields of the structured array apart is twenty times slower
    v = frames.view(np.int8).reshape(frames.shape + (2,)) \
        .astype(np.float64).view(np.complex128)[..., 0]
    if precision == 'float64':
        s = np.fft.fft(v, axis=-1)
    elif precision == 'bfloat16':
        n = v.shape[-1]
        k = np.arange(n)
        ang = -2.0 * np.pi * ((k[:, None] * k[None, :]) % n) / n
        w = _bf16(np.cos(ang)) + 1j * _bf16(np.sin(ang))
        s = (_bf16(v.real) + 1j * _bf16(v.imag)) @ w
    else:
        raise ValueError('unknown precision %r' % precision)
    x, y = s[:, 0], s[:, 1]
    xx = x.real ** 2 + x.imag ** 2
    yy = y.real ** 2 + y.imag ** 2
    xy = x * np.conj(y)
    r = cfg['rfactor']

    def reduced(a):
        return a.reshape(a.shape[0], a.shape[1] // r, r).sum(-1)
    return np.stack([reduced(xx + yy), reduced(xx - yy),
                     reduced(2 * xy.real), reduced(-2 * xy.imag)], axis=1)


def compare(got, want):
    """('rel_err', value): worst error over the reference's peak."""
    got = np.asarray(got, dtype=np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return 'rel_err', float('inf')
    return 'rel_err', float(np.max(np.abs(got - want)) /
                            (np.max(np.abs(want)) or 1.0))


def work(cfg):
    """Per gulp, from the shapes alone: ci8 in + float32 Stokes out
    bytes, and 5 N log2 N flops per transform."""
    ntime, npol, nfine, r = shapes(cfg)
    return {'samples': ntime * npol * nfine,
            'bytes': ntime * npol * nfine * 2
            + ntime * 4 * (nfine // r) * 4,
            'flops': 5.0 * nfine * math.log2(nfine) * ntime * npol,
            'int8_ops': 0.0}
