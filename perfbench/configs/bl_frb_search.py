"""bl-frb-search: the chain, its plain reference and its work count.

A fast-radio-burst search on one Breakthrough Listen node: rawspec's
high-time-resolution product of 8-bit complex dual-polarisation
voltages (an 8-point transform of every 2.9296875 MHz channel, fine
channels in ascending order across the node, Stokes I, 128 spectra
summed: 512 channels of 366.2 kHz x 349.525 us), turned on the device
into [channel, time] and dedispersed by the FDMT (Zackay & Ofek 2017)
over 4096 delays.  The chain is the program's fused block over the
front's stages and a corner turn, two header views that make the
node's 64 x 8 fine channels one axis, and its FDMT block with negative
delays, which holds the 4096 filterbank samples before each gulp on the
device: one product a gulp, built through its public API at its
defaults.

Frame t of product k is the sweep that reaches the band's bottom edge,
f0 = 1100 MHz, at filterbank sample t of gulp k; channel c it crossed
delay(c) samples earlier.  Row d of it is the sweep of DM d x dm_step,
which the output header states in pc cm^-3.

The reference below imports nothing of the program and is float64
numpy throughout, written from the equations:

    X_k = sum_n x_n exp(-2 pi i n k / 8), shifted so that k runs from
    -4 to 3; I = |X_pol0|^2 + |X_pol1|^2, summed over 128 spectra.

    FDMT over channels c of [f0 + c df, f0 + (c + 1) df], negative
    delays (the sweep looks back from f0):
    A_c[d](t) = sum_{i <= d} I_c(t - i);
    a subband [fl, fh] holds nd = ceil((D - 1) cff(fl, fh) / cff(band))
    + 1 delays, cff(a, b) = |a^-2 - b^-2|, D = 4096;
    merging [fl, fm] and [fm, fh]:  A[d](t) = A_lo[d1](t) +
    A_hi[d2](t - d1), d1 = round(d cff(fl, fm) / cff(fl, fh)) and
    d2 = d - d1, each clipped to its half's delays; subbands pair up in
    order, an odd last one passing through.

The delay tables are upstream Bifrost's (src/fdmt.cu), rounded as its
Python port rounds them: half-way cases, which these bands never meet,
to even.  Departures from the source: one node's band is searched
alone; no matched filter, threshold or candidate list follows the
FDMT; a replayed gulp is not its predecessor's continuation, and the
history is whatever gulps the replay order put before it, in the
program and here alike.  ``pick`` carries the product's index and the
run's seed, read from the sampler's generator as guppi_fold.py reads
them, and the reference rebuilds the gulps of the history through the
traffic generator's own functions; it computes each pool gulp's
filterbank once, each run of pool gulps' concatenation once, and of the
FDMT only the rows that the two compared DM rows need.
"""

import math
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def shapes(cfg):
    """(ntime, nchan, npol, nfft, nspec, nfine, nout, max_delay): a
    gulp's voltage frames, coarse channels, polarisations, transform
    length and spectra summed; the fine channels, the filterbank
    samples a gulp and the FDMT's delays."""
    nchan, npol, nfft = cfg['input']['frame_shape']
    ntime = cfg['gulp_nframe']
    nspec = cfg['channelise']['nspec']
    return (ntime, nchan, npol, nfft, nspec, nchan * nfft, ntime // nspec,
            cfg['fdmt']['max_delay'])


def fine_band(cfg):
    """(f0, df) in MHz: the fine channels' first frequency and step."""
    nfft = cfg['input']['frame_shape'][2]
    return cfg['band']['lowest_mhz'], cfg['band']['chan_mhz'] / nfft


def header(cfg):
    _, nchan, npol, nfft = shapes(cfg)[:4]
    band = cfg['band']
    df = band['chan_mhz']
    dt = 1e-6 / df
    return {'name': 'perfbench-bl-frb-search', 'time_tag': 0,
            '_tensor': {'shape': [-1, nchan, npol, nfft], 'dtype': 'ci8',
                        'labels': ['time', 'freq', 'pol', 'fine_time'],
                        'scales': [[0, nfft * dt],
                                   [band['lowest_mhz'] + df / 2, df],
                                   [0, 1], [0, dt]],
                        'units': ['s', 'MHz', None, 's']}}


def chain(bf, upstream, cfg):
    """The device chain, downstream of a 'tpu'-space ring, at the
    program's defaults."""
    from bifrost_tpu.stages import (DetectStage, FftStage, ReduceStage,
                                    TransposeStage)
    front = bf.blocks.fused(upstream, [
        FftStage('fine_time', axis_labels='fine_freq', apply_fftshift=True),
        DetectStage('stokes_i'),
        ReduceStage('time', cfg['channelise']['nspec']),
        TransposeStage(['pol', 'freq', 'fine_freq', 'time'])])
    channels = bf.views.merge_axes(bf.views.delete_axis(front, 'pol'),
                                   'freq', 'fine_freq', label='freq')
    fdmt = bf.blocks.fdmt(channels, max_delay=cfg['fdmt']['max_delay'],
                          negative_delays=True)
    # the history is the block's own, held on the device; a program
    # whose FDMT block asks its ring for max_delay frames of lookahead
    # instead (sized per sequence: before the block held its history)
    # would read the wrong frames for every gulp
    try:
        lookahead = fdmt.define_input_overlap_nframe(None)
    except AttributeError:
        lookahead = None
    if lookahead != 0:
        raise RuntimeError('bl-frb-search: this program\'s FDMT block '
                           'takes no history of its own')
    return fdmt


def gulps_per_product(cfg):
    return 1


def control_env(cfg):
    """The program's own nearest-lower-precision path: the 8-point
    transform as DFT matrix products (BF_FFT_IMPL=dftmm, which jnp.fft
    of that length otherwise is, at float32's precision) with one bf16
    pass a product."""
    return {'BF_FFT_IMPL': 'dftmm', 'BF_FFT_DFT_DTYPE': 'bf16'}


def _seeded(rng):
    """(product index, run seed) ``rng`` was made for: the sampler
    (traffic.Sampler.where) seeds it with [seed, 3, k]."""
    try:
        entropy = rng.bit_generator.seed_seq.entropy
        return int(entropy[2]), int(entropy[0])
    except (AttributeError, IndexError, TypeError):
        return None


def pick(rng, cfg, full):
    """[k, seed, rows...]: the product's index and the run's seed,
    which the reference needs to rebuild the gulps before it, then the
    DM rows that are compared, each whole (every time): one seeded row
    from each of ``rows_per_product`` equal stretches of the delays; of
    product 0 the first and the last."""
    seeded = _seeded(rng)
    if seeded is None:
        raise ValueError('bl-frb-search: the sampler\'s generator does not '
                         'say which product it picks for')
    md = cfg['fdmt']['max_delay']
    n = min(cfg['sample']['rows_per_product'], md)
    edges = np.arange(n + 1) * md // n
    rows = rng.integers(edges[:-1], edges[1:])
    if seeded[0] == 0:
        rows[0], rows[-1] = 0, md - 1
    return np.concatenate([list(seeded), rows]).astype(np.int64)


def take(product, idx):
    """The compared part of one product (max_delay, nout): a copy of
    the picked DM rows."""
    return np.asarray(product)[np.asarray(idx[2:])]


def _bf16(x):
    import ml_dtypes
    return x.astype(np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def _dft_rows(nfft, precision):
    """(2 nfft, 2 nfft) float64: the nfft-point DFT as a real matrix
    that takes a row of (re, im) pairs, as ci8 holds a channel's
    samples, to [Re X_0 .. Re X_n-1, Im X_0 .. Im X_n-1].
    ``precision='bfloat16'`` rounds it so (the control: one pass; the
    voltages are exact in bfloat16)."""
    n = np.arange(nfft)
    c = np.cos(2 * np.pi * np.outer(n, n) / nfft)
    s = np.sin(2 * np.pi * np.outer(n, n) / nfft)
    m = np.empty((2 * nfft, 2 * nfft))
    # X_k = sum_n (re_n + i im_n)(c_nk - i s_nk)
    m[0::2, :nfft], m[1::2, :nfft] = c, s
    m[0::2, nfft:], m[1::2, nfft:] = -s, c
    if precision == 'bfloat16':
        return _bf16(m)
    if precision != 'float64':
        raise ValueError('unknown precision %r' % precision)
    return m


def _filterbank(gulp, cfg, precision):
    """(nfine, nout) float64: Stokes I of one gulp's 8-point spectra,
    fine channels ascending across the node, ``nspec`` spectra summed;
    stretches of the gulp on a few threads (numpy's products and sums
    leave the GIL).  ``precision='bfloat16'`` is the control
    (:func:`_dft_rows`)."""
    ntime, nchan, npol, nfft, nspec, nfine, nout, _ = shapes(cfg)
    m = _dft_rows(nfft, precision)
    raw = gulp.view(np.int8).reshape(ntime, -1)
    out = np.empty((nout, nchan, nfft))
    # stretches of 1024 frames: 16 MB of float64 a thread, where the
    # program's own host memory leaves a few GB of the machine's
    step = max(nspec, (1 << 10) // nspec * nspec)

    def stretch(t0):
        spec = raw[t0:t0 + step].reshape(-1, 2 * nfft).astype(np.float64) @ m
        power = spec[:, :nfft] ** 2 + spec[:, nfft:] ** 2
        power = power.reshape(-1, nspec, nchan, npol, nfft).sum(axis=(1, 3))
        out[t0 // nspec:t0 // nspec + len(power)] = \
            np.fft.fftshift(power, axes=-1)
    with ThreadPoolExecutor(max_workers=4) as ex:
        list(ex.map(stretch, range(0, ntime, step)))
    return np.ascontiguousarray(out.reshape(nout, nfine).T)


def _cff(a, b):
    return abs(a ** -2 - b ** -2)


def levels(nfine, max_delay, f0, df):
    """[(subbands, nd)], the channels first and the whole band last:
    each subband's (lowest, highest) frequency and its count of
    delays; a level's subbands pair up in order, an odd last one
    passing through."""
    band = _cff(f0, f0 + nfine * df)

    def nd(lo, hi):
        return int(math.ceil((max_delay - 1) * _cff(lo, hi) / band)) + 1
    subs = [(f0 + c * df, f0 + (c + 1) * df) for c in range(nfine)]
    out = [(subs, [nd(*s) for s in subs])]
    while len(subs) > 1:
        subs = [(subs[i][0], subs[min(i + 1, len(subs) - 1)][1])
                for i in range(0, len(subs), 2)]
        out.append((subs, [nd(*s) for s in subs]))
    return out


def fdmt_rows(x, rows, max_delay, f0, df):
    """Rows ``rows`` of the FDMT of ``x`` (nfine, N) float64 with
    negative delays, each (N,): the recursion, evaluated for the
    (level, subband, delay) rows that these need alone."""
    lv = levels(x.shape[0], max_delay, f0, df)
    n = x.shape[1]
    memo = {}
    # running sums along time, a zero first: a channel's first-level
    # row is the difference of two of them, one pass where the sum of
    # its d + 1 shifts took d + 1
    run = np.zeros((x.shape[0], n + 1))
    np.cumsum(x, axis=1, out=run[:, 1:])

    def row(level, s, d):
        key = (level, s, d)
        got = memo.get(key)
        if got is not None:
            return got
        if level == 0:
            out = run[s, 1:].copy()
            out[d + 1:] -= run[s, 1:n - d]
        else:
            subs, nds = lv[level - 1]
            if 2 * s + 1 == len(subs):          # passes through
                out = row(level - 1, 2 * s, min(d, nds[2 * s] - 1))
            else:
                fl, fm = subs[2 * s][0], subs[2 * s + 1][0]
                fh = subs[2 * s + 1][1]
                whole = _cff(fl, fh)
                ratio = _cff(fl, fm) / whole if whole else 0.0
                d1 = min(max(int(np.round(d * ratio)), 0), nds[2 * s] - 1)
                d2 = min(max(d - d1, 0), nds[2 * s + 1] - 1)
                out = row(level - 1, 2 * s, d1).copy()
                out[d1:] += row(level - 1, 2 * s + 1, d2)[:n - d1]
        memo[key] = out
        return out
    top = len(lv) - 1
    try:
        return np.stack([row(top, 0, int(d)) for d in rows])
    finally:
        # ``row`` refers to itself, a cycle that refcounting never
        # frees: the rows it made (about 100 MB a call) went only with
        # the cyclic collector, and piled up at a few calls a second
        memo.clear()
        run = None  # noqa: F841 (the closure's cell)


_lock = threading.Lock()
_banks = {}         # (shapes, seed, precision) -> filterbank per pool gulp
_runs = {}          # (shapes, seed, precision, pool run) -> concatenation


def _history(cfg, seed, k, precision):
    """(nfine, (nhist + 1) nout): the filterbanks of gulps k - nhist to
    k concatenated, zeros before the stream's first; the gulps rebuilt
    from the seed by the traffic generator's own functions, each pool
    gulp's filterbank computed once."""
    import traffic
    mix = traffic.load(cfg['traffic'])
    key = (shapes(cfg), seed, precision)
    with _lock:
        if key not in _banks:
            pool = traffic.make_pool(cfg, mix, seed)
            with ThreadPoolExecutor(max_workers=len(pool)) as ex:
                _banks[key] = list(ex.map(
                    lambda g: _filterbank(g, cfg, precision), pool))
            del pool
        banks = _banks[key]
        nout, md = shapes(cfg)[-2:]
        order = traffic.replay_order(mix, seed)
        run = tuple(int(order[g % len(order)]) if g >= 0 else None
                    for g in range(k - -(-md // nout), k + 1))
        x = _runs.get(key + (run,))
        if x is None:
            x = _runs[key + (run,)] = np.concatenate(
                [banks[i] if i is not None else np.zeros_like(banks[0])
                 for i in run], axis=1)
    return x


def reference(gulps, idx, cfg, precision='float64'):
    """What ``take`` should hold for the product ``idx[0]``: the DM
    rows ``idx[2:]`` at the times of its own gulp (``gulps``, the
    pool's, which the history rebuilds with those before it).
    ``precision='bfloat16'`` is the control (:func:`_filterbank`)."""
    k, seed = int(idx[0]), int(idx[1])
    assert len(gulps) == gulps_per_product(cfg)
    nout, md = shapes(cfg)[-2:]
    f0, df = fine_band(cfg)
    x = _history(cfg, seed, k, precision)
    return fdmt_rows(x, idx[2:], md, f0, df)[:, -nout:]


def compare(got, want):
    """('rel_err', value): worst error over the reference's peak."""
    got = np.asarray(got, dtype=np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return 'rel_err', float('inf')
    return 'rel_err', float(np.max(np.abs(got - want)) /
                            (np.max(np.abs(want)) or 1.0))


def fdmt_work(cfg):
    """The FDMT's least work a gulp, over the gulp's new filterbank
    samples alone (recomputing the history's counts as waste): the
    first level reads the filterbank and writes every channel's rows,
    each later level reads two rows for each it writes, every row
    ``nout`` float32 samples; one add a sample of every row written."""
    nfine, nout, md = shapes(cfg)[-3:]
    lv = levels(nfine, md, *fine_band(cfg))
    first = sum(lv[0][1])
    later = sum(sum(nd) for _subs, nd in lv[1:])
    return {'bytes': 4.0 * nout * (nfine + first + 3 * later),
            'flops': float(nout * (first + later))}


def work(cfg):
    """Per gulp, from the shapes alone, whatever implements it: the
    gulp's complex samples (what real time is measured in); its ci8
    bytes in, and the FDMT's least bytes, the product's among them;
    5 N log2 N flops an 8-point transform, four a sample to detect and
    sum it, and the FDMT's adds."""
    ntime, nchan, npol, nfft = shapes(cfg)[:4]
    samples = ntime * nchan * npol * nfft
    fdmt = fdmt_work(cfg)
    return {'samples': samples,
            'bytes': 2.0 * samples + fdmt['bytes'],
            'flops': 5.0 * nfft * math.log2(nfft) * samples / nfft
            + 4.0 * samples + fdmt['flops'],
            'int8_ops': 0.0}
