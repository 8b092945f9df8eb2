"""Coherent dedispersion and fold, a pulsar-timing backend's coherent
mode: CoherentDedispersionStage -> DetectStage('stokes') -> FoldStage,
fused into one program (stages.match_coherent_fold) or run as the
stages, against a float64 reference written here from the equations
and importing nothing of the program.  Sizes are small; the functions
and the blocks are the ones the chip runs."""

import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu.dtype import DataType
from bifrost_tpu.stages import (CoherentDedispersionStage, DetectStage,
                                FoldStage, walk_headers, compose_stages)
from bifrost_tpu.telemetry import counters

from util import GatherSink, NumpySourceBlock

#: the module (``bifrost_tpu.ops.fft`` is also the name of a function
#: the package re-exports)
F = importlib.import_module('bifrost_tpu.ops.fft')
FOLD = importlib.import_module('bifrost_tpu.ops.fold')

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: PSR J1903+0327 at 1.5625 MHz: DM, and a period of 335923/100 samples
DM, PNUM, PDEN, NBIN = 297.5, 335923, 100, 2048
#: the dispersion constant, s MHz^2 / (pc cm^-3)
KDM = 4.148808e3
#: the lowest channel's centre and the channel width, MHz
F0, DF = 1100.78125, 1.5625

#: Tolerance of the program against float64, as the worst |got - want|
#: over the reference's peak.  Every transform is float32 (on the CPU
#: exactly; on the chip three bf16 passes a product, ~1.5e-6 for one
#: transform, PERF.md section 6); the chirp is float32 planes of a
#: float64 phase; a bin sums a few hundred samples, whose errors are
#: independent, so the profile's error is below one sample's.  The
#: program reads 2e-7 to 4e-7 here; one bf16 pass a product (the
#: control) reads 1e-3 and more.
TOL = 1e-5


# ---------------------------------------------------------------------------
# the float64 reference, from the equations
# ---------------------------------------------------------------------------

def _chirp(chan, nfft):
    """H*(f), H(f) = exp(+2 pi i 1e6 KDM DM f^2 / (f_c^2 (f_c + f))) the
    medium's transfer function in numpy's convention, f and f_c in MHz,
    at numpy's transform bins."""
    fc = F0 + DF * chan
    f = np.fft.fftfreq(nfft, d=1.0 / DF)
    return np.exp(-2j * np.pi * 1e6 * KDM * DM * f ** 2
                  / (fc ** 2 * (fc + f)))


def _bins(frame, new, origin):
    """floor(nbin ((pden n) mod pnum) / pnum) of the frame's samples
    n = frame * new + j + origin, in Python's and numpy's int64."""
    j = np.arange(new, dtype=np.int64)
    r0 = (PDEN * (frame * new + origin)) % PNUM
    return (NBIN * ((r0 + PDEN * j) % PNUM)) // PNUM


def _transforms(bf16):
    if not bf16:
        return (lambda v: np.fft.fft(v, axis=-1),
                lambda v: np.fft.ifft(v, axis=-1) * v.shape[-1])
    import ml_dtypes

    def rnd(x):
        return x.astype(np.float32).astype(ml_dtypes.bfloat16) \
            .astype(np.float64)

    def fwd(v):
        # one level of a DFT matrix product with both operands rounded
        # to bfloat16: one bf16 pass of the MXU
        n = v.shape[-1]
        i = np.arange(n)
        m = np.exp(-2j * np.pi * ((i[:, None] * i[None, :]) % n) / n)
        mr, mi = rnd(m.real), rnd(m.imag)
        vr, vi = rnd(v.real), rnd(v.imag)
        return (vr @ mr - vi @ mi) + 1j * (vr @ mi + vi @ mr)
    return fwd, lambda v: np.conj(fwd(np.conj(v)))


def _dedispersed(v, chan, nfft, overlap, bf16=False):
    """(npol, nsamp) complex128 of one channel -> (npol, new): each
    window keeps its outputs from overlap // 2 on, for a step."""
    step = nfft - overlap
    nwin = (v.shape[-1] - overlap) // step
    w = np.stack([v[:, i * step:i * step + nfft] for i in range(nwin)], 1)
    fwd, inv = _transforms(bf16)
    z = inv(fwd(w) * _chirp(chan, nfft))[..., overlap // 2:
                                         overlap // 2 + step]
    return z.reshape(v.shape[0], nwin * step)


def _stokes(z):
    x, y = z
    xy = x * np.conj(y)
    return np.stack([abs(x) ** 2 + abs(y) ** 2, abs(x) ** 2 - abs(y) ** 2,
                     2 * xy.real, -2 * xy.imag])


def reference(raw, frames, nfft, overlap, bf16=False):
    """Profiles (nframe, nchan, 4, nbin) of int8 voltages ``raw``
    (nframe, nchan, 2, nsamp, 2), frame t being frame ``frames[t]`` of
    its stream.  The header's time origin (:func:`_header`) is a
    block's first sample, so output j of a frame is its sample
    overlap // 2 + j."""
    v = raw[..., 0].astype(np.float64) + 1j * raw[..., 1]
    out = []
    for t, frame in enumerate(frames):
        per = []
        for c in range(v.shape[1]):
            s = _stokes(_dedispersed(v[t, c], c, nfft, overlap, bf16))
            b = _bins(frame, s.shape[-1], overlap // 2)
            per.append([np.bincount(b, weights=s[k], minlength=NBIN)
                        for k in range(4)])
        out.append(per)
    return np.array(out)


def rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got) - want))
                 / np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# the program's pieces
# ---------------------------------------------------------------------------

def _header(nchan, nsamp):
    return {'name': 'fold', 'time_tag': 0,
            '_tensor': {'shape': [-1, nchan, 2, nsamp], 'dtype': 'ci8',
                        'labels': ['time', 'freq', 'pol', 'fine_time'],
                        'scales': [[0, 1], [F0, DF], [0, 1],
                                   [0, 1e-6 / DF]],
                        'units': ['s', 'MHz', None, 's']}}


def _stages(nfft, overlap):
    return [CoherentDedispersionStage(DM, nfft, overlap),
            DetectStage('stokes'), FoldStage(NBIN, PNUM, PDEN)]


def _voltages(shape, seed):
    return np.random.default_rng(seed).integers(-64, 64, shape,
                                                dtype=np.int8)


def _plan(nfft, overlap, nchan, nsamp, substitute, ntime=1):
    stages = _stages(nfft, overlap)
    headers = walk_headers(stages, _header(nchan, nsamp))
    fn, info = compose_stages(stages, headers, (ntime, nchan, 2, nsamp, 2),
                              np.dtype('int8'), substitute=substitute)
    return stages, fn, info


def _operands(stages, frame, nframe):
    """Every stage's operands for the gulp of ``nframe`` frames from
    ``frame``, in the chain's order, as FusedBlock passes them."""
    return tuple(op for s in stages for op in s.gulp_operands(frame, nframe))


@pytest.mark.parametrize('n', [1 << 15, 1 << 16])
def test_long_fft_inverse_against_float64(n):
    """The three-level path in the inverse direction: numpy's ifft times
    n (cuFFT's unnormalised inverse), through the function the chip
    runs; fft_path chooses it for an inverse as for a forward."""
    import jax
    import jax.numpy as jnp
    factors = F.long_factors(n)
    assert F.fft_path((2, n), [1], inverse=True) == \
        {'path': 'long', 'factors': list(factors), 'precision': 'high'}
    rng = np.random.default_rng(n)
    x = rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n))
    want = np.fft.ifft(x, axis=-1) * n
    yr, yi = jax.jit(lambda a, b: F.long_fft(a, b, factors, inverse=True))(
        jnp.asarray(x.real, jnp.float32), jnp.asarray(x.imag, jnp.float32))
    got = np.asarray(yr).astype(np.float64) + 1j * np.asarray(yi)
    assert rel_err(got, want) < 2e-6
    y = jax.jit(lambda z: F.fftn_dispatch(z, [1], inverse=True))(
        jnp.asarray(x, jnp.complex64))
    assert rel_err(np.asarray(y), want) < 2e-6


def test_chirp_in_the_levels_order_dedisperses_as_in_frequency_order():
    """On the three-level path the spectrum stays in the levels' order
    between the transforms and the chirp is held in it
    (ops.dedisp.in_transform_order, from the path record): two windows
    through that overlap-save give the series that jnp.fft's gives with
    the chirp in frequency order, and float64's; on jnp.fft's path the
    chirp is as it was made."""
    import jax
    import jax.numpy as jnp
    from bifrost_tpu.ops import dedisp
    n, chan = 1 << 15, 2
    path = dedisp.transform_path(n)
    assert path['order'] == dedisp.transform_path(n, True)['order'] == \
        'levels'
    n1, n2, n3 = path['factors']
    h = dedisp.chirp(DM, [F0 + DF * chan], DF, n)
    held = dedisp.in_transform_order(h, n)
    k1, k2, k3 = np.unravel_index(np.arange(n), (n1, n2, n3))
    assert np.array_equal(held[0], h[0, k1 + n1 * k2 + n1 * n2 * k3])
    assert 'order' not in dedisp.transform_path(4096)
    short = dedisp.chirp(DM, [F0], DF, 4096)
    assert np.array_equal(dedisp.in_transform_order(short, 4096), short)
    rng = np.random.default_rng(17)
    w = rng.integers(-64, 64, (2, n)) + 1j * rng.integers(-64, 64, (2, n))
    want = np.fft.ifft(np.fft.fft(w, axis=-1) * _chirp(chan, n), axis=-1) * n

    def levels(wr, wi, hr, hi):
        def t(ar, ai, inverse):
            return F.long_fft(ar, ai, (n1, n2, n3), precision='highest',
                              natural=False, inverse=inverse)
        yr, yi = t(wr, wi, False)
        return t(yr * hr - yi * hi, yr * hi + yi * hr, True)

    def frequency(z, hz):
        return jnp.fft.ifft(jnp.fft.fft(z, axis=-1) * hz, axis=-1) * n
    planes = [jnp.asarray(p, jnp.float32)
              for p in (w.real, w.imag, held.real, held.imag)]
    zr, zi = jax.jit(levels)(*planes)
    got = np.asarray(zr).astype(np.float64) + 1j * np.asarray(zi)
    xla = np.asarray(jax.jit(frequency)(jnp.asarray(w, jnp.complex64),
                                        jnp.asarray(h, jnp.complex64)))
    assert rel_err(got, want) < 2e-6 and rel_err(xla, want) < 2e-6
    assert rel_err(got, xla) < 4e-6
    # the chirp left in frequency order is another filter
    zr, zi = jax.jit(levels)(*planes[:2], *(jnp.asarray(p, jnp.float32)
                                           for p in (h.real, h.imag)))
    assert rel_err(np.asarray(zr) + 1j * np.asarray(zi), want) > 0.1


@pytest.mark.parametrize('substitute', [True, False],
                         ids=['fused', 'stages'])
@pytest.mark.parametrize('nfft,overlap', [(1 << 15, 4096), (4096, 512)],
                         ids=['long_path', 'short_path'])
def test_chain_against_the_float64_reference(nfft, overlap, substitute):
    """One gulp of two frames of three channels at frames 7 and 8 of
    the stream, from its int8 pairs and from its int16 words: the one
    program (no spectrum in HBM), or the three stages composed, within
    TOL of float64.  The bf16 control of the transforms is not."""
    import jax
    nchan, nwin = 3, 2
    nsamp = overlap + nwin * (nfft - overlap)
    stages, fn, info = _plan(nfft, overlap, nchan, nsamp, substitute,
                             ntime=2)
    path = 'long' if nfft > F.LONG_NFFT else 'xla'
    assert info['fft']['path'] == info['fft']['inverse']['path'] == path
    # the spectrum between the transforms, and the chirp, in the
    # levels' order on the three-level path
    assert info['fft'].get('order') == info['fft']['inverse'].get('order') \
        == ('levels' if path == 'long' else None)
    assert info['impl'] == ('coherent-fold' if substitute else 'xla-fused')
    raw = _voltages((2, nchan, 2, nsamp, 2), seed=nfft)
    (starts,) = stages[2].gulp_operands(7, 2)
    assert starts.dtype == np.int32 and starts.shape == (2,)
    want = reference(raw, [7, 8], nfft, overlap)
    run = jax.jit(fn)
    ops = _operands(stages, 7, 2)
    got = np.asarray(run(raw, *ops))
    assert got.shape == (2, nchan, 4, NBIN) and got.dtype == np.float32
    assert rel_err(got, want) < TOL
    from bifrost_tpu.stages import from_words
    words = raw.view(np.int16).reshape(-1)
    run_words = jax.jit(from_words(fn, (2, nchan, 2, nsamp, 2)))
    assert rel_err(run_words(words, *ops), want) < TOL
    if nfft == 4096 and substitute:
        # the control: one bf16 pass a product, forward and inverse
        control = reference(raw[:1], [7], nfft, overlap, bf16=True)
        assert rel_err(control, want[:1]) > 50 * TOL


def _dispersed_pulse(nsamp, at, chan, amp=60.0):
    """A pulse whose centre frequency arrives at sample ``at``, as the
    medium disperses it, made in the time domain from the delay
    equation alone (nothing of H): the offset f from the channel's
    centre f_c arrives KDM DM ((f_c + f)^-2 - f_c^-2) s after it, so the
    higher frequencies first, and the voltage at time t is the tone of
    the f that arrives then, of phase 2 pi int f dt (exact:
    2 pi (2 A u - f_c t), A = 1e6 KDM DM, u = sqrt(t / A + f_c^-2), t
    in microseconds).  Complex (nsamp,), zero outside the channel's
    sweep."""
    a = 1e6 * KDM * DM
    fc = F0 + DF * chan
    t = (np.arange(nsamp) - at) / DF
    first = a * ((fc + DF / 2) ** -2 - fc ** -2)     # the top edge
    last = a * ((fc - DF / 2) ** -2 - fc ** -2)      # the bottom edge
    inside = (t > first) & (t < last)
    u = np.sqrt(np.clip(t, first, last) / a + fc ** -2)
    return np.where(inside, amp * np.exp(2j * np.pi * (2 * a * u - fc * t)),
                    0)


@pytest.mark.parametrize('at', [16000, 32768 - 1000],
                         ids=['mid_window', 'near_a_windows_end'])
def test_a_dispersed_pulse_comes_out_as_one_sample(at):
    """A pulse dispersed in the time domain (:func:`_dispersed_pulse`,
    4500 samples long at this channel) and quantised to ci8, in a block
    of two windows: the dedispersion stage gives it back with >= 99 % of
    its power in one sample, the one its centre frequency arrived in,
    and the fused chain puts >= 99 % of Stokes I in that sample's bin.
    That checks the chirp's sign (were it the medium's own, the pulse
    would come out dispersed twice) and the overlap-save's edges: near
    the end of window 0, within half the chirp's span, the pulse's
    later half lies past the window, so only the next window, which
    keeps its outputs from overlap // 2 on, can give it whole."""
    import jax
    from bifrost_tpu.ops.dedisp import chirp_span_samples
    nfft, overlap, chan = 1 << 15, 8192, 1
    nsamp = overlap + 2 * (nfft - overlap)
    if at > nfft // 2:      # within half the chirp's span of the end
        assert nfft - at < chirp_span_samples(DM, F0 + DF * chan, DF) / 2
    disp = _dispersed_pulse(nsamp, at, chan)
    raw = np.zeros((1, 2, 2, nsamp, 2), np.int8)
    raw[0, chan, :, :, 0] = np.rint(disp.real)
    raw[0, chan, :, :, 1] = np.rint(disp.imag)
    stages = _stages(nfft, overlap)
    walk_headers(stages, _header(2, nsamp))
    ded = stages[0].build({'shape': [1, 2, 2, nsamp, 2],
                           'dtype': DataType('ci8'), 'reim': True})
    z = np.asarray(jax.jit(ded)(raw, *stages[0].gulp_operands(0, 1)))
    power = np.abs(z[0, chan, 0]) ** 2
    assert np.argmax(power) == at - overlap // 2
    assert power.max() >= 0.99 * power.sum()
    _s, fn, _i = _plan(nfft, overlap, 2, nsamp, True)
    prof = np.asarray(jax.jit(fn)(raw, *_operands(_s, 0, 1)))[0, chan, 0]
    # the header's origin is the block's first sample: the pulse is at
    # n = at, whichever output holds it
    assert np.argmax(prof) == (NBIN * ((PDEN * at) % PNUM)) // PNUM
    assert prof.max() >= 0.99 * prof.sum()


@pytest.mark.parametrize('rule', [(1200, 256, 1009, 3), (700, 2048, PNUM,
                                                         PDEN)],
                         ids=['coarse', 'deployed'])
def test_fold_bins_are_the_integer_rule(rule):
    """Each sample folded alone (a row of an identity) lands in the
    bin the rule gives it, from start residues on bin edges, one short
    of them and at the period's end, so that gulps cross bin edges at
    every place."""
    import jax
    nsamp, nbin, pnum, pden = rule
    r = FOLD.FoldRule(nsamp, nbin, pnum, pden)
    edge = -(-5 * pnum // nbin)          # the first residue of bin 5
    run = jax.jit(lambda x, r0: FOLD.fold(x, r0, r))
    for r0 in (0, 1, edge, edge - 1, pnum - 1, pnum // 2):
        got = np.asarray(run(np.eye(nsamp, dtype=np.float32), np.int32(r0)))
        want = (nbin * ((r0 + pden * np.arange(nsamp)) % pnum)) // pnum
        assert np.array_equal(got.argmax(axis=1), want), r0
        assert np.array_equal(got.sum(axis=1), np.ones(nsamp))


#: the kernel's cut at small shapes: (nsamp, nbin, pnum, pden) with a
#: turn of 337 samples (not a multiple of 128, a window of 512) and one
#: of 256 (a multiple, a window of 384), a few dozen rotations each
_CUT_RULES = {'turn_337': (8192, 256, 1009, 3),
              'turn_256': (6400, 128, 2555, 10)}


def _cut_cases():
    for name, (nsamp, nbin, pnum, pden) in _CUT_RULES.items():
        seeded = np.random.default_rng(pnum).integers(2, pnum - 1, 2)
        for rows in (8, 16):
            for r0 in (0, 1, pnum - 1) + tuple(int(r) for r in seeded):
                yield pytest.param(name, rows, r0,
                                   id='%s-rows%d-r%d' % (name, rows, r0))


@pytest.mark.parametrize('rule,rows,r0', _cut_cases())
def test_kernel_cut_is_the_xla_cut_and_the_rule(rule, rows, r0):
    """The kernel that cuts the rotations from the series where it lies
    (ops.fold._kernel_cut, interpreted here) gives the XLA cut's
    partial rotations and, through fold_bins, the integer rule's
    profiles, to float32's rounding, from start residues 0, 1, pnum - 1
    and two seeded: the first rotation begins before the series (its
    first sample lies before it from a residue of pden or more), the
    last runs past its end, and the last windows are clipped to the
    series' end."""
    import jax.numpy as jnp
    nsamp, nbin, pnum, pden = _CUT_RULES[rule]
    r = FOLD.FoldRule(nsamp, nbin, pnum, pden)
    W = FOLD._window(r)
    assert nsamp % 128 == 0 and W % 128 == 0 and W >= r.turn + 127
    assert (r.turn % 128 == 0) == (rule == 'turn_256')
    start, w = FOLD._turn_weights(np.int32(r0), r, W)
    start = np.asarray(start)
    assert (start[0] < 0) == (r0 >= pden) and start[-1] + r.turn > nsamp
    assert start[-1] > nsamp - W        # a window clipped to the end
    x = np.random.default_rng(r0).standard_normal((rows, nsamp)) \
        .astype(np.float32)
    got = np.asarray(FOLD._kernel_cut(jnp.asarray(x), jnp.asarray(start), w,
                                      r, interpret=True))
    assert got.shape == (r.nclass, rows, W)
    assert not got[..., r.turn:].any()
    xla = np.asarray(FOLD.fold_turns(jnp.asarray(x), np.int32(r0), r))
    np.testing.assert_allclose(got[..., :r.turn], xla, rtol=1e-6,
                               atol=1e-5)
    prof = np.asarray(FOLD.fold_bins(jnp.asarray(got[..., :r.turn]), r))
    b = FOLD.phase_bins(r0, nsamp, nbin, pnum, pden)
    want = np.stack([np.bincount(b, weights=row, minlength=nbin)
                     for row in x.astype(np.float64)])
    assert rel_err(prof, want) < 1e-6


@pytest.mark.parametrize('cut,nchan,on_tpu', [
    ('kernel', 2, True), ('xla', 3, True), ('xla', 2, False)],
    ids=['kernel', 'rows_not_tiles_of_eight', 'off_the_chip'])
def test_the_plan_names_the_cut_it_runs(cut, nchan, on_tpu, monkeypatch):
    """The plan's record says which cut folds the gulp
    (ops.fold.cut_path): the kernel on a TPU (the backend's answer
    steered here, the kernel interpreted), the XLA cut where the
    Stokes rows, four a channel, do not come in tiles of eight, or off
    the chip; each chain is within TOL of float64."""
    import functools
    import jax
    monkeypatch.setattr(FOLD, '_on_tpu', lambda: on_tpu)
    monkeypatch.setattr(FOLD, '_kernel_cut',
                        functools.partial(FOLD._kernel_cut, interpret=True))
    nfft, overlap = 4096, 512
    nsamp = overlap + 2 * (nfft - overlap)
    stages, fn, info = _plan(nfft, overlap, nchan, nsamp, True)
    assert info['impl'] == 'coherent-fold' and info['fold'] == cut
    assert FOLD.cut_path(nchan * 4, stages[2].rule) == cut
    raw = _voltages((1, nchan, 2, nsamp, 2), seed=nchan)
    got = np.asarray(jax.jit(fn)(raw, *_operands(stages, 3, 1)))
    assert rel_err(got, reference(raw, [3], nfft, overlap)) < TOL


def test_residues_are_exact_far_into_a_stream():
    """A frame's start residue from its offset in Python's integers,
    however far into the stream (int64 would not hold pden * n)."""
    new = 917504
    for frame in (0, 1, 17, 10 ** 9, 2 ** 40 + 3):
        got = FOLD.residues(frame, 3, new, PNUM, PDEN)
        assert list(got) == [(PDEN * (frame + t) * new) % PNUM
                             for t in range(3)]
    # an origin before the stream's first sample (guppi-fold's: the
    # dedispersed output starts half an overlap before the first new
    # sample): residues in [0, pnum) all the same
    got = FOLD.residues(0, 2, new, PNUM, PDEN, origin=-4096)
    assert list(got) == [(-PDEN * 4096) % PNUM,
                         (PDEN * (new - 4096)) % PNUM]
    assert 0 <= got.min() and got.max() < PNUM
    with pytest.raises(ValueError):
        FOLD.FoldRule(1 << 25, NBIN, PNUM, PDEN)      # pden n overflows


def test_one_compile_for_every_phase():
    """Two gulps at different phases run one compiled program: the
    start residue is an argument, not a constant."""
    import jax
    from bifrost_tpu.telemetry import spans
    spans.watch_jax()
    nfft, overlap = 4096, 512
    stages, fn, _ = _plan(nfft, overlap, 2, nfft, True)
    raw = _voltages((1, 2, 2, nfft, 2), seed=3)
    run = jax.jit(fn)
    a = np.asarray(run(raw, *_operands(stages, 0, 1)))
    n = counters.get('jit.compiles')
    b = np.asarray(run(raw, *_operands(stages, 5, 1)))
    assert counters.get('jit.compiles') == n
    assert not np.allclose(a, b)
    assert rel_err(b, reference(raw, [5], nfft, overlap)) < TOL


class _Frames(NumpySourceBlock):
    """One frame a gulp, as the benchmark's source writes them."""

    def on_data(self, reader, ospans):
        arr = reader.read(1)
        if arr is None:
            return [0]
        dst = ospans[0].data.as_numpy()
        np.copyto(dst.view(np.uint8), arr.view(np.uint8))
        return [1]


@pytest.mark.parametrize('form', ['fused', 'blocks'])
def test_pipeline_against_the_reference(form):
    """host ring -> copy('tpu') -> the chain -> accumulate(2) ->
    copy('system') -> sink: one fused block, or a block a stage.  Every
    product is the sum of its frames' profiles, each at its own phase;
    the counters say what ran and count every new sample once and the
    overlap never."""
    nfft, overlap, nchan, nwin, nint = 4096, 512, 2, 2, 2
    nsamp = overlap + nwin * (nfft - overlap)
    new = nsamp - overlap
    raw = _voltages((6, nchan, 2, nsamp, 2), seed=11)
    ci8 = np.dtype([('re', np.int8), ('im', np.int8)])
    gulps = [raw[i:i + 1].view(ci8).reshape(1, nchan, 2, nsamp)
             for i in range(6)]
    counters.reset()
    with bf.Pipeline() as p:
        src = _Frames(gulps, _header(nchan, nsamp), 1)
        blk = bf.blocks.copy(src, space='tpu')
        if form == 'fused':
            blk = bf.blocks.fused(blk, _stages(nfft, overlap))
        else:
            for stage in _stages(nfft, overlap):
                blk = bf.blocks.fused(blk, [stage])
        acc = bf.blocks.accumulate(blk, nint)
        sink = GatherSink(bf.blocks.copy(acc, space='system'))
        p.run()
    out = sink.result()
    assert out.shape == (3, nchan, 4, NBIN)
    want = reference(raw, range(6), nfft, overlap)
    for k in range(3):
        assert rel_err(out[k], want[2 * k:2 * k + 2].sum(0)) < TOL, k
    assert sink.headers[0]['_tensor']['labels'][-1] == 'phase'
    assert counters.get('dedisp.gulps') == 6
    assert counters.get('dedisp.fused_gulps') == (6 if form == 'fused'
                                                  else 0)
    assert counters.get('dedisp.long_gulps') == 0       # 4096 points
    assert counters.get('dedisp.level_order_gulps') == 0
    # the fold counts where it folds what the block dedispersed
    assert counters.get('fold.samples') == \
        (6 * nchan * 2 * new if form == 'fused' else 0)
    # off the chip the fold cuts with XLA (ops.fold.cut_path)
    assert counters.get('fold.gulps') == (6 if form == 'fused' else 0)
    assert counters.get('fold.kernel_gulps') == 0
    assert acc.impl_info['impl'] == ('coherent-fold' if form == 'fused'
                                     else 'xla-fused')


def test_headers_of_the_stages():
    """The dedispersion keeps as many samples as a block has new ones
    and starts the time scale at its sample overlap // 2, which the
    fold counts its samples from; the fold's last axis is the
    phase."""
    stages = _stages(1 << 15, 4096)
    h = walk_headers(stages, _header(3, 4096 + 2 * 28672))
    assert h[1]['_tensor']['shape'] == [-1, 3, 2, 2 * 28672]
    assert h[1]['_tensor']['dtype'] == 'cf32'
    assert h[1]['_tensor']['scales'][-1][0] == pytest.approx(2048e-6 / DF)
    assert stages[2].origin == 2048
    assert stages[0].frame_samples == 3 * 2 * 2 * 28672
    assert h[2]['_tensor']['shape'] == [-1, 3, 4, 2 * 28672]
    t = h[3]['_tensor']
    assert t['shape'] == [-1, 3, 4, NBIN] and t['dtype'] == 'f32'
    assert t['labels'][-1] == 'phase' and t['scales'][-1] == [0, 1 / NBIN]
    with pytest.raises(ValueError):
        walk_headers(_stages(1 << 15, 4096), _header(3, 40000))
    with pytest.raises(KeyError):
        hdr = _header(3, 4096 + 2 * 28672)
        hdr['_tensor']['labels'][1] = 'beam'
        walk_headers(_stages(1 << 15, 4096), hdr)


def test_a_program_without_the_stages_fails_at_chain_build(monkeypatch):
    """The benchmark's chain on a program that lacks the stages (its
    parent commit) raises at chain build, before anything runs, and
    does not hang."""
    import bifrost_tpu.stages as stages_mod
    path = os.path.join(ROOT, 'perfbench', 'configs', 'guppi_fold')
    spec = importlib.util.spec_from_file_location('cfg_guppi_fold',
                                                  path + '.py')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(path + '.json') as f:
        cfg = json.load(f)
    monkeypatch.delattr(stages_mod, 'CoherentDedispersionStage')
    with bf.Pipeline():
        src = _Frames([], mod.header(cfg), 1)
        dev = bf.blocks.copy(src, space='tpu')
        with pytest.raises(ImportError):
            mod.chain(bf, dev, cfg)
