"""Quantized coherent-beamformer engine (ops/beamform.py, the Pallas
kernels in ops/pallas_kernels.py, BeamformBlock and the fused
beamform->detect->integrate substitution in stages.py).

Kernel parity runs in Pallas interpret mode on the CPU test backend.
On the chip, chip_smoke.py phase B compiles every candidate at the
BASELINE.json shape and holds it to the float64 oracle, and the
benchmark's cell ``beamform-tab-replay`` runs the fused chain with a
weight set per channel at a deployment's shape (PR 35; its rehearsal
size is the pipeline test's below).
"""

import os

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu.ops.beamform import (Beamformer, BEAM_CLASSES,
                                      beam_class_rtol,
                                      quantize_weights,
                                      _wide_weight_block)

from util import NumpySourceBlock, GatherSink, simple_header

ci8_np = np.dtype([('re', 'i1'), ('im', 'i1')])


def _weights(B, S, P=None, seed=0):
    rng = np.random.RandomState(seed)
    shape = (B, S) if P is None else (P, B, S)
    return (rng.randn(*shape) + 1j * rng.randn(*shape)) \
        .astype(np.complex64)


def _volt_planes(T, F, P, S, seed=1, lim=64):
    rng = np.random.RandomState(seed)
    re = rng.randint(-lim, lim, (T, F, P, S)).astype(np.int8)
    im = rng.randint(-lim, lim, (T, F, P, S)).astype(np.int8)
    return re, im


def _oracle(re, im, w):
    """float64 einsum oracle: (T, F, P, S) x (P, B, S) -> (T, F, P, B)."""
    x = re.astype(np.float64) + 1j * im.astype(np.float64)
    return np.einsum('tfps,pbs->tfpb', x, w.astype(np.complex128))


# ---------------------------------------------------------------------------
# engine candidates: parity + the exact-int contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('shape', [(8, 2, 1, 8), (16, 4, 2, 16),
                                   (32, 3, 2, 24)])
def test_candidate_parity_multiple_shapes(shape):
    """Every candidate implementation stays inside its accuracy class
    of the float64 oracle at several (T, F, P, S) shapes."""
    T, F, P, S = shape
    B = 6
    w = _weights(B, S, P if P > 1 else None)
    eng = Beamformer(w, accuracy='int8')
    re, im = _volt_planes(T, F, P, S)
    ref = _oracle(re, im, w if w.ndim == 3 else w[None])
    scale = np.max(np.abs(ref))
    bounds = {'xla': 1e-5, 'planar': 1e-3, 'planar_bf16': 8e-3,
              'pallas_bf16': 8e-3, 'int8_wide': 4e-2}
    for name, bound in bounds.items():
        y = np.asarray(eng._jit(name, P)(re, im))
        rel = np.max(np.abs(y - ref)) / scale
        assert rel <= bound, (name, rel)


def test_int8_wide_is_exact_int():
    """The widened-int8 candidate's integer core is bit-identical to
    the numpy int64 oracle — EXACT int32 accumulation, no float
    anywhere before the dequantization scale."""
    import jax.numpy as jnp
    T, F, P, S, B = 16, 3, 2, 24, 5
    w = _weights(B, S, P)
    eng = Beamformer(w, accuracy='int8')
    re, im = _volt_planes(T, F, P, S, lim=127)
    w2 = _wide_weight_block(eng.wr8, eng.wi8)
    yr, yi = Beamformer.int8_planes(jnp.asarray(re), jnp.asarray(im),
                                    jnp.asarray(w2), B)
    r64, i64 = re.astype(np.int64), im.astype(np.int64)
    wr64, wi64 = eng.wr8.astype(np.int64), eng.wi8.astype(np.int64)
    want_r = (np.einsum('tfps,pbs->tfpb', r64, wr64) -
              np.einsum('tfps,pbs->tfpb', i64, wi64))
    want_i = (np.einsum('tfps,pbs->tfpb', r64, wi64) +
              np.einsum('tfps,pbs->tfpb', i64, wr64))
    np.testing.assert_array_equal(np.asarray(yr, np.int64), want_r)
    np.testing.assert_array_equal(np.asarray(yi, np.int64), want_i)


def test_weight_quantization_symmetric_clip():
    """quantize_weights clips at +/-127 (never -128) so the widened
    block's negated -wi8 copy cannot overflow int8."""
    w = np.array([[1.0 + 0j, -1.0 + 1j]], np.complex64)
    wr8, wi8, scale = quantize_weights(w.real.astype(np.float32),
                                       w.imag.astype(np.float32))
    assert wr8.min() >= -127 and wr8.max() <= 127
    assert wi8.min() >= -127 and wi8.max() <= 127
    w2 = _wide_weight_block(wr8[None] if wr8.ndim == 2 else wr8,
                            wi8[None] if wi8.ndim == 2 else wi8)
    assert w2.dtype == np.int8
    assert w2.min() >= -127


# ---------------------------------------------------------------------------
# Pallas kernels (interpret mode) vs the engine's exact-int core
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('shape', [(8, 2, 8, 4), (16, 4, 16, 8)])
def test_pallas_beamform_int8_matches_oracle(shape):
    from bifrost_tpu.ops import pallas_kernels as pk
    T, F, S, B = shape
    rng = np.random.RandomState(3)
    wr = rng.randint(-127, 128, (B, S)).astype(np.int8)
    wi = rng.randint(-127, 128, (B, S)).astype(np.int8)
    re = rng.randint(-127, 128, (T, F, S)).astype(np.int8)
    im = rng.randint(-127, 128, (T, F, S)).astype(np.int8)
    yr, yi = pk.beamform_int8(wr, wi, re, im, interpret=True)
    r64, i64 = re.astype(np.int64), im.astype(np.int64)
    wr64, wi64 = wr.astype(np.int64), wi.astype(np.int64)
    np.testing.assert_array_equal(
        np.asarray(yr, np.int64),
        np.einsum('tfs,bs->tfb', r64, wr64) -
        np.einsum('tfs,bs->tfb', i64, wi64))
    np.testing.assert_array_equal(
        np.asarray(yi, np.int64),
        np.einsum('tfs,bs->tfb', r64, wi64) +
        np.einsum('tfs,bs->tfb', i64, wr64))


def test_pallas_beamform_bf16_within_class():
    from bifrost_tpu.ops import pallas_kernels as pk
    T, F, S, B = 16, 2, 16, 4
    rng = np.random.RandomState(4)
    wr = rng.randn(B, S).astype(np.float32)
    wi = rng.randn(B, S).astype(np.float32)
    re = rng.randint(-64, 64, (T, F, S)).astype(np.int8)
    im = rng.randint(-64, 64, (T, F, S)).astype(np.int8)
    yr, yi = pk.beamform_bf16(wr, wi, re, im, interpret=True)
    x = re.astype(np.float64) + 1j * im.astype(np.float64)
    w = wr.astype(np.float64) + 1j * wi.astype(np.float64)
    ref = np.einsum('tfs,bs->tfb', x, w)
    got = np.asarray(yr) + 1j * np.asarray(yi)
    rel = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert rel <= BEAM_CLASSES['bf16'], rel


def test_pallas_fused_detect_matches_quantized_oracle():
    """beamform_detect: dual-pol beamform -> Stokes -> R-frame
    integrate in one program, vs the float64 oracle built from the
    QUANTIZED weights (the kernel's weights are int8 by construction)."""
    from bifrost_tpu.ops.beamform import fused_detect
    T, F, S, B, R = 16, 3, 8, 4, 4
    w = _weights(B, S)
    eng = Beamformer(w, accuracy='int8')
    rng = np.random.RandomState(6)
    x = np.zeros((T, F, S, 2, 2), np.int8)
    x[...] = rng.randint(-64, 64, x.shape)
    # interpret mode engages automatically off-TPU (_xcorr_interpret)
    out = np.asarray(fused_detect(eng, x, R))
    wq = (eng.wr8.astype(np.float64) +
          1j * eng.wi8.astype(np.float64))[0] * eng.wscale
    volt = x[..., 0].astype(np.float64) + 1j * x[..., 1].astype(np.float64)
    y = np.einsum('tfsp,bs->tfpb', volt, wq)
    bx, by = y[:, :, 0], y[:, :, 1]
    xx, yy = np.abs(bx) ** 2, np.abs(by) ** 2
    xy = bx * np.conj(by)
    st = np.stack([xx + yy, xx - yy, 2 * xy.real, -2 * xy.imag],
                  axis=2)
    ref = st.reshape(T // R, R, F, 4, B).sum(axis=1)
    rel = np.max(np.abs(out - ref)) / np.max(np.abs(ref))
    assert out.shape == (T // R, F, 4, B)
    assert rel < 1e-5, rel


# ---------------------------------------------------------------------------
# the accuracy gate: lossy candidates stay opt-in
# ---------------------------------------------------------------------------

def test_gate_rejects_lossy_candidate_at_default_rtol():
    """The single-pass bf16 candidate (~2^-8 input rounding) fails the
    f32-class gate (rtol 1e-3) at a realistic shape — lossy winners
    cannot race their way into a default-accuracy session."""
    import jax.numpy as jnp
    T, F, P, S, B = 32, 4, 2, 32, 8
    w = _weights(B, S, P)
    eng = Beamformer(w, accuracy='f32')
    re, im = _volt_planes(T, F, P, S)
    rej = jnp.asarray(re)
    imj = jnp.asarray(im)
    keep, had_errors = eng._gate(['xla', 'planar', 'planar_bf16'], P,
                                 lambda: (rej, imj))
    assert not had_errors
    assert 'xla' in keep and 'planar' in keep
    assert 'planar_bf16' not in keep


def test_candidate_eligibility_per_class():
    """A class that does not admit a lossy candidate's error excludes
    it from the race outright; int candidates additionally need int
    input."""
    w = _weights(4, 8, 2)
    assert Beamformer(w, accuracy='f32')._candidates(True) == \
        ['xla', 'planar']
    bf16 = Beamformer(w, accuracy='bf16')._candidates(True)
    assert 'planar_bf16' in bf16 and 'int8_wide' not in bf16
    # the Pallas bf16 kernel races only where it compiles natively
    assert ('pallas_bf16' in bf16) == Beamformer._pallas_raceable()
    i8 = Beamformer(w, accuracy='int8')._candidates(True)
    assert 'int8_wide' in i8
    # float input can never feed the int8 kernels
    assert 'int8_wide' not in Beamformer(
        w, accuracy='int8')._candidates(False)


def test_gate_rtol_env_override(monkeypatch):
    monkeypatch.setenv('BF_BEAM_GATE_RTOL', '0.5')
    assert beam_class_rtol('f32') == 0.5
    monkeypatch.delenv('BF_BEAM_GATE_RTOL')
    assert beam_class_rtol('f32') == BEAM_CLASSES['f32']
    # a non-default bound is part of the probe-cache key
    w = _weights(4, 8)
    eng = Beamformer(w, accuracy='f32')
    k_default = eng._key((8, 2, 1, 8), 'int8', True)
    monkeypatch.setenv('BF_BEAM_GATE_RTOL', '0.5')
    k_wide = eng._key((8, 2, 1, 8), 'int8', True)
    assert k_default != k_wide and 'gate_rtol' in k_wide


def test_bf_beam_impl_forces_candidate(monkeypatch):
    """BF_BEAM_IMPL forces any candidate unconditionally — bypassing
    both the race and the gate (the operator's override)."""
    monkeypatch.setenv('BF_BEAM_IMPL', 'int8_wide')
    w = _weights(4, 8, 2)
    eng = Beamformer(w, accuracy='f32')
    assert eng._force == 'int8_wide'
    re, im = _volt_planes(8, 2, 2, 8)
    y = np.asarray(eng(re, im))
    # prewarm records the forced choice (the block path)
    assert eng.prewarm(8, 2, npol=2) == 'int8_wide'
    ref = _oracle(re, im, w)
    rel = np.max(np.abs(y - ref)) / np.max(np.abs(ref))
    assert rel <= BEAM_CLASSES['int8']
    # the explicit impl= argument does the same
    eng2 = Beamformer(w, accuracy='f32', impl='planar')
    assert eng2._force == 'planar'


def test_invalid_accuracy_and_weights_rejected():
    with pytest.raises(ValueError):
        Beamformer(_weights(4, 8), accuracy='f16')
    with pytest.raises(ValueError):
        Beamformer(np.zeros(4, np.complex64))


# ---------------------------------------------------------------------------
# BeamformBlock in a pipeline: standalone, fused substitution,
# macro-gulp K>1, mesh sharding
# ---------------------------------------------------------------------------

def _ci8_gulps(T, F, S, P, n=1, seed=5, lim=32):
    rng = np.random.RandomState(seed)
    gulps = []
    for _ in range(n):
        raw = np.zeros((T, F, S, P), dtype=ci8_np)
        raw['re'] = rng.randint(-lim, lim, raw.shape)
        raw['im'] = rng.randint(-lim, lim, raw.shape)
        gulps.append(raw)
    return gulps


def _run_block_chain(gulps, hdr, w, T, accuracy='int8', gulp_batch=1,
                     mesh=None, impl=None, fused_chain=None,
                     name='Beam'):
    import contextlib
    from bifrost_tpu.telemetry import counters
    counters.reset()
    scope = bf.block_scope(mesh=mesh) if mesh is not None \
        else contextlib.nullcontext()
    with bf.Pipeline(gulp_batch=gulp_batch) as p:
        src = NumpySourceBlock([g.copy() for g in gulps], hdr,
                               gulp_nframe=T)
        with scope:
            b = bf.blocks.copy(src, space='tpu')
            if fused_chain is not None:
                b = bf.blocks.fused(b, fused_chain, name=name)
            else:
                b = bf.blocks.beamform(b, w, accuracy=accuracy,
                                       impl=impl, name=name)
        b = bf.blocks.copy(b, space='system')
        sink = GatherSink(b)
        p.run()
    return sink.result(), counters.snapshot()


def test_block_perpol_matches_oracle():
    T, F, S, P, B = 16, 4, 8, 2, 4
    w = _weights(B, S, P)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'])
    out, _ = _run_block_chain(_ci8_gulps(T, F, S, P), hdr, w, T)
    raw = _ci8_gulps(T, F, S, P)[0]
    ref = np.einsum('tfsp,pbs->tfpb',
                    raw['re'].astype(np.float64) +
                    1j * raw['im'].astype(np.float64),
                    w.astype(np.complex128))
    assert out.shape == (T, F, P, B)
    rel = np.max(np.abs(out - ref)) / np.max(np.abs(ref))
    assert rel <= BEAM_CLASSES['int8'], rel


def test_block_folded_pol_single_beam_axis():
    """(B, S*P) weights fold pol into the contraction: output labels
    ['time', 'freq', 'beam']."""
    T, F, S, P, B = 8, 2, 4, 2, 3
    w = _weights(B, S * P)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'])
    out, _ = _run_block_chain(_ci8_gulps(T, F, S, P), hdr, w, T)
    raw = _ci8_gulps(T, F, S, P)[0]
    x = (raw['re'].astype(np.float64) +
         1j * raw['im'].astype(np.float64)).reshape(T, F, S * P)
    ref = np.einsum('tfn,bn->tfb', x, w.astype(np.complex128))
    assert out.shape == (T, F, B)
    rel = np.max(np.abs(out - ref)) / np.max(np.abs(ref))
    assert rel <= BEAM_CLASSES['int8'], rel


def test_block_macro_gulp_batches_without_fallback():
    """BeamformBlock is macro-gulp eligible: at K=4 the block runs
    batched dispatches (no macro.fallback.* for it) and the output is
    identical to the K=1 stream."""
    T, F, S, P, B = 16, 2, 8, 2, 4
    w = _weights(B, S, P)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'])
    gulps = _ci8_gulps(T, F, S, P, n=8)
    base, _ = _run_block_chain(gulps, hdr, w, T, name='BeamK1')
    batched, snap = _run_block_chain(gulps, hdr, w, T, gulp_batch=4,
                                     name='BeamK4')
    np.testing.assert_array_equal(batched, base)
    # the beamform block itself batched: 8 logical gulps in 2 dispatches
    disp = sum(v for k, v in snap.items()
               if 'BeamK4' in k and k.endswith('.dispatches'))
    glp = sum(v for k, v in snap.items()
              if 'BeamK4' in k and k.endswith('.gulps'))
    assert glp == 8 and disp <= 2, (disp, glp)
    # the only fallback reason in the chain is 'block' (the host
    # source/sink, normal per BF-I161) — the beamform block itself
    # never fell back (no overlap/nonlinear/dynamic/... counters)
    bad = {k: v for k, v in snap.items()
           if k.startswith('macro.fallback.') and v > 0 and
           k not in ('macro.fallback.block',
                     'macro.fallback.multi_reader_retired')}
    assert not bad, bad


def test_block_mesh_sharded_matches_and_zero_reshard():
    """Mesh-sharded execution (frame-local plan — beamforming is
    time-concat equivariant): output matches single-device and the
    steady state pays no reshard."""
    from bifrost_tpu.parallel import create_mesh
    T, F, S, P, B = 16, 2, 8, 2, 4
    w = _weights(B, S, P)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'])
    gulps = _ci8_gulps(T, F, S, P, n=4)
    base, _ = _run_block_chain(gulps, hdr, w, T, name='BeamSingle')
    mesh = create_mesh({'sp': 8})
    meshed, snap = _run_block_chain(gulps, hdr, w, T, mesh=mesh,
                                    name='BeamMesh')
    np.testing.assert_allclose(meshed, base, rtol=1e-5, atol=1e-5)
    # zero-reshard assertion on the frame-local path: only the prewarm
    # zeros gulp may relayout
    assert snap.get('mesh.reshards', 0) <= 1, snap


def test_fused_substitution_engages_and_matches(monkeypatch):
    """BF_BEAM_FUSED=force substitutes the fused Pallas kernel
    (interpret mode off-TPU) for the beamform->stokes->integrate
    chain; output matches the quantized-weights oracle."""
    from bifrost_tpu.stages import (BeamformStage, DetectStage,
                                    ReduceStage)
    monkeypatch.setenv('BF_BEAM_FUSED', 'force')
    T, F, S, P, B, R = 16, 2, 8, 2, 4, 4
    w = _weights(B, S)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'])
    gulps = _ci8_gulps(T, F, S, P)
    chain = [BeamformStage(w, accuracy='int8'),
             DetectStage('stokes', axis='pol'),
             ReduceStage('time', R)]
    out, _ = _run_block_chain(gulps, hdr, w, T, fused_chain=chain,
                              name='BeamFused')
    eng = Beamformer(w, accuracy='int8')
    wq = (eng.wr8.astype(np.float64) +
          1j * eng.wi8.astype(np.float64))[0] * eng.wscale
    raw = gulps[0]
    x = raw['re'].astype(np.float64) + 1j * raw['im'].astype(np.float64)
    y = np.einsum('tfsp,bs->tfpb', x, wq)
    bx, by = y[:, :, 0], y[:, :, 1]
    xx, yy = np.abs(bx) ** 2, np.abs(by) ** 2
    xy = bx * np.conj(by)
    st = np.stack([xx + yy, xx - yy, 2 * xy.real, -2 * xy.imag],
                  axis=2)
    ref = st.reshape(T // R, R, F, 4, B).sum(axis=1)
    assert out.shape == (T // R, F, 4, B)
    rel = np.max(np.abs(out - ref)) / np.max(np.abs(ref))
    assert rel < 1e-5, rel


def test_fused_substitution_requires_int8_class(monkeypatch):
    """Under BF_BEAM_FUSED=auto the substitution is refused off-TPU
    and for accuracy classes below int8 — the XLA stage path runs and
    still produces a correct stream."""
    from bifrost_tpu.stages import (BeamformStage, DetectStage,
                                    ReduceStage, match_beamformer,
                                    walk_headers)
    monkeypatch.setenv('BF_BEAM_FUSED', 'auto')
    T, F, S, P, B, R = 8, 2, 4, 2, 3, 4
    w = _weights(B, S)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'])
    stages = [BeamformStage(w, accuracy='f32'),
              DetectStage('stokes', axis='pol'),
              ReduceStage('time', R)]
    headers = walk_headers(stages, hdr)
    assert match_beamformer(stages, headers, (T, F, S, P, 2),
                            'int8') is None
    # wrong detect mode never matches either
    stages = [BeamformStage(w, accuracy='int8'),
              DetectStage('coherence', axis='pol'),
              ReduceStage('time', R)]
    headers = walk_headers(stages, hdr)
    assert match_beamformer(stages, headers, (T, F, S, P, 2),
                            'int8') is None


def test_block_rejects_bad_streams():
    from bifrost_tpu.stages import BeamformStage
    w = _weights(4, 8)
    st = BeamformStage(w)
    with pytest.raises(ValueError):
        st.transform_header(simple_header(
            [-1, 4, 8], 'ci8', labels=['time', 'station', 'freq']))
    with pytest.raises(TypeError):
        st.transform_header(simple_header(
            [-1, 4, 8], 'f32', labels=['time', 'freq', 'station']))
    with pytest.raises(ValueError):
        # station count mismatch
        st.transform_header(simple_header(
            [-1, 4, 6], 'ci8', labels=['time', 'freq', 'station']))


def test_gemm_ops_accounting():
    """The engine's ops/frame accounting (8 real ops per complex MAC)
    feeds the gemm_gops_per_s perf key and the bench ops/s row."""
    w = _weights(4, 8, 2)
    eng = Beamformer(w, accuracy='int8')
    assert eng.ops_per_frame(nfreq=16) == 8 * 16 * 2 * 4 * 8
    assert eng.ops_per_frame(nfreq=16, npol=1) == 8 * 16 * 1 * 4 * 8


# ---------------------------------------------------------------------------
# a weight set per channel (a tied-array beam is a delay: PR 35)
# ---------------------------------------------------------------------------

def _phase_weights(F, P, B, S, seed=0):
    """(w8r, w8i, w): int8 weights of random phase and modulus 127,
    and the complex64 form a stage is handed, w8 / 127."""
    rng = np.random.default_rng(seed)
    phase = rng.random((F, P, B, S)) * 2 * np.pi
    w8r = np.rint(127 * np.cos(phase)).astype(np.int8)
    w8i = np.rint(127 * np.sin(phase)).astype(np.int8)
    w = ((w8r + 1j * w8i) * np.float32(1 / 127.)).astype(np.complex64)
    return w8r, w8i, w


def _int_beams(x, w8r, w8i):
    """int64 beam sums (re, im): x (T, F, S, P, 2) int8 against
    ([F,] P, B, S) int8 weights -> (T, F, P, B)."""
    xr, xi = x[..., 0].astype(np.int64), x[..., 1].astype(np.int64)
    wr, wi = w8r.astype(np.int64), w8i.astype(np.int64)
    sub = 'tfsp,fpbs->tfpb' if wr.ndim == 4 else 'tfsp,pbs->tfpb'
    return (np.einsum(sub, xr, wr) - np.einsum(sub, xi, wi),
            np.einsum(sub, xr, wi) + np.einsum(sub, xi, wr))


def _stokes_i(x, w8r, w8i, R, scale):
    """float64 Stokes I of the int64 beams, R frames summed, times
    ``scale`` over 127^2: unrounded."""
    br, bi = _int_beams(x, w8r, w8i)
    p = (br.astype(np.float64) ** 2 + bi.astype(np.float64) ** 2) \
        .sum(axis=2)
    p = p.reshape(p.shape[0] // R, R, *p.shape[1:]).sum(axis=1)
    return p * (scale / 127. ** 2)


@pytest.mark.parametrize('name', ['xla', 'planar', 'planar_bf16',
                                  'int8_wide'])
def test_per_channel_weights_every_candidate_kept(name):
    """(F, P, B, S) weights: the four einsum candidates take the
    frequency axis as a batch axis and stay inside their class of the
    int64 oracle; the widened int8 one is exact."""
    T, F, P, S, B = 16, 3, 2, 8, 5
    w8r, w8i, w = _phase_weights(F, P, B, S)
    eng = Beamformer(w, accuracy='int8')
    assert (eng.nfreq_w, eng.npol_w, eng.nbeam, eng.nstand) == (F, P, B, S)
    x = np.random.default_rng(2).integers(
        -64, 64, (T, F, S, P, 2), dtype=np.int8)
    br, bi = _int_beams(x, w8r, w8i)
    ref = (br + 1j * bi) / 127.
    re = np.ascontiguousarray(x[..., 0].transpose(0, 1, 3, 2))
    im = np.ascontiguousarray(x[..., 1].transpose(0, 1, 3, 2))
    y = np.asarray(eng._jit(name, P)(re, im))
    rel = np.max(np.abs(y - ref)) / np.max(np.abs(ref))
    bound = {'xla': 1e-5, 'planar': 1e-3, 'planar_bf16': 8e-3,
             'int8_wide': 1e-6}[name]
    assert y.shape == (T, F, P, B) and rel <= bound, (name, rel)
    # the frequency axis is part of a measurement's identity
    assert eng._key(re.shape, 'int8', True) != \
        Beamformer(w[0], accuracy='int8')._key(re.shape, 'int8', True)


@pytest.mark.parametrize('name', ['pallas', 'pallas_bf16'])
def test_per_channel_weights_refuse_the_pallas_candidates(name,
                                                          monkeypatch):
    """The complex-beam kernels hold one weight set for every channel:
    forced, they refuse per-channel weights by name; where they would
    have raced (a TPU), they are left out and the selection says so."""
    from bifrost_tpu.ops import mprobe
    _, _, w = _phase_weights(3, 2, 5, 8)
    eng = Beamformer(w, accuracy='int8', impl=name)
    re, im = _volt_planes(16, 3, 2, 8)
    with pytest.raises(ValueError, match='one weight set'):
        eng(re, im)
    monkeypatch.setattr(Beamformer, '_pallas_raceable',
                        staticmethod(lambda: True))
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter('ignore', RuntimeWarning)   # said once
        names = Beamformer(w, accuracy='int8')._candidates(True)
    assert names == ['xla', 'planar', 'planar_bf16', 'int8_wide']
    assert 'one weight set' in mprobe.refusals()['beamform/%s' % name]
    # one set for every channel still races them
    assert name in Beamformer(w[0], accuracy='int8')._candidates(True)


@pytest.mark.parametrize('per_channel', [True, False],
                         ids=['per_channel', 'one_set'])
@pytest.mark.parametrize('tile', [None, 1024],
                         ids=['default_two_tiles', 'untiled'])
def test_fused_chain_against_the_oracle(tile, per_channel):
    """The one kernel (stokes_i, sum 16, u8) against the int64/float64
    oracle: within half a step and float32's rounding of the
    unrounded reference, tiled and untiled alike, and from the gulp's
    words bit-identical to the pairs."""
    from bifrost_tpu.ops.beamform import fused_detect
    from bifrost_tpu.ops.pallas_kernels import beam_time_tile
    T, F, P, S, B, R = 1024, 3, 2, 8, 5, 16
    w8r, w8i, w = _phase_weights(F, P, B, S, seed=4)
    if not per_channel:
        w8r, w8i, w = w8r[0], w8i[0], w[0]
    eng = Beamformer(w, accuracy='int8')
    x = np.random.default_rng(5).integers(
        -64, 64, (T, F, S, P, 2), dtype=np.int8)
    scale = 64. / (R * P * S * 2731.)
    want = np.clip(_stokes_i(x, w8r, w8i, R, scale), 0, 255)
    how = dict(stokes='stokes_i', scale=scale,
               quantize=(0, 255, 'uint8'), time_tile=tile)
    pairs = np.asarray(fused_detect(eng, x, R, **how))
    words = np.asarray(fused_detect(
        eng, x.view(np.int16).reshape(-1), R, nfreq=F, **how))
    assert beam_time_tile(T, R, 1, tile) == (512 if tile is None
                                             else tile)
    assert pairs.dtype == np.uint8 and pairs.shape == (T // R, F, 1, B)
    assert np.array_equal(words, pairs)
    err = np.max(np.abs(pairs[:, :, 0].astype(np.float64) - want))
    assert err <= 0.5 + 1e-3, err
    assert 40 < want.mean() < 90          # the scale: a quarter of u8


def test_weights_round_trip_through_the_engines_quantisation():
    """int8 weights handed over as w8 / 127 come back from the
    engine's quantisation (one scale: the largest modulus over 127)
    bit for bit, and the scale is 1 / 127."""
    w8r, w8i, w = _phase_weights(4, 2, 16, 8, seed=9)
    eng = Beamformer(w, accuracy='int8')
    assert np.array_equal(eng.wr8, w8r) and np.array_equal(eng.wi8, w8i)
    assert eng.wscale == pytest.approx(1 / 127., rel=1e-6)
    assert eng.ops_per_frame(nfreq=4) == 8 * 4 * 2 * 16 * 8


def test_frequency_axis_that_does_not_match_is_refused():
    """Weights for 4 channels on a stream of 6: refused in
    transform_header, with both numbers."""
    from bifrost_tpu.stages import BeamformStage
    _, _, w = _phase_weights(4, 2, 5, 8)
    st = BeamformStage(w, accuracy='int8')
    with pytest.raises(ValueError, match=r'4 frequency channels.*has 6'):
        st.transform_header(simple_header(
            [-1, 6, 8, 2], 'ci8',
            labels=['time', 'freq', 'station', 'pol']))
    ohdr = st.transform_header(simple_header(
        [-1, 4, 8, 2], 'ci8', labels=['time', 'freq', 'station', 'pol']))
    assert ohdr['_tensor']['labels'] == ['time', 'freq', 'pol', 'beam']
    assert ohdr['_tensor']['shape'] == [-1, 4, 2, 5]
    with pytest.raises(ValueError):
        Beamformer(np.zeros((2, 4, 2, 5, 8), np.complex64))


def test_standalone_block_takes_per_channel_weights():
    """bf.blocks.beamform with (F, P, B, S) weights gives the complex
    beams, at a shape whose output fits."""
    T, F, S, P, B = 16, 4, 8, 2, 4
    w8r, w8i, w = _phase_weights(F, P, B, S, seed=3)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'])
    gulps = _ci8_gulps(T, F, S, P)
    out, _ = _run_block_chain(gulps, hdr, w, T, name='BeamPerChan')
    x = np.stack([gulps[0]['re'], gulps[0]['im']], axis=-1)
    br, bi = _int_beams(x, w8r, w8i)
    assert out.shape == (T, F, P, B) and out.dtype == np.complex64
    np.testing.assert_allclose(out, (br + 1j * bi) / 127., rtol=1e-5)


def _tab_config():
    """The benchmark's configuration at its rehearsal size, and its
    module (the plain reference: imports nothing of the program)."""
    import importlib.util
    import json
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'perfbench', 'configs')
    with open(os.path.join(root, 'beamform_tab.json')) as f:
        cfg = json.load(f)
    cfg.update({k: v for k, v in cfg['rehearse'].items() if k != 'input'})
    cfg['input'] = dict(cfg['input'], **cfg['rehearse']['input'])
    spec = importlib.util.spec_from_file_location(
        'beamform_tab_for_tests', os.path.join(root, 'beamform_tab.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return cfg, mod


def test_pipeline_at_rehearsal_size_over_two_sequences():
    """host ring -> copy('tpu') -> fused [beamform, stokes_i, sum 16,
    u8] -> copy('system') -> sink through the public API, at the
    benchmark's rehearsal size (4 channels x 8 antennas x 16 beams,
    1024 frames: two time tiles), two sequences of two gulps: every
    product against the configuration's reference, the chain
    substituted whole and started from words, the four counters, and
    nothing compiled after the first gulp."""
    from bifrost_tpu.telemetry import counters
    cfg, mod = _tab_config()
    T, (F, S, P), B = cfg['gulp_nframe'], cfg['input']['frame_shape'], \
        cfg['nbeam']
    seed = 2 ** 31 + 11
    hdr = mod.header(cfg)
    gulps = [g for s in (1, 2) for g in _ci8_gulps(T, F, S, P, n=2,
                                                   seed=s, lim=64)]
    compiles = []

    class TwoSequences(NumpySourceBlock):
        def __init__(self):
            super(NumpySourceBlock, self).__init__(['first', 'second'],
                                                   T, space='system')
            self._header = hdr

        def create_reader(self, name):
            self._gulps = [g.copy() for g in
                           (gulps[:2] if name == 'first' else gulps[2:])]
            return super(TwoSequences, self).create_reader(name)

    class Sink(GatherSink):
        def on_data(self, ispan):
            compiles.append(counters.snapshot().get('jit.compiles', 0))
            return super(Sink, self).on_data(ispan)

    counters.reset()
    with bf.Pipeline() as p:
        src = TwoSequences()
        blk = mod.chain(bf, bf.blocks.copy(src, space='tpu'), cfg,
                        seed=seed)
        sink = Sink(bf.blocks.copy(blk, space='system'))
        p.run()
    out = sink.result()
    nout = T // cfg['tscrunch']
    assert out.shape == (4 * nout, F, 1, B) and out.dtype == np.uint8
    idx = (seed, np.arange(nout), np.arange(B))
    for k, gulp in enumerate(gulps):
        want = mod.reference([gulp], idx, cfg)
        name, err = mod.compare(mod.take(out[k * nout:(k + 1) * nout],
                                         idx), want)
        assert name == 'max_lsb_err' and err <= 0.5 + 1e-3, (k, err)
    info = blk.impl_info
    assert info['impl'] == 'pallas-beamform-detect'
    assert info['input'] == 'words' and info['weights'] == 'per channel'
    assert info['stokes'] == 'stokes_i' and info['quantize'] == 'u8'
    assert info['dot'] == 'int8' and info['time_tile'] == 512
    snap = counters.snapshot()
    assert snap['beamform.gulps'] == snap['beamform.fused_gulps'] == \
        snap['beamform.word_gulps'] == 4
    assert snap['beamform.int8_ops'] == 4 * 8 * B * T * F * S * P
    assert len(compiles) == 4 and len(set(compiles)) == 1, compiles


def test_a_band_beamformed_in_shares_equals_the_whole():
    """The share tied to the whole: an 8-channel band beamformed in
    two shares of 4 channels by separate chains, each with its own
    channels' weights, concatenated, equals the whole band's product
    byte for byte (no exchange between workers exists, so none is
    stood in for)."""
    from bifrost_tpu.stages import (BeamformStage, DetectStage,
                                    ReduceStage, QuantizeStage)
    T, F, S, P, B, R = 512, 8, 8, 2, 6, 16
    _, _, w = _phase_weights(F, P, B, S, seed=6)
    scale = 64. / (R * P * S * 2731.)
    gulps = _ci8_gulps(T, F, S, P, n=2, seed=8, lim=64)

    def run(chans, name):
        hdr = simple_header([-1, len(chans), S, P], 'ci8',
                            labels=['time', 'freq', 'station', 'pol'])
        part = [np.ascontiguousarray(g[:, chans]) for g in gulps]
        chain = [BeamformStage(w[chans], accuracy='int8'),
                 DetectStage('stokes_i'), ReduceStage('time', R),
                 QuantizeStage('u8', scale)]
        out, _ = _run_block_chain(part, hdr, None, T, fused_chain=chain,
                                  name=name)
        return out
    whole = run(np.arange(F), 'BandWhole')
    shares = [run(np.arange(4), 'BandLow'),
              run(np.arange(4, 8), 'BandHigh')]
    assert whole.shape == (2 * T // R, F, 1, B)
    assert whole.dtype == np.uint8 and whole.std() > 1
    assert np.array_equal(np.concatenate(shares, axis=1), whole)


def test_gates_and_probes_run_in_tiles_of_time(monkeypatch):
    """Neither prewarm nor the gate makes a full gulp's beam voltages:
    with the most a gate may make set to 4 KiB, the untiled gate at
    (64, 4, 2, 8) counts the baseline's 16 KiB and refuses, and
    prewarm gates and races a tile of 16 frames and caches the winner
    under the gulp's own shape."""
    import jax.numpy as jnp
    from bifrost_tpu.ops import beamform as beam
    monkeypatch.setattr(beam, 'GATE_BYTES', 4096)
    monkeypatch.setenv('BF_CACHE_DIR', os.path.join(
        os.environ.get('TMPDIR', '/tmp'), 'bf_gate_tiles_%d' % os.getpid()))
    T, F, P, S, B = 64, 4, 2, 8, 4
    assert beam.probe_nframe(T, F, P, B) == 16
    assert beam.probe_nframe(16384, 64, 2, 864) * 64 * 2 * 864 * 8 \
        <= 256 << 20                      # the default, at the deployment
    _, _, w = _phase_weights(F, P, B, S)
    eng = Beamformer(w, accuracy='int8')
    re, im = (jnp.asarray(a) for a in _volt_planes(T, F, P, S))
    with pytest.raises(ValueError, match=r'16384 bytes.*4096'):
        eng._gate(['xla', 'int8_wide'], P, lambda: (re, im))
    seen = []
    gate = eng._gate
    monkeypatch.setattr(eng, '_gate', lambda names, npol, make_args: (
        seen.append(make_args()[0].shape), gate(names, npol,
                                                make_args))[1])
    monkeypatch.setenv('BF_LINALG_PROBE', '1')
    winner = eng.prewarm(T, F, npol=P)
    assert seen == [(16, F, P, S)]
    assert eng.chosen[eng._key((T, F, P, S), 'int8', True)] == winner
