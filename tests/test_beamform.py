"""Quantized coherent-beamformer engine (ops/beamform.py, the Pallas
kernels in ops/pallas_kernels.py, BeamformBlock and the fused
beamform->detect->integrate substitution in stages.py).

Kernel parity runs in Pallas interpret mode on the CPU test backend.
On the chip, chip_smoke.py phase B compiles every candidate at the
BASELINE.json shape and holds it to the float64 oracle; no cell of the
benchmark times the beamformer yet (ROADMAP R7).
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
    """beamform_detect_int8: dual-pol beamform -> Stokes -> R-frame
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
