"""FX-correlator tests (docs/perf.md "FX
correlator"): the raced X-engine against the exact int64 oracle, the
accuracy-class admission rules, the fused/macro chain's byte
stability, the corner-turn collective against the transpose oracle,
the zero-collective sharded channelizer, and the visibility-format
round trip against live correlator output."""

import os

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu.ops import linalg as L

from util import NumpySourceBlock, GatherSink, simple_header


# (T, F, n) voltage-plane shapes for the oracle-parity sweep
SHAPES = [(8, 4, 6), (16, 3, 8), (12, 5, 4)]


def _planes(shape, seed=0):
    rng = np.random.RandomState(seed)
    re = rng.randint(-64, 64, shape).astype(np.int8)
    im = rng.randint(-64, 64, shape).astype(np.int8)
    return re, im


def _oracle_int(re, im):
    """The exactness reference: x @ x^H over time in int64, cast to
    complex64 (every sum is far below 2^24, so the cast is lossless)."""
    r = re.astype(np.int64)
    i = im.astype(np.int64)
    rr = np.einsum('tfi,tfj->fij', r, r) + np.einsum('tfi,tfj->fij',
                                                     i, i)
    ii = np.einsum('tfi,tfj->fij', i, r) - np.einsum('tfi,tfj->fij',
                                                     r, i)
    return (rr + 1j * ii).astype(np.complex64)


# ---------------------------------------------------------------------------
# X-engine candidates vs the exact oracle
# ---------------------------------------------------------------------------

EXACT_IMPLS = ['xla', 'planar', 'int8_3mm', 'int8_wide']


class TestXEngineOracle:
    @pytest.mark.parametrize('shape', SHAPES)
    @pytest.mark.parametrize('name', EXACT_IMPLS)
    def test_exact_candidates_bit_identical(self, shape, name):
        """Every non-lossy candidate is BIT-identical to the int64
        oracle on int8 planes — including the float lowerings, whose
        integer sums are exactly representable."""
        re, im = _planes(shape, seed=hash(shape) % 1000)
        eng = L.XEngine(accuracy='int8', impl=name)
        got = np.asarray(eng(re, im))
        np.testing.assert_array_equal(got, _oracle_int(re, im))

    def test_pallas_exact_on_tpu(self):
        import jax
        if jax.default_backend() != 'tpu':
            pytest.skip('pallas xcorr kernel is TPU-only')
        re, im = _planes(SHAPES[0])
        got = np.asarray(L.XEngine(accuracy='int8',
                                   impl='pallas')(re, im))
        np.testing.assert_array_equal(got, _oracle_int(re, im))

    def test_bf16_candidate_within_class(self):
        """The one-pass bf16 candidate is lossy by construction; it
        must sit inside its declared class bound vs the baseline."""
        re, im = _planes((16, 4, 8), seed=5)
        ref = _oracle_int(re, im)
        got = np.asarray(L.XEngine(accuracy='int8',
                                   impl='planar_bf16')(re, im))
        scale = float(np.max(np.abs(ref))) or 1.0
        assert float(np.max(np.abs(got - ref))) / scale \
            <= L.XCORR_CLASSES['bf16']

    def test_float_input_routes_float_path(self):
        """Float voltages cannot feed the int kernels: the engine
        must still match the oracle through its float baseline."""
        re, im = _planes((8, 3, 4), seed=2)
        eng = L.XEngine(accuracy='f32')
        got = np.asarray(eng(re.astype(np.float32),
                             im.astype(np.float32)))
        np.testing.assert_array_equal(got, _oracle_int(re, im))


class TestAccuracyClassGates:
    def test_f32_class_rejects_bf16_candidate(self):
        """'f32' admits only candidates whose construction error fits
        1e-3: the lossy one-pass bf16 GEMM is out..."""
        names = L.XEngine(accuracy='f32')._candidates(int_input=True)
        assert 'planar_bf16' not in names
        # ...but the EXACT int candidates race at every class
        assert 'int8_3mm' in names and 'int8_wide' in names

    def test_int8_class_admits_bf16_candidate(self):
        names = L.XEngine(accuracy='int8')._candidates(int_input=True)
        assert 'planar_bf16' in names

    def test_float_input_excludes_int_kernels(self):
        names = L.XEngine(accuracy='int8')._candidates(int_input=False)
        assert not (set(names) & L._XENGINE_INT_IMPLS)

    def test_lossy_set_is_only_bf16(self):
        assert L._XENGINE_LOSSY == frozenset(['planar_bf16'])

    def test_gate_rtol_env_override_keys_cache(self, monkeypatch):
        """BF_XCORR_GATE_RTOL changes the admitted set AND the probe
        key (a widened gate must not reuse a narrow gate's winner)."""
        eng = L.XEngine(accuracy='f32')
        base_key = eng._key((8, 4, 6), 'int8', True)
        monkeypatch.setenv('BF_XCORR_GATE_RTOL', '0.01')
        assert L.xcorr_class_rtol('f32') == 0.01
        widened = L.XEngine(accuracy='f32')._candidates(True)
        assert 'planar_bf16' in widened
        assert 'gate_rtol' in eng._key((8, 4, 6), 'int8', True)
        assert eng._key((8, 4, 6), 'int8', True) != base_key

    def test_bad_accuracy_rejected(self):
        with pytest.raises(ValueError):
            L.XEngine(accuracy='int4')


# ---------------------------------------------------------------------------
# the chain: F -> requantize -> X -> accumulate (blocks.correlate
# fusable form) — macro-gulp and segment byte stability
# ---------------------------------------------------------------------------

CNT, CNW, CNS, CNP = 16, 16, 4, 2
CR, CA = 4, 2


def _chain_volts(ngulp, seed=3):
    rng = np.random.RandomState(seed)
    gulps = []
    for _ in range(ngulp):
        raw = np.zeros((CNT, CNW, CNS, CNP),
                       dtype=np.dtype([('re', 'i1'), ('im', 'i1')]))
        raw['re'] = rng.randint(-64, 64, raw.shape)
        raw['im'] = rng.randint(-64, 64, raw.shape)
        gulps.append(raw)
    return gulps


def _chain_hdr():
    return simple_header([-1, CNW, CNS, CNP], 'ci8',
                         labels=['time', 'fine', 'station', 'pol'])


def _run_chain(ngulp=4, gulp_batch=1, segments=None, accuracy='int8'):
    with bf.Pipeline(gulp_batch=gulp_batch, segments=segments,
                     sync_depth=4) as p:
        src = NumpySourceBlock(_chain_volts(ngulp), _chain_hdr(),
                               gulp_nframe=CNT)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fft(b, axes='fine', axis_labels='freq')
        b = bf.blocks.quantize(b, 'ci8', scale=1. / CNW)
        b = bf.blocks.correlate(b, CR, accuracy=accuracy,
                                fusable=True)
        b = bf.blocks.accumulate(b, CA, fusable=True)
        sink = GatherSink(bf.blocks.copy(b, space='system'))
        p.run()
    return sink.result()


def _chain_oracle(ngulp=4):
    """Sequential reference: eager jnp F + quantize (the same XLA fft
    custom call the pipeline runs), then the int64 numpy X step."""
    import jax.numpy as jnp
    raw = np.concatenate(_chain_volts(ngulp), axis=0)
    v = raw['re'].astype(np.float32) + 1j * raw['im'].astype(np.float32)
    F = np.asarray(jnp.fft.fft(jnp.asarray(v), axis=1)) * \
        np.float32(1. / CNW)
    qr = np.clip(np.round(F.real), -128, 127).astype(np.int64)
    qi = np.clip(np.round(F.imag), -128, 127).astype(np.int64)
    n = CNS * CNP
    ntot = raw.shape[0]
    qr = qr.reshape(ntot // CR, CR, CNW, n)
    qi = qi.reshape(ntot // CR, CR, CNW, n)
    re = np.einsum('grfi,grfj->gfij', qr, qr) + \
        np.einsum('grfi,grfj->gfij', qi, qi)
    im = np.einsum('grfi,grfj->gfij', qi, qr) - \
        np.einsum('grfi,grfj->gfij', qr, qi)
    vis = (re + 1j * im).astype(np.complex64)
    vis = vis.reshape(-1, CA, CNW, n, n).sum(axis=1).astype(np.complex64)
    return vis.reshape(-1, CNW, CNS, CNP, CNS, CNP)


class TestCorrelatorChain:
    def test_chain_matches_sequential_oracle(self):
        got = _run_chain()
        np.testing.assert_array_equal(got, _chain_oracle())

    def test_macro_gulp_byte_identical(self):
        base = _run_chain(ngulp=4, gulp_batch=1)
        macro = _run_chain(ngulp=4, gulp_batch=4)
        np.testing.assert_array_equal(macro, base)

    def test_segment_fused_byte_identical(self):
        base = _run_chain(ngulp=4, segments='off')
        fused = _run_chain(ngulp=4, segments='force')
        np.testing.assert_array_equal(fused, base)

    def test_f32_arm_equals_int_arm(self):
        """Integer visibilities are exact in complex64: even the
        forced-float engine admits no tolerance on ci8 planes."""
        np.testing.assert_array_equal(_run_chain(accuracy='f32'),
                                      _run_chain(accuracy='int8'))

    def test_nondividing_integration_rejected(self):
        from bifrost_tpu.stages import CorrelateStage
        stage = CorrelateStage(5)
        hdr = simple_header([-1, CNW, CNS, CNP], 'ci8',
                            labels=['time', 'freq', 'station', 'pol'])
        stage.transform_header(hdr)       # header side is fine
        with pytest.raises(ValueError):
            stage.build({'shape': (16, CNW, CNS, CNP),
                         'dtype': 'int8'})


# ---------------------------------------------------------------------------
# corner turn vs the transpose oracle (CPU mesh; the pallas remote-DMA
# form needs real ICI and is raced only on TPU)
# ---------------------------------------------------------------------------

class TestCornerTurn:
    @pytest.mark.parametrize('impl', ['xla', 'ring'])
    def test_matches_transpose_oracle(self, impl):
        from bifrost_tpu.parallel import create_mesh, corner_turn
        mesh = create_mesh({'sp': 8})
        T, F = 16, 32
        rng = np.random.RandomState(7)
        x = rng.randint(-64, 64, (T, F, 3, 2)).astype(np.int8)
        fn = corner_turn(mesh, 'sp', impl=impl, stacked=True)
        got = np.asarray(fn(x))              # (D, T, F/D, 3, 2)
        fc = F // 8
        for d in range(8):
            np.testing.assert_array_equal(got[d],
                                          x[:, d * fc:(d + 1) * fc])

    def test_ring_equals_xla(self):
        from bifrost_tpu.parallel import create_mesh, corner_turn
        mesh = create_mesh({'sp': 8})
        rng = np.random.RandomState(8)
        x = (rng.randn(8, 16, 4) + 1j * rng.randn(8, 16, 4)) \
            .astype(np.complex64)
        a = np.asarray(corner_turn(mesh, 'sp', impl='xla',
                                   stacked=True)(x))
        b = np.asarray(corner_turn(mesh, 'sp', impl='ring',
                                   stacked=True)(x))
        np.testing.assert_array_equal(a, b)

    def test_ring_needs_static_ndev(self):
        import jax.numpy as jnp
        from bifrost_tpu.parallel import corner_turn_local
        with pytest.raises(ValueError):
            corner_turn_local(np.zeros((4, 8)), 'sp', impl='ring',
                              ndev=jnp.int32(8))

    def test_bad_impl_rejected(self):
        from bifrost_tpu.parallel import corner_turn_local
        with pytest.raises(ValueError):
            corner_turn_local(np.zeros((4, 8)), 'sp', impl='fft')


# ---------------------------------------------------------------------------
# cross-chip channelizer: decomposed DFT, channel-sharded, ZERO
# collectives inside a frame (compiled-HLO stats)
# ---------------------------------------------------------------------------

class TestShardedChannelizer:
    def test_exact_and_collective_free(self):
        import jax
        from bifrost_tpu.parallel import create_mesh, freq_sharded_dft
        from bifrost_tpu.parallel.scope import collective_counts
        mesh = create_mesh({'sp': 8})
        N = 64
        rng = np.random.RandomState(9)
        x = (rng.randn(4, N) + 1j * rng.randn(4, N)) \
            .astype(np.complex64)
        fn = freq_sharded_dft(mesh, N, axis_name='sp', nbatch=1)
        got = np.asarray(fn(x))
        ref = np.fft.fft(x, axis=-1)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-4)
        # the compiled program moves NO bytes between devices
        txt = jax.jit(fn).lower(x).compile().as_text()
        assert collective_counts(txt) == {}, collective_counts(txt)


# ---------------------------------------------------------------------------
# mesh-striped correlator: psum plan vs the corner-turn plan, both
# byte-equal to the single-device run
# ---------------------------------------------------------------------------

def _mesh_correlate(mesh, corner=None, monkeypatch=None):
    if corner is not None:
        monkeypatch.setenv('BF_XCORR_CORNER_TURN', corner)
    rng = np.random.RandomState(11)
    gulps = []
    for _ in range(2):
        raw = np.zeros((16, 8, 3, 2),
                       dtype=np.dtype([('re', 'i1'), ('im', 'i1')]))
        raw['re'] = rng.randint(-64, 64, raw.shape)
        raw['im'] = rng.randint(-64, 64, raw.shape)
        gulps.append(raw)
    hdr = simple_header([-1, 8, 3, 2], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'],
                        gulp_nframe=16)
    with bf.Pipeline() as p:
        src = NumpySourceBlock(gulps, hdr, gulp_nframe=16)
        b = bf.blocks.copy(src, space='tpu')
        with bf.block_scope(mesh=mesh):
            b = bf.blocks.correlate(b, nframe_per_integration=16,
                                    accuracy='int8')
        sink = GatherSink(bf.blocks.copy(b, space='system'))
        p.run()
    return sink.result()


class TestMeshCorrelate:
    def test_psum_plan_matches_single(self):
        from bifrost_tpu.parallel import create_mesh
        base = _mesh_correlate(None)
        meshed = _mesh_correlate(create_mesh({'sp': 8}))
        np.testing.assert_array_equal(meshed, base)

    def test_corner_plan_matches_single(self, monkeypatch):
        from bifrost_tpu.parallel import create_mesh
        base = _mesh_correlate(None)
        meshed = _mesh_correlate(create_mesh({'sp': 8}), corner='xla',
                                 monkeypatch=monkeypatch)
        np.testing.assert_array_equal(meshed, base)

    def test_correlate_block_flags_collective_boundary(self):
        """The segment planner must see the mesh-resident correlator
        as a collective meeting point (BF-I191), never fuse across."""
        from bifrost_tpu.parallel import create_mesh
        from bifrost_tpu.blocks.correlate import CorrelateBlock
        with bf.Pipeline():
            src = NumpySourceBlock(
                [], simple_header([-1, 8, 3, 2], 'ci8',
                                  labels=['time', 'freq', 'station',
                                          'pol']), gulp_nframe=16)
            b = bf.blocks.copy(src, space='tpu')
            with bf.block_scope(mesh=create_mesh({'sp': 8})):
                corr = bf.blocks.correlate(b, 16)
            assert isinstance(corr, CorrelateBlock)
            assert corr._collective_boundary
            plain = bf.blocks.correlate(b, 16)
            assert not plain._collective_boundary


# ---------------------------------------------------------------------------
# visibility-format round trip against live correlator output
# ---------------------------------------------------------------------------

class TestConvertVisibilitiesRoundtrip:
    def _run(self, convert):
        with bf.Pipeline() as p:
            src = NumpySourceBlock(_chain_volts(2, seed=13),
                                   _chain_hdr(), gulp_nframe=CNT)
            b = bf.blocks.copy(src, space='tpu')
            b = bf.blocks.fft(b, axes='fine', axis_labels='freq')
            b = bf.blocks.quantize(b, 'ci8', scale=1. / CNW)
            b = bf.blocks.correlate(b, CR, accuracy='int8',
                                    fusable=True)
            if convert:
                b = bf.blocks.convert_visibilities(b, 'storage')
                if convert == 'roundtrip':
                    b = bf.blocks.convert_visibilities(b, 'matrix')
            sink = GatherSink(bf.blocks.copy(b, space='system'))
            p.run()
        return sink.result()

    def test_roundtrip_bit_identical(self):
        """matrix -> storage -> matrix over LIVE correlator output is
        the identity: the Stokes basis change halves exactly on the
        integer visibilities."""
        matrix = self._run(convert=None)
        back = self._run(convert='roundtrip')
        np.testing.assert_array_equal(back, matrix)

    def test_storage_packing_against_matrix(self):
        """The packed (time, baseline, freq, stokes) stream equals the
        IQUV combination of the full matrix's lower triangle."""
        matrix = self._run(convert=None)       # (t, f, s, p, s, p)
        storage = self._run(convert='storage')  # (t, nbl, f, 4)
        nbl = CNS * (CNS + 1) // 2
        assert storage.shape[1:] == (nbl, CNW, 4)
        k = 0
        for i in range(CNS):
            for j in range(i + 1):
                v = matrix[:, :, i, :, j, :]    # (t, f, 2, 2)
                I = v[..., 0, 0] + v[..., 1, 1]
                Q = v[..., 0, 0] - v[..., 1, 1]
                U = v[..., 0, 1] + v[..., 1, 0]
                V = (v[..., 0, 1] - v[..., 1, 0]) * 1j
                got = storage[:, k]             # (t, f, 4)
                np.testing.assert_array_equal(
                    got, np.stack([I, Q, U, V], axis=-1)
                    .astype(np.complex64))
                k += 1
