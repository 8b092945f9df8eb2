"""Fused Pallas spectrometer kernel vs the float64 numpy oracle.

Runs in Pallas interpret mode on the CPU test backend.  On the chip,
chip_smoke.py phase A holds the kernel, inside the served chain at full
width, to the same oracle, phase B compiles it, and every gpuspec cell
of the benchmark checks its products (`correct`) and times it.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from bifrost_tpu.ops.spectrometer import (fused_spectrometer,
                                          spectrometer_oracle)


def _run(T, nfft, rfactor, time_tile, seed=0):
    rng = np.random.RandomState(seed)
    volt = rng.randint(-64, 64, size=(T, 2, nfft, 2)).astype(np.int8)
    got = np.asarray(fused_spectrometer(
        jnp.asarray(volt), rfactor=rfactor, time_tile=time_tile,
        interpret=True))
    want = spectrometer_oracle(volt, rfactor=rfactor)
    rel = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30)
    return got, want, rel


def test_matches_oracle_4096():
    got, want, rel = _run(T=8, nfft=4096, rfactor=4, time_tile=4)
    assert got.shape == (8, 4, 1024)
    assert rel < 1e-5


def test_matches_oracle_small_fft():
    got, want, rel = _run(T=8, nfft=256, rfactor=4, time_tile=8)
    assert got.shape == (8, 4, 64)
    assert rel < 1e-5


def test_rfactor_variants():
    for rf in (1, 2, 8):
        got, want, rel = _run(T=4, nfft=1024, rfactor=rf, time_tile=4,
                              seed=rf)
        assert got.shape == (4, 4, 1024 // rf)
        assert rel < 1e-5, rf


def test_time_tile_not_dividing_T_shrinks():
    # T=6 with requested tile 4 -> falls back to a divisor (3)
    got, want, rel = _run(T=6, nfft=256, rfactor=4, time_tile=4)
    assert got.shape == (6, 4, 64)
    assert rel < 1e-5


def test_rejects_bad_shapes():
    volt = np.zeros((4, 2, 300, 2), np.int8)    # not a power of two
    with pytest.raises(ValueError):
        fused_spectrometer(jnp.asarray(volt), interpret=True)
    volt = np.zeros((4, 1, 256, 2), np.int8)    # single pol
    with pytest.raises(ValueError):
        fused_spectrometer(jnp.asarray(volt), interpret=True)


def test_rejects_rfactor_beyond_radix():
    # n1 for 256 is 16; rfactor 32 cannot divide the radix split
    volt = np.zeros((4, 2, 256, 2), np.int8)
    with pytest.raises(ValueError):
        fused_spectrometer(jnp.asarray(volt), rfactor=32,
                           interpret=True)


def _run_fused_ci8_chain(raw, rfactor=4, mesh=None):
    """Build the ci8 fused FFT->stokes->reduce pipeline the two
    substitution tests share and return the gathered output."""
    import bifrost_tpu as bf
    from bifrost_tpu.stages import FftStage, DetectStage, ReduceStage
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from util import NumpySourceBlock, GatherSink, simple_header
    import contextlib
    T, _, NF = raw.shape
    hdr = simple_header([-1, 2, NF], 'ci8',
                        labels=['time', 'pol', 'fine_time'])
    scope = bf.block_scope(mesh=mesh) if mesh is not None \
        else contextlib.nullcontext()
    with bf.Pipeline() as p:
        src = NumpySourceBlock([raw], hdr, gulp_nframe=T)
        with scope:
            b = bf.blocks.copy(src, space='tpu')
            b = bf.blocks.fused(b, [
                FftStage('fine_time', axis_labels='freq'),
                DetectStage('stokes', axis='pol'),
                ReduceStage('freq', rfactor),
            ])
        b = bf.blocks.copy(b, space='system')
        sink = GatherSink(b)
        p.run()
    return sink.result()


def test_fused_block_substitutes_kernel(monkeypatch):
    """The FusedBlock spectrometer pattern-match swaps in the Pallas
    kernel (interpret mode here) and the pipeline output still matches
    the oracle."""
    from bifrost_tpu.ops import spectrometer as spec
    from bifrost_tpu.dtype import ci8 as ci8_dtype

    calls = []
    real = spec.fused_spectrometer

    def fake(v, **kw):
        calls.append(kw)
        kw.pop('interpret', None)
        return real(v, interpret=True, **kw)

    monkeypatch.setattr(spec, 'choose_precision', lambda *a, **k: None)
    monkeypatch.setattr(spec, 'fused_spectrometer', fake)

    T, NF, RF = 8, 256, 4
    rng = np.random.RandomState(3)
    raw = np.zeros((T, 2, NF), dtype=ci8_dtype)
    raw['re'] = rng.randint(-32, 32, size=(T, 2, NF))
    raw['im'] = rng.randint(-32, 32, size=(T, 2, NF))
    out = _run_fused_ci8_chain(raw, rfactor=RF)
    assert calls, "pattern matcher did not substitute the kernel"
    volt = np.stack([raw['re'], raw['im']], axis=-1).astype(np.int8)
    want = spectrometer_oracle(volt, rfactor=RF)
    rel = np.max(np.abs(out - want)) / np.max(np.abs(want))
    assert out.shape == (T, 4, NF // RF)
    assert rel < 1e-5


def test_matcher_rejects_non_matching_chains(monkeypatch):
    """Chains that differ from the spectrometer pattern keep the XLA
    path (matcher returns None)."""
    from bifrost_tpu.ops import spectrometer as spec
    from bifrost_tpu.stages import (FftStage, DetectStage, ReduceStage,
                                    match_spectrometer)
    monkeypatch.setattr(spec, 'choose_precision', lambda *a, **k: None)
    hdr = {'_tensor': {'shape': [-1, 2, 256], 'dtype': 'ci8',
                       'labels': ['time', 'pol', 'fine_time'],
                       'scales': [[0, 1]] * 3, 'units': [None] * 3}}

    def build(stages):
        h = dict(hdr)
        headers = [h]
        for s in stages:
            h = s.transform_header(h)
            headers.append(h)
        return headers

    # matching chain sanity
    st = [FftStage('fine_time', axis_labels='freq'),
          DetectStage('stokes', axis='pol'), ReduceStage('freq', 4)]
    hs = build(st)
    assert match_spectrometer(st, hs, (8, 2, 256, 2), 'int8') is not None
    # wrong detect mode
    st = [FftStage('fine_time', axis_labels='freq'),
          DetectStage('coherence', axis='pol'), ReduceStage('freq', 4)]
    hs = build(st)
    assert match_spectrometer(st, hs, (8, 2, 256, 2), 'int8') is None
    # fftshift enabled
    st = [FftStage('fine_time', axis_labels='freq', apply_fftshift=True),
          DetectStage('stokes', axis='pol'), ReduceStage('freq', 4)]
    hs = build(st)
    assert match_spectrometer(st, hs, (8, 2, 256, 2), 'int8') is None
    # mean reduce
    st = [FftStage('fine_time', axis_labels='freq'),
          DetectStage('stokes', axis='pol'),
          ReduceStage('freq', 4, op='mean')]
    hs = build(st)
    assert match_spectrometer(st, hs, (8, 2, 256, 2), 'int8') is None
    # non-power-of-two nfft never reaches the kernel
    assert match_spectrometer(st, hs, (8, 2, 192, 2), 'int8') is None


def test_choose_split_prefers_lane_native():
    from bifrost_tpu.ops.spectrometer import _choose_split
    # minor dim a multiple of 128 (the only split Mosaic compiles)
    assert _choose_split(4096, 4) == (32, 128)
    assert _choose_split(1024, 8) == (8, 128)
    # square fallback when the lane-native n1 can't host rfactor
    assert _choose_split(256, 4) == (16, 16)
    # no valid split at all -> ValueError
    with pytest.raises(ValueError):
        _choose_split(256, 32)
    with pytest.raises(ValueError):
        _choose_split(192, 4)       # not a power of two


def test_precision_modes_match_oracle():
    rng = np.random.RandomState(2)
    volt = rng.randint(-64, 64, size=(4, 2, 1024, 2)).astype(np.int8)
    want = spectrometer_oracle(volt, rfactor=4)
    for prec in (None, 'high', 'highest'):
        got = np.asarray(fused_spectrometer(
            jnp.asarray(volt), rfactor=4, time_tile=4, precision=prec,
            interpret=True))
        rel = np.max(np.abs(got - want)) / np.max(np.abs(want))
        # interpret mode runs f32 throughout; all modes must agree
        assert rel < 1e-5, (prec, rel)


def test_epilogue_transpose_matches_kernel_transpose():
    rng = np.random.RandomState(9)
    volt = rng.randint(-64, 64, size=(4, 2, 1024, 2)).astype(np.int8)
    a = np.asarray(fused_spectrometer(jnp.asarray(volt), rfactor=4,
                                      time_tile=4, interpret=True,
                                      transpose='kernel'))
    b = np.asarray(fused_spectrometer(jnp.asarray(volt), rfactor=4,
                                      time_tile=4, interpret=True,
                                      transpose='epilogue'))
    assert np.array_equal(a, b)


def test_kernel_usable_rejects_invalid_config():
    from bifrost_tpu.ops import spectrometer as spec
    # no split supports rfactor 32 at nfft 256 -> unusable, no compile
    assert not spec.kernel_usable(256, 32, 16, None, 'kernel')


def test_matcher_probes_usability(monkeypatch):
    """match_spectrometer consults kernel_usable with the exact
    substitution config and returns None when it fails."""
    from bifrost_tpu.ops import spectrometer as spec
    from bifrost_tpu.stages import (FftStage, DetectStage, ReduceStage,
                                    match_spectrometer)
    monkeypatch.setattr(spec, 'choose_precision', lambda *a, **k: None)
    seen = {}

    def fake_usable(nfft, rfactor, tile, prec, trans):
        seen.update(nfft=nfft, rfactor=rfactor, tile=tile,
                    prec=prec, trans=trans)
        return False

    monkeypatch.setattr(spec, 'kernel_usable', fake_usable)
    hdr = {'_tensor': {'shape': [-1, 2, 256], 'dtype': 'ci8',
                       'labels': ['time', 'pol', 'fine_time'],
                       'scales': [[0, 1]] * 3, 'units': [None] * 3}}
    st = [FftStage('fine_time', axis_labels='freq'),
          DetectStage('stokes', axis='pol'), ReduceStage('freq', 4)]
    headers = [hdr]
    h = hdr
    for s in st:
        h = s.transform_header(h)
        headers.append(h)
    assert match_spectrometer(st, headers, (8, 2, 256, 2),
                              'int8') is None
    # tile is the EFFECTIVE one after shrink-to-divisor vs the real
    # frame count (8 here), not the raw BF_SPEC_TILE default; the
    # transpose is the shape's (resolve_transpose): 256 = 16 x 16 with
    # rfactor 4 leaves a minor dim of 4, not lane-native -> epilogue
    assert seen == {'nfft': 256, 'rfactor': 4, 'tile': 8,
                    'prec': None, 'trans': 'epilogue'}


def test_split_override(monkeypatch):
    monkeypatch.setenv('BF_SPEC_SPLIT', '128')
    got, want, rel = _run(T=4, nfft=4096, rfactor=4, time_tile=4)
    assert rel < 1e-5
    # invalid overrides fall back to the square split
    monkeypatch.setenv('BF_SPEC_SPLIT', 'nope')
    got, want, rel = _run(T=4, nfft=4096, rfactor=4, time_tile=4)
    assert rel < 1e-5


def test_mesh_scope_substitutes_kernel_per_shard(monkeypatch):
    """Under BlockScope(mesh=...) the FusedBlock substitutes the
    Pallas kernel PER SHARD via shard_map on the frame axis, and the
    pipeline output still matches the oracle."""
    from bifrost_tpu.ops import spectrometer as spec
    from bifrost_tpu.dtype import ci8 as ci8_dtype
    from bifrost_tpu.parallel.mesh import create_mesh
    import jax

    if len(jax.devices()) < 8:
        pytest.skip('needs the 8-device virtual mesh')

    calls = []
    real = spec.fused_spectrometer

    def fake(v, **kw):
        calls.append(tuple(v.shape))
        kw.pop('interpret', None)
        return real(v, interpret=True, **kw)

    monkeypatch.setattr(spec, 'choose_precision', lambda *a, **k: None)
    monkeypatch.setattr(spec, 'fused_spectrometer', fake)

    T, NF = 16, 256
    rng = np.random.RandomState(6)
    raw = np.zeros((T, 2, NF), dtype=ci8_dtype)
    raw['re'] = rng.randint(-8, 8, size=(T, 2, NF))
    raw['im'] = rng.randint(-8, 8, size=(T, 2, NF))
    out = _run_fused_ci8_chain(raw, rfactor=4,
                               mesh=create_mesh({'sp': 8}))
    # matched at the per-shard shape: T/8 frames per device
    assert (T // 8, 2, NF, 2) in calls, calls
    volt = np.stack([raw['re'], raw['im']], axis=-1).astype(np.int8)
    want = spectrometer_oracle(volt, rfactor=4)
    assert out.shape == (T, 4, NF // 4)
    assert np.max(np.abs(out - want)) / np.max(np.abs(want)) < 1e-4


def test_mesh_scope_falls_back_to_gspmd_chain(monkeypatch):
    """When the kernel is not admitted (choose_precision 'off'), the
    mesh path still runs the GSPMD-sharded XLA chain."""
    from bifrost_tpu.ops import spectrometer as spec
    from bifrost_tpu.dtype import ci8 as ci8_dtype
    from bifrost_tpu.parallel.mesh import create_mesh
    import jax

    if len(jax.devices()) < 8:
        pytest.skip('needs the 8-device virtual mesh')

    monkeypatch.setattr(spec, 'choose_precision',
                        lambda *a, **k: 'off')
    T, NF = 8, 256
    rng = np.random.RandomState(7)
    raw = np.zeros((T, 2, NF), dtype=ci8_dtype)
    raw['re'] = rng.randint(-8, 8, size=(T, 2, NF))
    raw['im'] = rng.randint(-8, 8, size=(T, 2, NF))
    out = _run_fused_ci8_chain(raw, rfactor=4,
                               mesh=create_mesh({'sp': 8}))
    volt = np.stack([raw['re'], raw['im']], axis=-1).astype(np.int8)
    want = spectrometer_oracle(volt, rfactor=4)
    assert np.max(np.abs(out - want)) / np.max(np.abs(want)) < 1e-4


def test_fused_block_publishes_impl_record(monkeypatch, tmp_path):
    """The FusedBlock records the path its plan executes (impl_info)
    and publishes it to ProcLog <block>/impl, so benchmarks read what
    ran instead of re-deriving the substitution decision (VERDICT r3
    item 4)."""
    import bifrost_tpu as bf
    from bifrost_tpu import proclog as proclog_mod
    from bifrost_tpu.ops import spectrometer as spec
    from bifrost_tpu.dtype import ci8 as ci8_dtype
    from bifrost_tpu.stages import FftStage, DetectStage, ReduceStage
    import os
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from util import NumpySourceBlock, GatherSink, simple_header

    monkeypatch.setenv('BF_PROCLOG_DIR', str(tmp_path))
    real = spec.fused_spectrometer
    monkeypatch.setattr(spec, 'choose_precision', lambda *a, **k: None)
    monkeypatch.setattr(
        spec, 'fused_spectrometer',
        lambda v, **kw: real(v, **dict(kw, interpret=True)))

    T, NF, RF = 8, 256, 4
    rng = np.random.RandomState(3)
    raw = np.zeros((T, 2, NF), dtype=ci8_dtype)
    raw['re'] = rng.randint(-32, 32, size=(T, 2, NF))
    raw['im'] = rng.randint(-32, 32, size=(T, 2, NF))
    hdr = simple_header([-1, 2, NF], 'ci8',
                        labels=['time', 'pol', 'fine_time'])
    with bf.Pipeline() as p:
        src = NumpySourceBlock([raw], hdr, gulp_nframe=T)
        b = bf.blocks.copy(src, space='tpu')
        fb = bf.blocks.fused(b, [
            FftStage('fine_time', axis_labels='freq'),
            DetectStage('stokes', axis='pol'),
            ReduceStage('freq', RF),
        ])
        b = bf.blocks.copy(fb, space='system')
        sink = GatherSink(b)
        p.run()
    assert sink.result().shape == (T, 4, NF // RF)
    assert fb.impl_info['impl'] == 'pallas-spectrometer'
    assert fb.impl_info['rfactor'] == RF
    assert fb.impl_info['nfft'] == NF
    # published to the proclog tree
    logs = proclog_mod.load_by_pid(os.getpid())
    impl_logs = [blk['impl'] for blk in logs.values() if 'impl' in blk]
    assert any(v.get('impl') == 'pallas-spectrometer'
               for v in impl_logs), logs


def test_compose_stages_is_the_shared_chain_constructor():
    """compose_stages builds the same function a FusedBlock compiles;
    the driver entry (__graft_entry__) goes through it (VERDICT r3
    item 6)."""
    from bifrost_tpu.stages import (FftStage, DetectStage, ReduceStage,
                                    compose_stages, walk_headers)
    T, NF, RF = 8, 64, 4
    hdr = {'name': 's', 'time_tag': 0,
           '_tensor': {'shape': [-1, 2, NF], 'dtype': 'ci8',
                       'labels': ['time', 'pol', 'fine_time'],
                       'scales': [[0, 1]] * 3, 'units': [None] * 3}}
    st = [FftStage('fine_time', axis_labels='freq'),
          DetectStage('stokes', axis='pol'),
          ReduceStage('freq', RF)]
    headers = walk_headers(st, hdr)
    fn, info = compose_stages(st, headers, (T, 2, NF, 2), 'int8')
    assert info['impl'] in ('xla-fused', 'pallas-spectrometer')
    import jax.numpy as jnp
    rng = np.random.RandomState(5)
    volt = rng.randint(-32, 32, size=(T, 2, NF, 2)).astype(np.int8)
    got = np.asarray(fn(jnp.asarray(volt)))
    want = spectrometer_oracle(volt, rfactor=RF)
    rel = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert got.shape == (T, 4, NF // RF)
    assert rel < 1e-5
