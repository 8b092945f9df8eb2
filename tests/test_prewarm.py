"""Probe/compile pre-warming: measured probes and plan builds must run
at sequence start (on_sequence), never inside on_data — in the
reference's operating regime a first-gulp latency spike in a capture
pipeline is a dropped packet (its blocks pay plan build at sequence
start too: e.g. fdmt plan init in on_sequence, reference
python/bifrost/blocks/fdmt.py:38-140)."""

import numpy as np

import bifrost_tpu as bf
from tests.util import NumpySourceBlock, GatherSink, simple_header


def test_fused_plan_builds_outside_on_data(monkeypatch):
    """FusedBlock builds + compiles its plan during on_sequence; the
    steady-state gulps must not trigger a plan build."""
    from bifrost_tpu.blocks.fused import FusedBlock
    from bifrost_tpu.stages import FftStage, DetectStage
    from bifrost_tpu.dtype import ci8 as ci8_dtype

    state = {'in_on_data': False}
    builds = []
    orig_build = FusedBlock._build_plan
    orig_on_data = FusedBlock.on_data

    def spy_build(self, shape, dtype, **kw):
        builds.append(state['in_on_data'])
        return orig_build(self, shape, dtype, **kw)

    def spy_on_data(self, ispan, ospan):
        state['in_on_data'] = True
        try:
            return orig_on_data(self, ispan, ospan)
        finally:
            state['in_on_data'] = False

    monkeypatch.setattr(FusedBlock, '_build_plan', spy_build)
    monkeypatch.setattr(FusedBlock, 'on_data', spy_on_data)

    rng = np.random.RandomState(0)
    raw = np.zeros((16, 2, 16), dtype=ci8_dtype)
    raw['re'] = rng.randint(-16, 16, size=(16, 2, 16))
    raw['im'] = rng.randint(-16, 16, size=(16, 2, 16))
    with bf.Pipeline() as p:
        hdr = simple_header([-1, 2, 16], 'ci8',
                            labels=['time', 'pol', 'fine_time'])
        src = NumpySourceBlock([raw[:8], raw[8:]], hdr, gulp_nframe=8)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fused(b, [FftStage('fine_time', axis_labels='freq'),
                                DetectStage('stokes', axis='pol')])
        b = bf.blocks.copy(b, space='system')
        sink = GatherSink(b)
        p.run()
    out = sink.result()
    assert out.shape == (16, 4, 16)
    assert builds, 'plan was never built'
    assert not any(builds), \
        'FusedBlock plan build executed inside on_data (not pre-warmed)'


def test_fdmt_probe_outside_on_data(monkeypatch):
    """With measured core probing forced on, the probe must run during
    on_sequence pre-warm; neither the steady gulps nor the ragged final
    gulp may probe inside on_data (the tail reuses the locked winner)."""
    from bifrost_tpu.blocks.fdmt import FdmtBlock
    from bifrost_tpu.ops.fdmt import Fdmt

    monkeypatch.setenv('BF_FDMT_PROBE', '1')
    state = {'in_on_data': False}
    probes = []
    orig_probe = Fdmt._probe_cores
    orig_on_data = FdmtBlock.on_data

    def spy_probe(self, cands, shape, negative_delays):
        probes.append((state['in_on_data'], tuple(shape)))
        return orig_probe(self, cands, shape, negative_delays)

    def spy_on_data(self, ispan, ospan):
        state['in_on_data'] = True
        try:
            return orig_on_data(self, ispan, ospan)
        finally:
            state['in_on_data'] = False

    monkeypatch.setattr(Fdmt, '_probe_cores', spy_probe)
    monkeypatch.setattr(FdmtBlock, 'on_data', spy_on_data)

    nchan, T = 8, 64
    rng = np.random.RandomState(0)
    x = rng.rand(nchan, T).astype(np.float32)
    hdr = {
        'name': 'prewarm-test', 'time_tag': 0,
        '_tensor': {
            'shape': [nchan, -1],
            'dtype': 'f32',
            'labels': ['freq', 'time'],
            'scales': [[100.0, 1.0], [0.0, 1e-3]],
            'units': ['MHz', 's'],
        },
    }
    gulps = [x[:, i * 16:(i + 1) * 16].copy() for i in range(4)]

    class FreqSource(bf.SourceBlock):
        def create_reader(self, name):
            class R:
                def __enter__(self):
                    return self

                def __exit__(self, *e):
                    return False
            return R()

        def on_sequence(self, reader, name):
            self.i = 0
            return [dict(hdr)]

        def on_data(self, reader, ospans):
            if self.i >= len(gulps):
                return [0]
            g = gulps[self.i]
            self.i += 1
            d = ospans[0].data.as_numpy()
            d[...] = g
            return [g.shape[1]]

    collected = []

    class DMSink(bf.SinkBlock):
        def on_sequence(self, iseq):
            pass

        def on_data(self, ispan):
            collected.append(np.array(ispan.data.as_numpy()))

    with bf.Pipeline() as p:
        src = FreqSource(['freq'], gulp_nframe=16)
        b = bf.blocks.copy(src, space='tpu')
        b = FdmtBlock(b, max_delay=9)
        b = bf.blocks.copy(b, space='system')
        DMSink(b)
        p.run()

    assert collected, 'pipeline produced no output'
    assert probes, 'core probe never ran (BF_FDMT_PROBE=1 was set)'
    in_data = [s for flag, s in probes if flag]
    assert not in_data, \
        'FDMT core probe executed inside on_data at shapes %s' % in_data


def test_xcorr_probe_outside_on_data(monkeypatch, tmp_path):
    """With measured probing forced on, CorrelateBlock's X-engine
    probe must run at on_sequence (XEngine.prewarm); no mprobe.select
    may execute inside on_data — the traced call finds the winner in
    the cache."""
    from bifrost_tpu.blocks.correlate import CorrelateBlock
    from bifrost_tpu.ops import mprobe
    from bifrost_tpu.ops import linalg as L
    from bifrost_tpu.dtype import ci8 as ci8_dtype

    monkeypatch.setenv('BF_LINALG_PROBE', '1')
    monkeypatch.setenv('BF_CACHE_DIR', str(tmp_path))
    monkeypatch.setattr(L, '_xcorr_chosen', {})
    monkeypatch.setattr(mprobe, '_cache', {})
    state = {'in_on_data': False}
    probes = []
    orig_select = mprobe.select
    orig_on_data = CorrelateBlock.on_data

    def spy_select(name, *a, **k):
        probes.append((state['in_on_data'], name))
        return orig_select(name, *a, **k)

    def spy_on_data(self, ispan, ospan):
        state['in_on_data'] = True
        try:
            return orig_on_data(self, ispan, ospan)
        finally:
            state['in_on_data'] = False

    monkeypatch.setattr(mprobe, 'select', spy_select)
    monkeypatch.setattr(CorrelateBlock, 'on_data', spy_on_data)

    rng = np.random.RandomState(3)
    T, F, S, P = 16, 2, 3, 2
    raw = np.zeros((T, F, S, P), dtype=ci8_dtype)
    raw['re'] = rng.randint(-16, 16, size=raw.shape)
    raw['im'] = rng.randint(-16, 16, size=raw.shape)
    hdr = simple_header([-1, F, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'],
                        gulp_nframe=8)
    with bf.Pipeline() as p:
        src = NumpySourceBlock([raw[:8], raw[8:]], hdr, gulp_nframe=8)
        b = bf.blocks.copy(src, space='tpu')
        corr = bf.blocks.correlate(b, nframe_per_integration=16)
        b = bf.blocks.copy(corr, space='system')
        sink = GatherSink(b)
        p.run()
    assert sink.result() is not None
    xsel = [(ind, n) for ind, n in probes if n == 'xengine']
    assert xsel, 'X-engine probe never ran (prewarm missing)'
    assert not any(ind for ind, _ in xsel), \
        'X-engine probe executed inside on_data (not pre-warmed)'
    # the prewarmed winner must be keyed at the shape the traced
    # on_data call actually looks up — a t_eff/shape mismatch would
    # pass the asserts above while the gulps silently run the default
    n = S * P
    key = corr.engine._key((8, F, n), 'int8', True)
    assert key in corr.engine.chosen, (key, corr.engine.chosen)
