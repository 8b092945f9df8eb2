"""Compiled pipeline segments (bifrost_tpu.segments; docs/perf.md
"Compiled pipeline segments"): fusing a device-block chain into ONE
XLA program must give the unfused chain's answer (each held to the
float64 oracle; integer and same-program comparisons stay exact),
elide the interior rings completely (0 member dispatches, 0 ring traffic), keep
observability alive through synthesis, refuse every unprovable
boundary with the exact BF-I190 reason, and support the auto-tuner's
split/re-fuse knob."""

import os

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu import segments as bseg
from bifrost_tpu.blocks.fft import _StageBlock
from bifrost_tpu.macro import split_ranges
from bifrost_tpu.ops.spectrometer import spectrometer_oracle
from bifrost_tpu.stages import DetectStage
from bifrost_tpu.telemetry import counters, histograms
from tests.util import NumpySourceBlock, GatherSink, simple_header

NT, NP, NF, RF = 32, 2, 64, 4


def _volts(ngulp, seed=3):
    rng = np.random.RandomState(seed)
    gulps = []
    for _ in range(ngulp):
        raw = np.zeros((NT, NP, NF), dtype=np.dtype([('re', 'i1'),
                                                     ('im', 'i1')]))
        raw['re'] = rng.randint(-64, 64, raw.shape)
        raw['im'] = rng.randint(-64, 64, raw.shape)
        gulps.append(raw)
    return gulps


def _hdr():
    return simple_header([-1, NP, NF], 'ci8',
                         labels=['time', 'pol', 'fine_time'])


#: The fused segment and the unfused chain are DIFFERENT XLA programs,
#: and XLA promises no bit-identity between programs: under jax 0.9.0
#: the one-program FFT->detect->reduce differs from the three-program
#: chain in the last float32 bit.  So each is held to the float64
#: oracle.  A 64-point f32 FFT of int8 voltages, Stokes squares and a
#: sum of 4 measure 1.4e-7 of the peak here; 2e-6 leaves an order of
#: magnitude and is a thousand times tighter than a bf16 path (2^-9 =
#: 2e-3) could meet.  Exact equality stays for integer paths and for
#: two runs of ONE program (test_segment_is_deterministic).
ORACLE_RTOL = 2e-6


def _assert_matches_oracle(out, ngulp=6):
    volt = np.concatenate([np.stack([g['re'], g['im']], axis=-1)
                           for g in _volts(ngulp)])
    want = spectrometer_oracle(volt, rfactor=RF)
    assert out.shape == want.shape
    rel = np.max(np.abs(out - want)) / np.max(np.abs(want))
    assert rel <= ORACLE_RTOL, rel


def _run_chain(segments=None, gulp_batch=1, ngulp=6, donate=None,
               split=None, **scope):
    """src -> copy h2d -> fft -> detect -> reduce -> copy d2h -> sink
    as SEPARATE stage blocks (the segment compiler's raw material)."""
    counters.reset()
    with bf.Pipeline(segments=segments, gulp_batch=gulp_batch,
                     donate=donate, sync_depth=4, **scope) as p:
        src = NumpySourceBlock(_volts(ngulp), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
        b = bf.blocks.detect(b, mode='stokes', axis='pol')
        b = bf.blocks.reduce(b, 'freq', RF)
        b2 = bf.blocks.copy(b, space='system')
        sink = GatherSink(b2)
        if split is not None:
            # emulate the auto-tuner: compile, then set the split
            # knob before the first sequence resolves it
            segs = bseg.compile_pipeline(p)
            assert segs, 'expected a segment to compile'
            bseg.retune_split(segs[0], split)
        p.run()
    return sink.result(), p, counters.snapshot()


def _type_name(block_name):
    """'Pipeline_3/FftBlock_7' -> 'FftBlock' (instance counters are
    process-global, so assertions key on the type)."""
    return block_name.split('/')[-1].rsplit('_', 1)[0]


def _reasons(pipeline):
    """{(producer block type, reason)} from the shared planner — the
    set the BF-I190 diagnostics mirror."""
    _chains, boundaries = bseg.plan(pipeline)
    return {(_type_name(b['producer']), b['reason'])
            for b in boundaries}


def _i190(diags):
    return [d for d in diags if d.code == 'BF-I190']


# ---------------------------------------------------------------------------
# fusion correctness + elision
# ---------------------------------------------------------------------------

def test_segment_fuses_and_elides():
    base, p0, _ = _run_chain(None)
    out, p1, snap = _run_chain('auto')
    _assert_matches_oracle(base)
    _assert_matches_oracle(out)
    # 7 blocks -> 5: fft/detect/reduce replaced by one SegmentBlock
    assert len(p0.blocks) == 7
    assert len(p1.blocks) == 5
    assert len(p1._segments) == 1
    seg = p1._segments[0]
    assert [_type_name(m) for m in seg._members] == \
        ['FftBlock', 'DetectBlock', 'ReduceBlock']
    # plan-time accounting
    assert snap['segment.compiled'] == 1
    assert snap['segment.elided_rings'] == 2
    assert snap['segment.dispatches'] == 6
    assert snap['segment.gulps'] == 6
    # interior rings registered NO span traffic: no commit counter
    # ever appears for them
    for ring in seg._elided:
        assert counters.get('ring.%s.gulps' % ring) == 0
    # members dispatched ZERO times (block.*.dispatches == segments,
    # not blocks) but their synthesized gulps counters stay live
    for m in seg._members:
        assert ('block.%s.dispatches' % m) not in snap
        assert snap['block.%s.gulps' % m] == 6
    # SLO ages survive fusion: per-member commit-age histograms fed
    # from the segment's markers (the source stamps trace context)
    for m in seg._members:
        h = histograms.get('slo.%s.commit_age_s' % m)
        assert h is not None and h.count == 6


def test_segment_is_deterministic():
    """Two runs of ONE program agree to the bit."""
    first, _, _ = _run_chain('auto')
    again, _, _ = _run_chain('auto')
    assert np.array_equal(first, again)


def test_segment_composes_with_macro_gulp():
    base, _, _ = _run_chain(None, ngulp=8)
    out, p, snap = _run_chain('auto', gulp_batch=4, ngulp=8)
    _assert_matches_oracle(base, 8)
    _assert_matches_oracle(out, 8)
    # one dispatch per K-gulp span: 8 gulps at K=4 = 2 dispatches
    assert snap['segment.dispatches'] == 2
    assert snap['segment.gulps'] == 8
    seg = p._segments[0]
    assert seg.impl_info.get('batch') == 4


def test_segment_threads_donation_through_interiors():
    base, _, _ = _run_chain(None, ngulp=8)
    out, _, snap = _run_chain('auto', gulp_batch=4, ngulp=8,
                              donate=True)
    _assert_matches_oracle(base, 8)
    _assert_matches_oracle(out, 8)
    assert snap.get('donation.hits', 0) > 0


def test_force_mode_raises_without_a_fusable_chain():
    with pytest.raises(bseg.SegmentPlanError) as err:
        # a single device block: no chain of >= 2 can form
        counters.reset()
        with bf.Pipeline(segments='force') as p:
            src = NumpySourceBlock(_volts(1), _hdr(), gulp_nframe=NT)
            b = bf.blocks.copy(src, space='tpu')
            b = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
            GatherSink(bf.blocks.copy(b, space='system'))
            p.run()
    assert 'reason' not in str(err.value) or 'host' in str(err.value)


def test_force_mode_runs_when_a_segment_forms():
    base, _, _ = _run_chain(None)
    out, p, _ = _run_chain('force')
    _assert_matches_oracle(base)
    _assert_matches_oracle(out)
    assert len(p._segments) == 1


# ---------------------------------------------------------------------------
# fusion-breaking boundaries: exact BF-I190 reason + unfused-but-
# correct execution
# ---------------------------------------------------------------------------

def test_boundary_multi_reader():
    base, _, _ = _run_chain(None)
    counters.reset()
    with bf.Pipeline(segments='auto', sync_depth=4) as p:
        src = NumpySourceBlock(_volts(6), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        f = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
        d = bf.blocks.detect(f, mode='stokes', axis='pol')
        r = bf.blocks.reduce(d, 'freq', RF)
        sink = GatherSink(bf.blocks.copy(r, space='system'))
        # second reader on the fft->detect ring: that boundary must
        # not fuse...
        tap_sink = GatherSink(bf.blocks.copy(f, space='system'))
        assert ('FftBlock', 'multi_reader') in _reasons(p)
        p.run()
    # ...but detect->reduce still fuses (the safe sub-chain), and the
    # stream is the fully unfused run's, to the oracle's tolerance
    _assert_matches_oracle(base)
    _assert_matches_oracle(sink.result())
    assert counters.get('segment.compiled') == 1
    assert counters.get('segment.elided_rings') == 1
    assert len(p._segments) == 1 and len(p._segments[0]._members) == 2
    assert tap_sink.result() is not None


def test_boundary_tap_via_ring_view():
    base, _, _ = _run_chain(None)
    counters.reset()
    with bf.Pipeline(segments='auto', sync_depth=4) as p:
        src = NumpySourceBlock(_volts(6), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        f = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
        tap = bf.views.rename_axis(f, 'freq', 'chan')
        d = bf.blocks.detect(tap, mode='stokes', axis='pol')
        r = bf.blocks.reduce(d, 'chan', RF)
        sink = GatherSink(bf.blocks.copy(r, space='system'))
        assert ('FftBlock', 'tap') in _reasons(p)
        p.run()
    _assert_matches_oracle(base)
    _assert_matches_oracle(sink.result())
    # detect->reduce still fused behind the tap
    assert counters.get('segment.compiled') == 1


class _OverlapDetect(_StageBlock):
    """An otherwise-eligible stage block that declares FIR-style
    overlap history — a segment must never swallow it."""

    def __init__(self, iring, **kwargs):
        super(_OverlapDetect, self).__init__(
            iring, DetectStage('stokes', axis='pol'), **kwargs)

    def define_input_overlap_nframe(self, iseq):
        return 4


def _build_chain(mutate):
    """Build-only chain for boundary-reason assertions; ``mutate``
    constructs the middle blocks and returns nothing."""
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_volts(1), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        tail = mutate(b)
        GatherSink(bf.blocks.copy(tail, space='system'))
    return p


def test_boundary_overlap():
    def mutate(b):
        f = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
        return _OverlapDetect(f)
    p = _build_chain(mutate)
    assert ('FftBlock', 'overlap') in _reasons(p)


def test_boundary_host_blocks():
    # the plain chain with segments OFF: the copy movers are 'host'
    # boundaries, the stage-stage boundaries report 'disabled'
    def mutate(b):
        f = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
        return bf.blocks.detect(f, mode='stokes', axis='pol')
    p = _build_chain(mutate)
    reasons = _reasons(p)
    assert ('CopyBlock', 'host') in reasons
    assert ('DetectBlock', 'host') in reasons
    assert ('FftBlock', 'disabled') in reasons


def test_boundary_bridge_endpoint():
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_volts(1), _hdr(), gulp_nframe=NT)
        bf.blocks.bridge_sink(src, '127.0.0.1', 1)
    assert ('NumpySourceBlock', 'bridge') in _reasons(p)


def test_boundary_mesh_reshard_seam():
    import jax
    if jax.device_count() < 2:
        pytest.skip('needs a multi-device host platform')
    from bifrost_tpu.parallel import create_mesh
    mesh = create_mesh({'sp': 2})

    def mutate(b):
        with bf.block_scope(mesh=mesh):
            f = bf.blocks.fft(b, axes='fine_time',
                              axis_labels='freq')
        return bf.blocks.detect(f, mode='stokes', axis='pol')
    p = _build_chain(mutate)
    assert ('FftBlock', 'mesh_reshard') in _reasons(p)


def test_boundary_tunables_and_supervision_and_unguaranteed():
    def mutate(b):
        f = bf.blocks.fft(b, axes='fine_time', axis_labels='freq',
                          core=0)
        return bf.blocks.detect(f, mode='stokes', axis='pol', core=1)
    assert ('FftBlock', 'tunables') in _reasons(_build_chain(mutate))

    def mutate2(b):
        f = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
        return bf.blocks.detect(f, mode='stokes', axis='pol',
                                on_failure='restart')
    assert ('FftBlock', 'supervision') in \
        _reasons(_build_chain(mutate2))

    def mutate3(b):
        f = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
        return bf.blocks.detect(f, mode='stokes', axis='pol',
                                guarantee=False)
    assert ('FftBlock', 'unguaranteed') in \
        _reasons(_build_chain(mutate3))


def test_validate_reports_bf_i190_with_reasons():
    """Pipeline.validate() mirrors the planner: one BF-I190 per
    unfused device-ring boundary, message carrying the reason slug."""
    def mutate(b):
        f = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
        d = bf.blocks.detect(f, mode='stokes', axis='pol')
        return bf.blocks.reduce(d, 'freq', RF)
    p = _build_chain(mutate)
    diags = _i190(p.validate())
    # 4 device-ring boundaries: copy->fft (host), fft->detect and
    # detect->reduce (disabled), reduce->copy (host)
    assert len(diags) == 4
    msgs = ' | '.join(d.message for d in diags)
    assert 'reason: disabled' in msgs and 'reason: host' in msgs
    for d in diags:
        assert d.severity == 'info' and d.ring


def test_ringcheck_sees_no_traffic_on_elided_interiors(monkeypatch):
    """BF_RINGCHECK=1 over a fused run: the protocol checker stays
    clean and the elided interior rings register zero span traffic."""
    monkeypatch.setenv('BF_RINGCHECK', '1')
    from bifrost_tpu.analysis import ringcheck
    base, _, _ = _run_chain(None)
    out, p, snap = _run_chain('auto')
    monkeypatch.delenv('BF_RINGCHECK')
    ringcheck.reconfigure()
    _assert_matches_oracle(base)
    _assert_matches_oracle(out)
    assert snap.get('ringcheck.violations', 0) == 0
    for ring in p._segments[0]._elided:
        assert counters.get('ring.%s.gulps' % ring) == 0


# ---------------------------------------------------------------------------
# in-program halo carry: the lifted 'overlap' boundary (BF-I192;
# docs/perf.md "FDMT FRB search")
# ---------------------------------------------------------------------------

F_DM, T_DM, G_DM, MD_DM, NTAP_DM = 8, 256, 32, 8, 4


class _FilterbankSource(bf.SourceBlock):
    """Time-LAST (freq, time) f32 stream — the dedispersion chain's
    native layout (NumpySourceBlock is frame-axis-first)."""

    def __init__(self, **kwargs):
        super(_FilterbankSource, self).__init__(
            ['filterbank'], gulp_nframe=G_DM, **kwargs)

    def create_reader(self, name):
        class R(object):
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False
        return R()

    def on_sequence(self, reader, name):
        rng = np.random.RandomState(11)
        self._data = rng.randn(F_DM, T_DM).astype(np.float32)
        self._pos = 0
        return [{'name': 'filterbank', 'time_tag': 0,
                 '_tensor': {'shape': [F_DM, -1], 'dtype': 'f32',
                             'labels': ['freq', 'time'],
                             'scales': [[100.0, 1.0], [0.0, 1e-3]],
                             'units': ['MHz', 's']}}]

    def on_data(self, reader, ospans):
        if self._pos >= T_DM:
            return [0]
        n = min(ospans[0].nframe, T_DM - self._pos)
        ospans[0].data.as_numpy()[:, :n] = \
            self._data[:, self._pos:self._pos + n]
        self._pos += n
        return [n]


def _run_dm_chain(segments=None, gulp_batch=1):
    """src -> copy h2d -> fdmt -> matched_filter -> threshold -> copy
    d2h -> sink: every interior boundary is an overlap boundary the
    halo carry must lift."""
    counters.reset()
    collected = []

    class _TimeLastSink(bf.SinkBlock):
        def on_sequence(self, iseq):
            pass

        def on_data(self, ispan):
            from bifrost_tpu.xfer import to_host
            collected.append(np.array(to_host(ispan.data), copy=True))

    with bf.Pipeline(segments=segments, gulp_batch=gulp_batch,
                     sync_depth=4) as p:
        src = _FilterbankSource()
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fdmt_stage(b, max_delay=MD_DM)
        b = bf.blocks.matched_filter(b, NTAP_DM)
        b = bf.blocks.threshold(b, 0.5)
        b = bf.blocks.copy(b, space='system')
        _TimeLastSink(b)
        p.run()
    out = np.concatenate(collected, axis=-1)
    return out, p, counters.snapshot()


def test_halo_carry_fuses_overlap_chain_byte_identical():
    """A provably-safe overlap chain fuses WITH the in-program halo
    carry: byte-identical output, one segment, interior rings elided
    with zero traffic, and the segment.overlap_carried counter
    records each lifted boundary."""
    base, p0, snap0 = _run_dm_chain(None)
    assert snap0.get('segment.overlap_carried', 0) == 0
    out, p, snap = _run_dm_chain('force')
    assert np.array_equal(base, out)
    assert len(p._segments) == 1
    seg = p._segments[0]
    assert [_type_name(m) for m in seg._members] == \
        ['FdmtStageBlock', 'MatchedFilterBlock', 'ThresholdBlock']
    # both interior boundaries (fdmt->mf, mf->threshold) carried
    assert snap['segment.overlap_carried'] == 1
    assert snap['segment.compiled'] == 1
    assert snap['segment.elided_rings'] == 2
    for ring in seg._elided:
        assert counters.get('ring.%s.gulps' % ring) == 0
    for m in seg._members:
        assert ('block.%s.dispatches' % m) not in snap


def test_halo_carry_macro_gulp_byte_identical():
    """K>1 macro gulps over the carried segment: the ghost history is
    sliced from the span head ONCE and the interior handoffs are
    elided inside the scanned program — still byte-identical, with
    K fewer dispatches."""
    base, _, _ = _run_dm_chain(None)
    out, p, snap = _run_dm_chain('force', gulp_batch=4)
    assert np.array_equal(base, out)
    assert snap['segment.overlap_carried'] == 1
    # 8 logical gulps at K=4 -> 2 dispatches
    assert snap['segment.dispatches'] == 2
    assert snap['segment.gulps'] == 8


@pytest.mark.parametrize('segments,gulp_batch', [
    (None, 1), ('force', 1), ('force', 4)])
def test_dm_chain_matches_float64_oracle(segments, gulp_batch):
    """Every way of running the dedispersion chain (ring overlap
    between separate blocks, the in-program halo carry, the carry
    under macro gulps) gives what a sequential float64 reference of
    the same semantics gives: numpy FDMT, a fixed-order boxcar, the
    threshold.  Tolerance: the FDMT engine's own oracle gate
    (float32 sums of at most nchan * ntap terms), relative to the
    reference's peak.  A cell the reference puts within that
    tolerance of the threshold may fall on either side and is left
    out."""
    from bifrost_tpu.ops.fdmt import fdmt_numpy, fdmt_gate_rtol
    out, _, _ = _run_dm_chain(segments, gulp_batch=gulp_batch)
    data = np.random.RandomState(11).randn(F_DM, T_DM)
    dm = fdmt_numpy(F_DM, MD_DM, 100.0, 1.0, data.astype(np.float32))
    nvalid = dm.shape[-1] - (NTAP_DM - 1)
    mf = sum(dm[:, i:i + nvalid] for i in range(NTAP_DM))
    n = out.shape[-1]
    assert out.shape[0] == MD_DM and 0 < n <= nvalid
    mf = mf[:, :n]
    tol = fdmt_gate_rtol() * np.max(np.abs(mf))
    want = np.where(mf >= 0.5, mf, 0.0)
    decided = np.abs(mf - 0.5) > tol
    assert decided.mean() > 0.99
    np.testing.assert_allclose(out[decided], want[decided],
                               rtol=0, atol=tol)


def test_boundary_overlap_carried_reason():
    """The planner reports 'overlap_carried' (a FUSING record) for
    derivable stage overlap, and still cuts with 'overlap' when the
    consumer's declaration cannot be derived from its stages
    (test_boundary_overlap holds the mismatch case)."""
    with bf.Pipeline() as p:
        src = _FilterbankSource()
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fdmt_stage(b, max_delay=MD_DM)
        b = bf.blocks.matched_filter(b, NTAP_DM)
        GatherSink(bf.blocks.copy(b, space='system'))
    _chains, boundaries = bseg.plan(p, 'auto')
    reasons = {(_type_name(b['producer']), b['reason'])
               for b in boundaries}
    assert ('FdmtStageBlock', 'overlap_carried') in reasons
    assert ('FdmtStageBlock', 'overlap') not in reasons


def test_validate_reports_bf_i192_for_carried_boundary():
    """Pipeline.validate() surfaces each lifted overlap boundary as a
    BF-I192 info (never an error: carry is an optimization, and its
    silent disengage is what telemetry_diff watches)."""
    with bf.Pipeline(segments='auto') as p:
        src = _FilterbankSource()
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fdmt_stage(b, max_delay=MD_DM)
        b = bf.blocks.matched_filter(b, NTAP_DM)
        GatherSink(bf.blocks.copy(b, space='system'))
    diags = [d for d in p.validate() if d.code == 'BF-I192']
    assert len(diags) == 1
    assert diags[0].severity == 'info'
    assert 'halo carry' in diags[0].message
    assert not [d for d in p.validate()
                if d.code == 'BF-I190' and 'overlap' in d.message]


# ---------------------------------------------------------------------------
# split/re-fuse (the auto-tuner's segment-boundary knob)
# ---------------------------------------------------------------------------

def test_split_ranges_helper():
    assert split_ranges([1, 1, 1], 0) == [(0, 3)]
    assert split_ranges([1, 1, 1], 1) == [(0, 2), (2, 3)]
    assert split_ranges([1, 1, 1], 2) == [(0, 1), (1, 2), (2, 3)]
    assert split_ranges([3, 1], 1) == [(0, 3), (3, 4)]
    # clamps to the boundary count
    assert split_ranges([2, 1, 2], 5) == [(0, 2), (2, 3), (3, 5)]


@pytest.mark.parametrize('split,k,expected_disp', [(1, 1, 16),
                                                   (2, 4, 6)])
def test_split_execution_byte_identical(split, k, expected_disp):
    base, _, _ = _run_chain(None, ngulp=8)
    out, p, snap = _run_chain('auto', gulp_batch=k, ngulp=8,
                              split=split)
    assert np.array_equal(base, out)
    seg = p._segments[0]
    assert seg._splits_active == split
    # split+1 dispatches per (macro-)gulp set, still zero interior
    # ring traffic — and block.<segment>.dispatches agrees with the
    # segment.* counters (real compiled-program dispatches)
    assert snap['segment.dispatches'] == expected_disp
    assert snap['block.%s.dispatches' % seg.name] == expected_disp
    for ring in seg._elided:
        assert counters.get('ring.%s.gulps' % ring) == 0


def test_retune_split_clamps_and_applies_next_sequence():
    _, p, _ = _run_chain('auto')
    seg = p._segments[0]
    assert bseg.retune_split(seg, 99) == 2      # 3 members -> max 2
    assert bseg.retune_split(seg, -1) == 0
    assert bseg.retune_split(seg, 1) == 1
    # resolution happens per sequence, not retroactively
    assert seg._splits_active == 0
    assert seg._resolve_splits() == 1


def test_synthesized_member_spans(monkeypatch, tmp_path):
    """With span recording armed, member blocks get synthesized
    compute spans tagged with their segment (trace timeline survives
    fusion)."""
    from bifrost_tpu.telemetry import spans
    monkeypatch.setenv('BF_TRACE_FILE', str(tmp_path / 'trace.json'))
    try:
        out, p, _ = _run_chain('auto')
        seg = p._segments[0]
        synth = [(name, ev) for name, ev in spans.events()
                 if isinstance(ev[4], dict)
                 and ev[4].get('synthesized')]
        names = {ev[0] for _t, ev in synth}
        for m in seg._members:
            assert ('%s.on_data' % m) in names
        for _t, ev in synth:
            assert ev[4]['segment'] == seg.name
    finally:
        monkeypatch.delenv('BF_TRACE_FILE')
        spans.reconfigure()


def test_member_perf_proclogs_publish(monkeypatch):
    """like_top's discovery path: member perf ProcLogs keep
    publishing, carrying the in_segment marker and the segment's
    amortization ratio."""
    monkeypatch.setenv('BF_PROCLOG_INTERVAL', '0')
    from bifrost_tpu import proclog
    out, p, _ = _run_chain('auto', gulp_batch=4, ngulp=8)
    seg = p._segments[0]
    contents = proclog.load_by_pid(os.getpid())
    found = 0
    for m in seg._members:
        perf = contents.get(m, {}).get('perf')
        if not perf:
            continue
        found += 1
        assert perf.get('in_segment') == seg.name
        assert float(perf.get('gulps_per_dispatch', 0)) >= 1.0
    assert found == len(seg._members)


def test_root_retunes_reach_the_segment():
    """The compiler carries only the chain head's OWN pins, never
    scope-resolved values — a resolved sync_depth pinned onto the
    segment would silently cut the auto-tuner's root retunes (and
    profile warm starts) off from the fused hot path."""
    from bifrost_tpu.macro import resolve_gulp_batch
    from bifrost_tpu.pipeline import resolve_sync_depth
    _, p, _ = _run_chain('auto')            # Pipeline(sync_depth=4)
    seg = p._segments[0]
    assert seg.__dict__.get('_sync_depth') is None
    assert resolve_sync_depth(seg) == 4
    p._sync_depth = 9                       # the sync_depth knob
    assert resolve_sync_depth(seg) == 9
    p._gulp_batch = 8                       # the macro-K knob
    assert resolve_gulp_batch(seg) == 8
