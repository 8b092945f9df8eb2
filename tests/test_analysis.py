"""Static pipeline verifier + dynamic ring-protocol checker
(bifrost_tpu.analysis; docs/analysis.md).

Two halves, mirroring the module:

- seeded-misconfiguration fixtures asserting the verifier flags each
  class with its EXACT stable diagnostic code (the codes are API);
- fault-injected protocol corruptions in BOTH ring cores asserting the
  ringcheck shadow state machine trips every invariant class with a
  span-history trace.
"""

import threading
import time

import numpy as np
import pytest

import bifrost_tpu as bf
import bifrost_tpu.native as native_mod
from bifrost_tpu.analysis import ringcheck
from bifrost_tpu.analysis.ringcheck import RingProtocolError
from bifrost_tpu.analysis.verify import (CODES, PipelineValidationError)
from bifrost_tpu.ring import Ring, RingPoisonedError
from bifrost_tpu.stages import FftStage, DetectStage, ReduceStage
from bifrost_tpu.testing import faults
from tests.topologies import TOPOLOGIES
from tests.util import NumpySourceBlock, GatherSink, simple_header

pytestmark = pytest.mark.faults


# ---------------------------------------------------------------------------
# static verifier: seeded misconfigurations -> exact codes
# ---------------------------------------------------------------------------

NT, NP, NF = 64, 2, 256


def _raw(n=1):
    raw = np.zeros((NT, NP, NF), dtype=np.dtype([('re', 'i1'),
                                                 ('im', 'i1')]))
    return [raw.copy() for _ in range(n)]


def _hdr():
    return simple_header([-1, NP, NF], 'ci8',
                         labels=['time', 'pol', 'fine_time'])


def _codes(diags):
    return sorted(d.code for d in diags)


def test_clean_chain_validates_clean():
    """The fused spectroscopy chain (the benchmark's hot path) must
    verify with zero errors/warnings.
    Info-level findings are allowed (BF-I190 inventories the unfused
    device-ring boundaries on every chain, by design); anything
    visible in warn mode is not."""
    with bf.Pipeline(sync_depth=4) as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        fb = bf.blocks.fused(b, [FftStage('fine_time',
                                          axis_labels='freq'),
                                 DetectStage('stokes', axis='pol'),
                                 ReduceStage('freq', 4)])
        GatherSink(bf.blocks.copy(fb, space='system'))
        diags = p.validate()
    visible = [d for d in diags if d.severity != 'info']
    assert visible == [], _codes(visible)
    # the info inventory names each non-fused device-ring boundary
    assert {d.code for d in diags} <= {'BF-I190'}, _codes(diags)


@pytest.mark.parametrize('name', sorted(TOPOLOGIES))
def test_shipped_topologies_validate_clean(name):
    """Every topology the repo ships (tests/topologies.py) builds and
    verifies with nothing above info level: no error, and none of the
    warnings its builder's docstring rules out.  The verifier's rules
    and the blocks' declarations must not drift apart on any of
    them."""
    built = TOPOLOGIES[name]()
    for p in built if isinstance(built, list) else [built]:
        visible = [d for d in p.validate() if d.severity != 'info']
        assert visible == [], '%s: %s' % (p.name, visible)


def test_undersized_macro_ring_is_deadlock_error():
    """Seeded misconfiguration 1: the consumer reads a 4-gulp span
    batched by macro K=8 (32*NT frames held by its guarantee) but the
    largest declared capacity — its own buffer_nframe=16*NT, which
    also exceeds the writer's 2-macro-span depth — cannot hold that
    pin plus the writer's resident span: as declared, the writer
    deadlocks (only the runtime's silent auto-grow override rescues
    it) -> BF-E101."""
    with bf.Pipeline(gulp_batch=8) as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        fb = bf.blocks.fused(b, [FftStage('fine_time',
                                          axis_labels='freq')],
                             gulp_nframe=4 * NT,
                             buffer_nframe=16 * NT)
        GatherSink(bf.blocks.copy(fb, space='system'))
        diags = p.validate()
    hits = [d for d in diags if d.code == 'BF-E101']
    assert len(hits) == 1
    assert 'macro K=8' in hits[0].message
    assert hits[0].ring is not None


def test_dtype_contract_break_is_error():
    """Seeded misconfiguration 2: a stage whose header contract the
    upstream stream cannot satisfy (reducing an axis label that does
    not exist yet) -> BF-E121 at submit time, not gulp 0."""
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        fb = bf.blocks.fused(b, [ReduceStage('freq', 4)])  # no 'freq'
        GatherSink(bf.blocks.copy(fb, space='system'))
        diags = p.validate()
    assert [d.code for d in diags if d.is_error] == ['BF-E121']
    assert fb.name in [d.block for d in diags if d.is_error]


def test_donation_with_multi_reader_is_error():
    """Seeded misconfiguration 3: donate=True on a block whose input
    ring has a second reader -> exclusivity disprovable, BF-E130."""
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        fb = bf.blocks.fused(b, [FftStage('fine_time',
                                          axis_labels='freq')],
                             donate=True)
        tap = bf.blocks.fused(b, [DetectStage('stokes', axis='pol')])
        GatherSink(bf.blocks.copy(fb, space='system'))
        GatherSink(bf.blocks.copy(tap, space='system'))
        diags = p.validate()
    hits = [d for d in diags if d.code == 'BF-E130']
    assert len(hits) == 1 and hits[0].block == fb.name


def test_forced_reshard_mesh_chain_warns():
    """Seeded misconfiguration 4: an H2D copy OUTSIDE the mesh scope
    feeding a mesh fused block -> every gulp pays a relayout,
    BF-W140 (mesh.reshards > 0 predicted statically)."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ('sp',))
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')           # no mesh
        fb = bf.blocks.fused(b, [DetectStage('stokes', axis='pol')],
                             mesh=mesh)
        GatherSink(bf.blocks.copy(fb, space='system', mesh=mesh))
        diags = p.validate()
    hits = [d for d in diags if d.code == 'BF-W140']
    assert hits and hits[0].block == fb.name
    assert 'reshard' in hits[0].message


def test_covered_declaration_is_not_flagged():
    """An undersized buffer_nframe on one reader is harmless when
    another reader's request covers the bound (Ring.resize negotiates
    the MAX over all requests) — no BF-E101/W102 false positive on a
    pipeline that runs fine."""
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        fb1 = bf.blocks.fused(b, [FftStage('fine_time',
                                           axis_labels='freq')],
                              buffer_nframe=NT)        # undersized...
        fb2 = bf.blocks.fused(b, [DetectStage('scalar')],
                              buffer_nframe=64 * NT)   # ...but covered
        GatherSink(bf.blocks.copy(fb1, space='system'))
        GatherSink(bf.blocks.copy(fb2, space='system'))
        diags = p.validate()
    codes = _codes(diags)
    assert 'BF-E101' not in codes and 'BF-W102' not in codes, codes


def test_bridge_window_within_sender_resize_is_clean():
    """BF-W110 must account for RingSender's own runtime resize to
    window+2 spans — a plain window=4 bridge sink is NOT capped."""
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        bf.blocks.bridge.bridge_sink(src, '127.0.0.1', 59999,
                                     window=4)
        diags = p.validate()
    assert 'BF-W110' not in _codes(diags), _codes(diags)


def test_bridge_window_zero_is_error():
    """Seeded misconfiguration 5: BridgeSink(window=0) — the runtime
    clamp silently papers it over; the verifier flags the request."""
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        bf.blocks.bridge.bridge_sink(src, '127.0.0.1', 59999,
                                     window=0)
        diags = p.validate()
    assert [d.code for d in diags if d.is_error] == ['BF-E150']


def test_bridge_v1_wire_warnings():
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        bf.blocks.bridge.bridge_sink(src, '127.0.0.1', 59999,
                                     protocol=1, crc=True, window=4)
        diags = p.validate()
    codes = _codes(diags)
    assert 'BF-W151' in codes and 'BF-W152' in codes


def test_macro_ineligibility_reported():
    """A block that requests batching but is statically ineligible
    warns (BF-W160 with the reason); host blocks under a batching
    scope stay info-level (BF-I161)."""
    with bf.Pipeline(gulp_batch=8) as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        fb = bf.blocks.fused(b, [DetectStage('stokes', axis='pol')],
                             guarantee=False)   # static ineligibility
        GatherSink(bf.blocks.copy(fb, space='system'))
        diags = p.validate()
    w = [d for d in diags if d.code == 'BF-W160']
    assert len(w) == 1 and w[0].block == fb.name
    assert 'unguaranteed' in w[0].message
    assert any(d.code == 'BF-I161' for d in diags)   # the host sink


def test_float_path_on_quantized_ring_warns():
    """Seeded misconfiguration: a BeamformBlock on a ci8 ring whose
    'f32' accuracy class excludes the int8 candidates -> BF-W170; the
    'int8' class (or a forced int candidate) is clean; a forced FLOAT
    candidate on the same ring warns again."""
    rng = np.random.RandomState(0)
    # weights (B, S) for a ['time', 'freq', 'station', 'pol'] stream
    S, P, B = 8, 2, 4
    w = (rng.randn(B, S) + 1j * rng.randn(B, S)).astype(np.complex64)
    hdr = simple_header([-1, NF, S, P], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'])
    raw = np.zeros((NT, NF, S, P), dtype=np.dtype([('re', 'i1'),
                                                   ('im', 'i1')]))

    def build(**kw):
        with bf.Pipeline() as p:
            src = NumpySourceBlock([raw.copy()], hdr, gulp_nframe=NT)
            b = bf.blocks.copy(src, space='tpu')
            b = bf.blocks.beamform(b, w, **kw)
            GatherSink(bf.blocks.copy(b, space='system'))
            return p.validate()

    def visible(diags):
        # BF-I190 inventories unfused boundaries on every chain; this
        # test is about the warning
        return [d for d in diags if d.severity != 'info']

    diags = build(accuracy='f32')
    assert 'BF-W170' in _codes(diags), _codes(diags)
    assert visible(build(accuracy='int8')) == []
    assert visible(build(accuracy='f32', impl='int8_wide')) == []
    forced = build(accuracy='int8', impl='planar_bf16')
    assert 'BF-W170' in _codes(forced), _codes(forced)


def test_all_codes_catalogued():
    """Every diagnostic code the tests assert is in the stable
    catalog, and severities derive from the code letter."""
    for code, title in CODES.items():
        assert code.startswith('BF-') and code[3] in 'EWI'
        assert title


def test_validate_strict_refuses_to_run(monkeypatch):
    monkeypatch.setenv('BF_VALIDATE', 'strict')
    with bf.Pipeline(gulp_batch=8) as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        bf.blocks.fused(b, [FftStage('fine_time',
                                     axis_labels='freq')],
                        gulp_nframe=4 * NT, buffer_nframe=16 * NT)
        with pytest.raises(PipelineValidationError) as ei:
            p.run()
    assert 'BF-E101' in str(ei.value)


def test_validate_warn_still_runs(monkeypatch, capsys):
    """warn mode reports the same finding but the pipeline runs (the
    runtime's auto-grow sizing overrides the bad declaration)."""
    monkeypatch.setenv('BF_VALIDATE', 'warn')
    with bf.Pipeline(gulp_batch=8) as p:
        src = NumpySourceBlock(_raw(2), _hdr(), gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        fb = bf.blocks.fused(b, [FftStage('fine_time',
                                          axis_labels='freq')],
                             gulp_nframe=4 * NT,
                             buffer_nframe=16 * NT)
        sink = GatherSink(bf.blocks.copy(fb, space='system'))
        p.run()
    assert sink.result() is not None
    assert 'BF-E101' in capsys.readouterr().err


def test_lint_intercept_builds_without_running(monkeypatch, tmp_path):
    out = tmp_path / 'lint.jsonl'
    monkeypatch.setenv('BF_LINT', '1')
    monkeypatch.setenv('BF_LINT_OUT', str(out))
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_raw(), _hdr(), gulp_nframe=NT)
        sink = GatherSink(bf.blocks.copy(src))
        p.run()                      # validates and returns
    assert sink.result() is None     # nothing actually ran
    import json
    recs = [json.loads(line) for line in
            out.read_text().splitlines()]
    assert recs and recs[0]['pipeline'] == p.name
    assert recs[0]['nblocks'] == 3


# ---------------------------------------------------------------------------
# dynamic ring-protocol checker: corrupt the protocol, both cores
# ---------------------------------------------------------------------------

@pytest.fixture(params=['native', 'python'])
def ring_core(request, monkeypatch):
    """Run each checker test against BOTH ring cores (the same trick
    tests/test_ring_python_core.py uses to force the Python core)."""
    if request.param == 'python':
        monkeypatch.setattr(native_mod, '_lib', None)
        monkeypatch.setattr(native_mod, '_tried', True)
    elif not native_mod.available():
        pytest.skip('native core unavailable')
    return request.param


@pytest.fixture
def checker():
    ringcheck.set_enabled(True)
    ringcheck.reset()
    yield ringcheck
    faults.clear()
    ringcheck.set_enabled(False)
    ringcheck.reset()


def _open_seq(ring, gulp=8, buf=32):
    hdr = simple_header([-1, 4], 'f32')
    wr = ring.begin_writing()
    seq = wr.begin_sequence(hdr, gulp_nframe=gulp, buf_nframe=buf)
    return wr, seq


def test_double_commit_detected(ring_core, checker):
    ring = Ring(space='system', name='rc_dc_%s' % ring_core)
    wr, seq = _open_seq(ring)
    with faults.injected('ring.corrupt.double_commit',
                         match=ring.name):
        span = seq.reserve(8)
        span.data.as_numpy()[...] = 1.0
        span.commit(8)
        with pytest.raises(RingProtocolError) as ei:
            span.close()
    assert ei.value.invariant == 'double_commit'
    assert 'span history' in str(ei.value)
    assert ringcheck.violations()


def test_double_release_detected(ring_core, checker):
    ring = Ring(space='system', name='rc_dr_%s' % ring_core)
    wr, seq = _open_seq(ring)
    with seq.reserve(8) as span:
        span.data.as_numpy()[...] = 2.0
        span.commit(8)
    rseq = ring.open_earliest_sequence(guarantee=True)
    rspan = rseq.acquire(0, 8)
    with faults.injected('ring.corrupt.double_release',
                         match=ring.name):
        with pytest.raises(RingProtocolError) as ei:
            rspan.release()
    assert ei.value.invariant == 'double_release'
    assert 'release' in str(ei.value)


def test_acquire_uncommitted_detected(ring_core, checker):
    ring = Ring(space='system', name='rc_au_%s' % ring_core)
    wr, seq = _open_seq(ring)
    with seq.reserve(8) as span:
        span.data.as_numpy()[...] = 3.0
        span.commit(8)
    rseq = ring.open_earliest_sequence(guarantee=True)
    with faults.injected('ring.corrupt.acquire_uncommitted',
                         match=ring.name):
        with pytest.raises(RingProtocolError) as ei:
            rseq.acquire(0, 8)
    assert ei.value.invariant == 'acquire_uncommitted'
    assert 'committed head' in str(ei.value)


def test_commit_order_violation_detected(ring_core, checker):
    """A partial commit while a later reservation is outstanding
    breaks the in-order barrier's truncation rule — the checker
    catches it BEFORE the core does (no corruption seam needed; the
    illegal call sequence is enough)."""
    ring = Ring(space='system', name='rc_co_%s' % ring_core)
    wr, seq = _open_seq(ring, gulp=8, buf=64)
    s1 = seq.reserve(8)
    s2 = seq.reserve(8)
    s1.data.as_numpy()[...] = 1.0
    s1.commit(4)                      # partial, with s2 outstanding
    with pytest.raises(RingProtocolError) as ei:
        s1.close()
    assert ei.value.invariant == 'commit_order'
    # a zero-commit of the NEWEST span stays legal (clean unwind path)
    s2.commit(0)
    s2.close()


def test_guarantee_jump_detected(ring_core, checker):
    """Corrupt the CORE guarantee forward past a held span (the
    pre-PR-5 watermark bug): the checker flags the overwriting
    reserve the corrupted core then admits."""
    ring = Ring(space='system', name='rc_gj_%s' % ring_core)
    wr, seq = _open_seq(ring, gulp=8, buf=16)      # 2 spans capacity
    for val in (1.0, 2.0):
        with seq.reserve(8) as span:
            span.data.as_numpy()[...] = val
            span.commit(8)
    rseq = ring.open_earliest_sequence(guarantee=True)
    with faults.injected('ring.corrupt.guarantee_jump',
                         match=ring.name):
        rspan = rseq.acquire(0, 8)    # held span; guarantee jumps
    with pytest.raises(RingProtocolError) as ei:
        with seq.reserve(8) as span:  # overwrites the held span
            span.commit(0)
    assert ei.value.invariant == 'guarantee_pin'
    assert 'overwriting' in str(ei.value)


def test_poison_wakes_blocked_spans_clean(ring_core, checker):
    """The healthy path: poison wakes a blocked reader within the
    grace window — no violation recorded."""
    ring = Ring(space='system', name='rc_pw_%s' % ring_core)
    wr, seq = _open_seq(ring)
    woke = []

    def reader():
        try:
            rseq = ring.open_earliest_sequence(guarantee=True)
            rseq.acquire(0, 8)        # blocks: nothing committed
        except RingPoisonedError:
            woke.append('poisoned')
        except Exception as exc:      # pragma: no cover
            woke.append(repr(exc))

    t = threading.Thread(target=reader)
    t.start()
    time.sleep(0.2)
    ring.poison(RuntimeError('test poison'))
    t.join(5)
    assert not t.is_alive() and woke == ['poisoned']
    time.sleep(0.4)                   # let the wake timer run
    assert not ringcheck.violations()


def test_poison_nowake_detected(ring_core, checker, monkeypatch):
    """Corrupt poison to NOT wake blocked spans: the checker's wake
    timer must flag the still-blocked acquire with a span-history
    trace."""
    monkeypatch.setenv('BF_RINGCHECK_WAKE_SECS', '0.2')
    ring = Ring(space='system', name='rc_pn_%s' % ring_core)
    wr, seq = _open_seq(ring)
    woke = []

    def reader():
        try:
            rseq = ring.open_earliest_sequence(guarantee=True)
            rseq.acquire(0, 8)        # blocks: nothing committed
        except RingPoisonedError:
            woke.append('poisoned')
        except Exception as exc:      # pragma: no cover
            woke.append(repr(exc))

    t = threading.Thread(target=reader)
    t.start()
    time.sleep(0.2)
    with faults.injected('ring.corrupt.poison_nowake',
                         match=ring.name):
        ring.poison(RuntimeError('test poison'))
    deadline = time.monotonic() + 5
    while not ringcheck.violations() and time.monotonic() < deadline:
        time.sleep(0.05)
    viols = ringcheck.violations()
    assert viols and viols[-1].invariant == 'poison_wake'
    assert 'span history' in str(viols[-1])
    # un-hang the reader and close out
    ring._wake_all()
    t.join(5)
    assert not t.is_alive() and woke == ['poisoned']


def test_ringcheck_off_is_inert(ring_core):
    """BF_RINGCHECK=0: no shadow state is attached to rings at all —
    the disarmed seams are bit-identical in behavior to pre-checker
    code."""
    ringcheck.set_enabled(False)
    ring = Ring(space='system', name='rc_off_%s' % ring_core)
    wr, seq = _open_seq(ring)
    with seq.reserve(8) as span:
        span.data.as_numpy()[...] = 1.0
        span.commit(8)
    rseq = ring.open_earliest_sequence(guarantee=True)
    with rseq.acquire(0, 8):
        pass
    assert '_rc_shadow' not in ring.__dict__


def test_ringcheck_inside_pipeline(checker):
    """End to end: a real pipeline runs clean under BF_RINGCHECK=1
    (no false positives from the shadow model on the shipped
    protocol)."""
    with bf.Pipeline() as p:
        src = NumpySourceBlock(_raw(2), _hdr(), gulp_nframe=NT)
        sink = GatherSink(bf.blocks.copy(src))
        p.run()
    assert sink.result() is not None
    assert not ringcheck.violations()


def test_resize_under_span_detected(ring_core, checker):
    """The resize_quiescence invariant (the auto-tuner's retune
    protocol, docs/autotune.md): a core reporting a storage re-layout
    while spans are open is caught by the shadow state machine — in
    BOTH cores, via the ``ring.corrupt.resize_under_span`` seam that
    simulates applying the deferred resize under a live span."""
    ring = Ring(space='system', name='rc_rz_%s' % ring_core)
    wr, seq = _open_seq(ring)
    span = seq.reserve(8)
    with faults.injected('ring.corrupt.resize_under_span',
                         match=ring.name):
        with pytest.raises(RingProtocolError) as ei:
            ring.request_resize(1, ring.total_span * 2)
    assert ei.value.invariant == 'resize_quiescence'
    assert 'dangle' in str(ei.value)
    assert ringcheck.violations()
    span.data.as_numpy()[...] = 1.0
    span.commit(8)
    span.close()


def test_deferred_resize_clean_under_checker(ring_core, checker):
    """The LEGITIMATE deferred-resize protocol — request under an open
    span, apply at quiescence — must run clean under BF_RINGCHECK=1 in
    both cores (no false positives from the new invariant)."""
    ring = Ring(space='system', name='rc_rzok_%s' % ring_core)
    wr, seq = _open_seq(ring)
    before = ring.total_span
    span = seq.reserve(8)
    assert not ring.request_resize(1, before * 2)
    span.data.as_numpy()[...] = 1.0
    span.commit(8)
    span.close()
    assert ring.total_span >= before * 2
    assert not ringcheck.violations()
