"""Observability-layer tests (docs/observability.md): gulp-span
tracing with Chrome trace export, log2 latency histograms, the unified
snapshot / Prometheus export surface, and the watchdog flight
recorder — all on the CPU backend, driven where useful by the
deterministic fault harness (bifrost_tpu.testing.faults)."""

import contextlib
import io
import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu import proclog, trace
from bifrost_tpu.supervision import PipelineStallError
from bifrost_tpu.telemetry import (counters, exporter, histograms,
                                   spans)
from bifrost_tpu.testing import faults
from tests.util import NumpySourceBlock, GatherSink, simple_header

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, 'tools')


@pytest.fixture(autouse=True)
def clean_state():
    faults.clear()
    counters.reset()
    histograms.reset()
    spans.reset()
    yield
    faults.clear()
    counters.reset()
    histograms.reset()
    spans.reset()


def _hdr():
    return simple_header([-1, 3], 'f32')


def _gulps(n=5):
    return [np.full((4, 3), float(k), dtype=np.float32)
            for k in range(n)]


class Ident(bf.TransformBlock):
    """Pass-through host transform with a distinctive name."""

    def on_sequence(self, iseq):
        return dict(iseq.header)

    def on_data(self, ispan, ospan):
        ospan.data.as_numpy()[...] = ispan.data.as_numpy()


def _run_simple_pipeline(ngulp=5, device_hop=False, **pipe_kwargs):
    with bf.Pipeline(**pipe_kwargs) as p:
        src = NumpySourceBlock(_gulps(ngulp), _hdr(), gulp_nframe=4)
        if device_hop:
            up = bf.blocks.copy(src, space='tpu')
            down = bf.blocks.copy(up, space='system')
            sink = GatherSink(down)
        else:
            blk = Ident(src)
            sink = GatherSink(blk)
        p.run()
    return p, sink


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_basic_stats():
    h = histograms.Histogram('t.basic')
    for v in (0.001, 0.002, 0.004, 0.008, 0.016):
        h.record(v)
    snap = h.snapshot()
    assert snap['count'] == 5
    assert snap['sum'] == pytest.approx(0.031)
    assert snap['min'] == pytest.approx(0.001)
    assert snap['max'] == pytest.approx(0.016)
    # five distinct powers of two -> five distinct buckets
    assert len(snap['buckets']) == 5
    assert sum(snap['buckets'].values()) == 5


def test_histogram_percentiles_monotonic():
    rng = np.random.RandomState(7)
    h = histograms.get_or_create('t.mono')
    for v in np.exp(rng.randn(500) * 2.0 - 6.0):
        h.record(float(v))
    last = 0.0
    for p in range(1, 101):
        cur = h.percentile(p)
        assert cur >= last, 'p%d < p%d' % (p, p - 1)
        last = cur
    snap = h.snapshot()
    assert snap['p50'] <= snap['p90'] <= snap['p99']
    # estimates stay inside the observed range
    assert snap['min'] <= snap['p50'] <= snap['max']
    assert snap['min'] <= snap['p99'] <= snap['max']


def test_histogram_edge_values():
    h = histograms.Histogram('t.edge')
    assert h.percentile(99) == 0.0        # empty
    h.record(0.0)
    h.record(-1.0)                        # clamps to 0
    h.record(float('nan'))                # clamps to 0
    h.record(1e30)                        # clamps to top bucket
    snap = h.snapshot()
    assert snap['count'] == 4
    assert snap['min'] == 0.0 and snap['max'] == 1e30


def test_histogram_registry_observe_and_reset():
    histograms.observe('t.reg', 0.5)
    histograms.observe('t.reg', 0.5)
    assert histograms.get('t.reg').count == 2
    assert 't.reg' in histograms.snapshot()
    histograms.reset()
    assert histograms.get('t.reg') is None


# ---------------------------------------------------------------------------
# gulp-span tracing / Chrome trace export
# ---------------------------------------------------------------------------

def test_trace_file_has_complete_spans_per_gulp(monkeypatch, tmp_path):
    """The acceptance-criterion run: BF_TRACE_FILE set, a CPU pipeline
    with a device hop produces a valid Chrome trace with block-compute,
    ring-wait, and transfer spans, one complete compute span per
    gulp with (sequence, gulp) identity."""
    path = tmp_path / 'trace.json'
    monkeypatch.setenv('BF_TRACE_FILE', str(path))
    trace.reset()                      # satellite: re-read env
    ngulp = 5
    _run_simple_pipeline(ngulp=ngulp, device_hop=True)

    data = json.loads(path.read_text())
    evs = [e for e in data['traceEvents'] if e.get('ph') == 'X']
    assert evs, 'no complete events exported'
    for e in evs:
        assert 'ts' in e and 'dur' in e and e['dur'] >= 0

    # block-compute spans carry per-gulp identity
    copies = [e for e in evs
              if 'CopyBlock' in e['name'] and e['cat'] == 'compute']
    by_block = {}
    for e in copies:
        by_block.setdefault(e['name'], []).append(e)
    assert len(by_block) == 2          # both copy blocks traced
    for name, block_evs in by_block.items():
        idents = sorted((e['args']['seq'], e['args']['gulp'])
                        for e in block_evs)
        assert idents == [(0, g) for g in range(ngulp)], \
            '%s: %r' % (name, idents)

    # ring-wait spans from the flow-control seam
    ring_evs = [e for e in evs if e['cat'] == 'ring']
    assert any(e['name'].endswith('.reserve') for e in ring_evs)
    assert any(e['name'].endswith('.acquire') for e in ring_evs)
    # transfer spans from the device hop
    xfer_names = {e['name'] for e in evs if e['cat'] == 'xfer'}
    assert 'h2d' in xfer_names and 'd2h' in xfer_names
    # thread tracks are labeled with block names
    meta = [e for e in data['traceEvents']
            if e.get('ph') == 'M' and e.get('name') == 'thread_name']
    tnames = {e['args']['name'] for e in meta}
    assert any('CopyBlock' in t for t in tnames)


def test_spans_nest_and_close_under_faults(monkeypatch, tmp_path):
    monkeypatch.setenv('BF_TRACE_FILE', str(tmp_path / 't.json'))
    spans.reconfigure()
    with faults.injected('xfer.h2d', count=1):
        with pytest.raises(faults.FaultInjected):
            with spans.timed('outer', 'test', k=1):
                with spans.timed('inner', 'test'):
                    faults.fire('xfer.h2d')
    evs = [ev for _t, ev in spans.events() if ev[1] == 'test']
    assert [ev[0] for ev in evs] == ['inner', 'outer']  # close order
    (iname, _c, its, idur, _a), (oname, _c2, ots, odur, oargs) = \
        (ev[:5] for ev in evs)
    # inner nests inside outer despite the exception exit
    assert ots <= its
    assert its + idur <= ots + odur + 1.0   # 1us slack
    assert oargs == {'k': 1}


def test_trace_exported_even_when_pipeline_aborts(monkeypatch,
                                                  tmp_path):
    path = tmp_path / 'abort.json'
    monkeypatch.setenv('BF_TRACE_FILE', str(path))
    with faults.injected('block.on_data', match='Ident', after=2,
                         count=1):
        with pytest.raises(Exception):
            _run_simple_pipeline(ngulp=5)
    data = json.loads(path.read_text())
    idents = [e for e in data['traceEvents']
              if e.get('ph') == 'X' and 'Ident' in e['name']
              and e.get('cat') == 'compute']
    # gulps 0 and 1 completed; the faulted gulp raised BEFORE its
    # compute span opened (the fault seam precedes dispatch), so
    # exactly the completed gulps are traced
    assert len(idents) == 2


def test_span_buffer_env_bounds_events(monkeypatch, tmp_path):
    monkeypatch.setenv('BF_TRACE_FILE', str(tmp_path / 'b.json'))
    monkeypatch.setenv('BF_SPAN_BUFFER', '16')
    spans.reconfigure()
    for i in range(100):
        spans.record('ev%d' % i, 'test', float(i), 1.0)
    mine = [ev for _t, ev in spans.events() if ev[1] == 'test']
    assert len(mine) == 16
    assert mine[0][0] == 'ev84'        # ring kept the newest
    monkeypatch.delenv('BF_SPAN_BUFFER')
    spans.reconfigure()


# ---------------------------------------------------------------------------
# unified snapshot + exporters
# ---------------------------------------------------------------------------

def test_snapshot_merges_counters_histograms_rings():
    p, sink = _run_simple_pipeline(ngulp=5)
    snap = bf.telemetry.snapshot()
    assert set(snap) == {'counters', 'gauges', 'histograms', 'rings',
                         'devices', 'mesh', 'tenants', 'scheduler',
                         'threads', 'identity'}
    # every ring's capacity, and their sum a space (depth by bytes)
    caps = {k: v for k, v in snap['gauges'].items()
            if k.endswith('.capacity_bytes')}
    assert caps and all(v > 0 for v in caps.values())
    assert snap['gauges']['ring.held_bytes.system'] > 0
    assert snap['identity']['pid'] == os.getpid()
    assert snap['counters'].get('pipeline.gulps', 0) > 0
    assert any(k.startswith('block.') and k.endswith('.gulp_s')
               for k in snap['histograms'])
    assert any(k.startswith('ring.') and k.endswith('.reserve_s')
               for k in snap['histograms'])
    assert snap['rings'], 'live ring occupancy missing'
    for occ in snap['rings'].values():
        if 'fill' in occ:
            assert 0.0 <= occ['fill'] <= 1.0
    # per-ring throughput counters feed the gulps/s rate
    assert any(k.startswith('ring.') and k.endswith('.gulps')
               for k in snap['counters'])


_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+0-9.eE]+$|'
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \+Inf$')


def test_prometheus_file_written_and_parses(monkeypatch, tmp_path):
    prom = tmp_path / 'metrics.prom'
    monkeypatch.setenv('BF_METRICS_FILE', str(prom))
    _run_simple_pipeline(ngulp=5)
    text = prom.read_text()
    assert text.endswith('\n')
    for line in text.splitlines():
        if not line or line.startswith('#'):
            continue
        assert _PROM_LINE.match(line), 'unparseable line: %r' % line
    # histogram buckets are cumulative and capped by _count
    counts = {}
    buckets = {}
    for line in text.splitlines():
        m = re.match(r'bifrost_tpu_hist_count\{name="([^"]+)"\} (\d+)',
                     line)
        if m:
            counts[m.group(1)] = int(m.group(2))
        m = re.match(r'bifrost_tpu_hist_bucket\{name="([^"]+)",'
                     r'le="([^"]+)"\} (\d+)', line)
        if m:
            buckets.setdefault(m.group(1), []).append(
                (m.group(2), int(m.group(3))))
    assert counts and buckets
    for name, bs in buckets.items():
        cum = [n for _le, n in bs]
        assert cum == sorted(cum), '%s buckets not cumulative' % name
        assert bs[-1][0] == '+Inf'
        assert bs[-1][1] == counts[name]
    assert 'bifrost_tpu_counter_total{name="pipeline.gulps"}' in text
    assert 'bifrost_tpu_ring_fill_ratio' in text


def test_proclog_metrics_and_rings_flow_published():
    p, _sink = _run_simple_pipeline(ngulp=5)
    contents = proclog.load_by_pid(os.getpid())
    metrics = contents.get('telemetry', {}).get('metrics', {})
    assert any(k.startswith('c.pipeline.gulps') for k in metrics)
    assert any(k.startswith('h.block.') and k.endswith('.p99')
               for k in metrics)
    flow = {}
    for block, logs in contents.items():
        if block.replace(os.sep, '/').startswith('rings_flow'):
            flow.update(logs)
    assert flow, 'no rings_flow/<name> proclogs published'
    entry = next(iter(flow.values()))
    assert 'occupancy_pct' in entry
    assert 'gulps' in entry and 'gulps_per_s' in entry


# ---------------------------------------------------------------------------
# flight recorder + watchdog integration
# ---------------------------------------------------------------------------

def test_watchdog_dump_includes_flight_recorder(monkeypatch):
    """A forced stall dumps the span timeline alongside the thread
    stacks (the PR's acceptance criterion)."""
    monkeypatch.setenv('BF_WATCHDOG_ESCALATE', '1')
    stderr = io.StringIO()
    with faults.injected('block.on_data', match='Ident', count=1,
                         after=1, delay=10, exc=None):
        with bf.Pipeline(watchdog_secs=0.5) as p:
            p.shutdown_timeout = 1.0
            src = NumpySourceBlock(_gulps(50), _hdr(), gulp_nframe=4)
            blk = Ident(src)
            GatherSink(blk)
            box = []

            def target():
                try:
                    with contextlib.redirect_stderr(stderr):
                        p.run()
                    box.append(None)
                except BaseException as exc:
                    box.append(exc)

            t = threading.Thread(target=target, daemon=True)
            t.start()
            t.join(20)
            assert not t.is_alive()
    assert isinstance(box[0], PipelineStallError)
    dump = stderr.getvalue()
    assert 'Thread' in dump                  # stacks, as before
    assert 'flight recorder' in dump         # plus the timeline
    # the recorder shows spans leading up to the stall (gulp 0 made it
    # through before the delay fault wedged gulp 1)
    assert '.on_data' in dump or '.reserve' in dump


def test_flight_record_formats_empty_state():
    spans.reset()
    text = spans.flight_record()
    assert 'no spans recorded' in text


# ---------------------------------------------------------------------------
# satellites: trace.reset, CLI status, tool columns/labels
# ---------------------------------------------------------------------------

def test_trace_reset_rereads_span_config(monkeypatch, tmp_path):
    path = str(tmp_path / 'via_reset.json')
    monkeypatch.setenv('BF_TRACE_FILE', path)
    trace.reset()
    assert spans.trace_file() == path
    monkeypatch.delenv('BF_TRACE_FILE')
    trace.reset()
    assert spans.trace_file() is None


def _tool(name, *args):
    # explicit cwd: tests elsewhere in the suite may chdir away from
    # the repo root, and the subprocess must still import bifrost_tpu
    return subprocess.run([sys.executable,
                           os.path.join(TOOLS, name)] + list(args),
                          capture_output=True, text=True, cwd=ROOT,
                          env=dict(os.environ), timeout=120)


def test_telemetry_cli_status_prints_live_snapshot(tmp_path):
    env = dict(os.environ)
    env['BF_CACHE_DIR'] = str(tmp_path)
    res = subprocess.run(
        [sys.executable, '-m', 'bifrost_tpu.telemetry', '--status'],
        capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=120)
    assert res.returncode == 0, res.stderr
    assert 'live process counters' in res.stdout
    assert 'live process histograms' in res.stdout


def test_like_top_shows_percentile_columns():
    _run_simple_pipeline(ngulp=5)
    res = _tool('like_top.py', '--once')
    assert res.returncode == 0, res.stderr
    assert 'p50(ms)' in res.stdout and 'p99(ms)' in res.stdout
    assert 'Wait99' in res.stdout


def test_pipeline2dot_labels_ring_edges_with_flow():
    _run_simple_pipeline(ngulp=5)
    res = _tool('pipeline2dot.py', str(os.getpid()))
    assert res.returncode == 0, res.stderr
    assert '% full' in res.stdout
    assert 'gulps' in res.stdout


# ---------------------------------------------------------------------------
# distributed tracing: trace context (header_standard + pipeline)
# ---------------------------------------------------------------------------

def test_trace_context_helpers():
    from bifrost_tpu import header_standard as hs
    hdr = {'name': 'x'}
    ctx = hs.ensure_trace_context(hdr)
    assert ctx is hdr['_trace']
    assert len(ctx['id']) == 16 and ctx['origin_ns'] > 0
    # idempotent: a second ensure keeps the stamp
    assert hs.ensure_trace_context(hdr) is ctx
    # propagation copies into outputs lacking one
    o1, o2 = {'a': 1}, {'_trace': {'id': 'keepme', 'origin_ns': 1}}
    got = hs.propagate_trace_context(hdr, [o1, o2])
    assert got['id'] == ctx['id']
    assert o1['_trace']['id'] == ctx['id']
    assert o2['_trace']['id'] == 'keepme'     # never overwritten
    # headers without context propagate nothing
    assert hs.propagate_trace_context({'name': 'y'}, [{}]) is None


def test_trace_context_env_toggle(monkeypatch):
    from bifrost_tpu import header_standard as hs
    monkeypatch.setenv('BF_TRACE_CONTEXT', '0')
    hdr = {}
    assert hs.ensure_trace_context(hdr) is None
    assert '_trace' not in hdr
    monkeypatch.delenv('BF_TRACE_CONTEXT')
    assert hs.ensure_trace_context(hdr) is not None


def test_pipeline_stamps_and_propagates_trace_context():
    """Source stamps at first commit; transform and sink sequences
    inherit the same stream-unique id end to end."""
    p, sink = _run_simple_pipeline(ngulp=3)
    assert sink.headers, 'sink saw no sequences'
    ctx = sink.headers[0].get('_trace')
    assert ctx and len(ctx['id']) == 16
    assert ctx['origin_ns'] > 0 and ctx.get('host')


def test_pipeline_trace_context_disabled(monkeypatch):
    monkeypatch.setenv('BF_TRACE_CONTEXT', '0')
    p, sink = _run_simple_pipeline(ngulp=3)
    assert '_trace' not in sink.headers[0]


def test_compute_spans_carry_trace_id(monkeypatch, tmp_path):
    path = tmp_path / 'ctx_trace.json'
    monkeypatch.setenv('BF_TRACE_FILE', str(path))
    p, sink = _run_simple_pipeline(ngulp=3)
    data = json.loads(path.read_text())
    trace_id = sink.headers[0]['_trace']['id']
    computes = [e for e in data['traceEvents']
                if e.get('ph') == 'X' and e.get('cat') == 'compute']
    assert computes
    for e in computes:
        assert e['args']['trace'] == trace_id
        assert 'seq' in e['args'] and 'gulp' in e['args']
    # clock-correlation metadata rides along for trace_merge.py
    assert 'bf_clock' in data['otherData']


# ---------------------------------------------------------------------------
# capture-to-commit SLOs (telemetry.slo)
# ---------------------------------------------------------------------------

def test_slo_capture_age_extrapolates_tsamp():
    from bifrost_tpu.telemetry import slo
    import time as time_mod
    now = time_mod.time()
    hdr = {'_trace': {'id': 'x' * 16,
                      'origin_ns': int((now - 10.0) * 1e9)},
           'tsamp': 2.0}
    # frame 4 was captured at origin + 8s -> age ~2s, not ~10s
    age = slo.capture_age_s(hdr, frame_end=4, now=now)
    assert age == pytest.approx(2.0, abs=0.1)
    # no tsamp: age measured against the sequence origin
    del hdr['tsamp']
    assert slo.capture_age_s(hdr, frame_end=4, now=now) == \
        pytest.approx(10.0, abs=0.1)
    # no context: no observation
    assert slo.capture_age_s({'name': 'x'}) is None


def test_slo_histograms_and_exit_p99():
    ngulp = 5
    p, sink = _run_simple_pipeline(ngulp=ngulp)
    snap = bf.telemetry.snapshot()
    hists = snap['histograms']
    # per-block commit ages from ring._note_commit (both the source's
    # and the transform's output rings commit with a traced header)
    commit = [k for k in hists
              if k.startswith('slo.') and k.endswith('.commit_age_s')]
    assert commit, 'no commit-age histograms recorded'
    # THE pipeline-exit percentile (sink blocks)
    h = hists.get('slo.exit_age_s')
    assert h and h['count'] == ngulp
    assert h['p99'] >= h['p50'] > 0.0
    # no budget armed: no violations
    assert snap['counters'].get('slo.violations', 0) == 0


def test_slo_budget_violations(monkeypatch):
    from bifrost_tpu.telemetry import counters as tc
    monkeypatch.setenv('BF_SLO_MS', '0.000001')   # 1ns budget
    p, sink = _run_simple_pipeline(ngulp=4)
    snap = bf.telemetry.snapshot()
    assert snap['counters'].get('slo.violations', 0) > 0
    per_block = [k for k, v in snap['counters'].items()
                 if k.startswith('slo.') and k.endswith('.violations')
                 and k != 'slo.violations' and v > 0]
    assert per_block
    monkeypatch.setenv('BF_SLO_MS', '60000')      # 60s budget
    tc.reset()
    _run_simple_pipeline(ngulp=4)
    assert tc.get('slo.violations') == 0


def test_slo_age99_reaches_like_top():
    _run_simple_pipeline(ngulp=5)
    res = _tool('like_top.py', '--once')
    assert res.returncode == 0, res.stderr
    assert 'Age99' in res.stdout


# ---------------------------------------------------------------------------
# satellite: span-buffer overflow accounting
# ---------------------------------------------------------------------------

def test_dropped_spans_counted_and_snapshot(monkeypatch, tmp_path):
    monkeypatch.setenv('BF_TRACE_FILE', str(tmp_path / 'd.json'))
    monkeypatch.setenv('BF_SPAN_BUFFER', '16')
    spans.reconfigure()
    for i in range(40):
        spans.record('ov%d' % i, 'test', float(i), 1.0)
    assert spans.dropped_spans() == 40 - 16
    snap = bf.telemetry.snapshot()
    assert snap['counters']['trace.dropped_spans'] == 24
    # the flight recorder discloses the saturation
    dump = spans.flight_record()
    assert 'dropped' in dump and 'saturation' in dump
    monkeypatch.delenv('BF_SPAN_BUFFER')
    spans.reconfigure()


def test_dropped_spans_survive_buffer_prune(monkeypatch, tmp_path):
    """trace.dropped_spans is exported as a cumulative counter: a
    dead thread's drops must survive prune_dead_buffers (Pipeline.run
    calls it at every start) instead of vanishing."""
    import threading
    monkeypatch.setenv('BF_TRACE_FILE', str(tmp_path / 'p.json'))
    monkeypatch.setenv('BF_SPAN_BUFFER', '16')
    spans.reconfigure()

    def overflow():
        for i in range(30):
            spans.record('pr%d' % i, 'test', float(i), 1.0)

    t = threading.Thread(target=overflow)
    t.start()
    t.join()
    assert spans.dropped_spans() == 14
    spans.prune_dead_buffers()         # the thread is dead: pruned
    assert spans.dropped_spans() == 14  # ...but the count is kept
    monkeypatch.delenv('BF_SPAN_BUFFER')
    spans.reconfigure()


def test_no_drops_no_counter():
    spans.record('small', 'test', 0.0, 1.0)
    snap = bf.telemetry.snapshot()
    assert 'trace.dropped_spans' not in snap['counters']


# ---------------------------------------------------------------------------
# satellite: Prometheus textfile path (escaping / atomicity / round-trip)
# ---------------------------------------------------------------------------

def test_prometheus_label_escaping():
    counters.inc('weird"name\\with\nnasties')
    histograms.observe('hist"quoted\\slash', 0.5)
    text = exporter.prometheus_text()
    for line in text.splitlines():
        if not line or line.startswith('#'):
            continue
        assert _PROM_LINE.match(line), 'unparseable line: %r' % line
    # the escapes round-trip: \" for quotes, \\ for backslash, \n as
    # the two-character escape (never a raw newline inside a label)
    assert r'weird\"name\\with\nnasties' in text
    assert r'hist\"quoted\\slash' in text


def test_prometheus_atomic_publish(tmp_path):
    counters.inc('atomic.probe')
    path = str(tmp_path / 'm.prom')
    exporter.write_prometheus(path)
    # the tmp staging file was renamed away, never left behind
    leftovers = [p for p in os.listdir(str(tmp_path))
                 if p != 'm.prom']
    assert not leftovers, leftovers
    assert 'atomic.probe' in open(path).read()


def _parse_prometheus(text):
    """{(metric, frozenset(labels)): value} over every sample line."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith('#'):
            continue
        m = re.match(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
                     r'(?:\{(.*)\})? (.+)$', line)
        assert m, 'unparseable line: %r' % line
        name, labels, value = m.groups()
        label_set = frozenset((labels or '').split(','))
        out[(name, label_set)] = value
    return out


def test_prometheus_roundtrip_every_snapshot_key():
    """Every counter, histogram, ring, and device entry snapshot()
    emits appears in the textfile with the right value."""
    p, _sink = _run_simple_pipeline(ngulp=4, device_hop=True)
    snap = bf.telemetry.snapshot()
    parsed = _parse_prometheus(exporter.prometheus_text(snap))

    def esc(v):
        return str(v).replace('\\', r'\\').replace('"', r'\"') \
                     .replace('\n', r'\n')

    for name, val in snap['counters'].items():
        key = ('bifrost_tpu_counter_total',
               frozenset(['name="%s"' % esc(name)]))
        assert key in parsed, 'counter %r missing' % name
        assert int(parsed[key]) == val
    for name, h in snap['histograms'].items():
        key = ('bifrost_tpu_hist_count',
               frozenset(['name="%s"' % esc(name)]))
        assert key in parsed, 'histogram %r missing' % name
        assert int(parsed[key]) == h['count']
    for name, d in snap['rings'].items():
        if 'fill' in d:
            key = ('bifrost_tpu_ring_fill_ratio',
                   frozenset(['ring="%s"' % esc(name)]))
            assert key in parsed, 'ring %r missing' % name
    for idx, d in snap['devices'].items():
        if 'bytes_in_use' in d:
            key = ('bifrost_tpu_device_bytes',
                   frozenset(['device="%s"' % esc(idx),
                              'kind="in_use"']))
            assert key in parsed, 'device %r missing' % idx


def test_snapshot_device_and_mesh_sections():
    _run_simple_pipeline(ngulp=3, device_hop=True)
    snap = bf.telemetry.snapshot()
    # jax is imported (device hop ran), so device stats are sampled
    assert snap['devices'], 'no device memory stats'
    entry = next(iter(snap['devices'].values()))
    assert 'platform' in entry
    assert isinstance(snap['mesh'], dict)


def test_metrics_publisher_tracks_hbm_watermark():
    pub = exporter.MetricsPublisher(interval=60)
    snap = {'devices': {0: {'bytes_in_use': 100}}}
    pub._note_watermarks(snap)
    assert snap['devices'][0]['watermark_bytes'] == 100
    snap2 = {'devices': {0: {'bytes_in_use': 40}}}
    pub._note_watermarks(snap2)
    # the watermark is the peak across publishes, not the sample
    assert snap2['devices'][0]['watermark_bytes'] == 100


# ---------------------------------------------------------------------------
# tools: trace_merge / telemetry_diff / pipeline2dot bridge nodes
# ---------------------------------------------------------------------------

def _synthetic_trace(path, host, events, sessions):
    data = {'traceEvents': events, 'displayTimeUnit': 'ms',
            'otherData': {'bf_clock': {'host': host, 'pid': 1234,
                                       'sessions': sessions}}}
    with open(str(path), 'w') as f:
        json.dump(data, f)


def test_trace_merge_shifts_clocks(tmp_path):
    """The rx file's timeline lands on the tx file's clock via the
    handshake offset."""
    ev = {'ph': 'X', 'name': 'x.on_data', 'cat': 'compute',
          'pid': 1, 'tid': 1, 'dur': 5.0,
          'args': {'trace': 'abc', 'seq': 0, 'gulp': 0}}
    _synthetic_trace(tmp_path / 'tx.json', 'hostA',
                     [dict(ev, ts=1000.0)],
                     {'sess1': {'role': 'tx', 'offset_us': 500.0,
                                'rtt_us': 10.0}})
    _synthetic_trace(tmp_path / 'rx.json', 'hostB',
                     [dict(ev, ts=1600.0)],
                     {'sess1': {'role': 'rx'}})
    out = tmp_path / 'merged.json'
    res = _tool('trace_merge.py', '-o', str(out),
                str(tmp_path / 'tx.json'), str(tmp_path / 'rx.json'))
    assert res.returncode == 0, res.stderr
    data = json.loads(out.read_text())
    evs = [e for e in data['traceEvents'] if e.get('ph') == 'X']
    assert len(evs) == 2
    by_pid = {e['pid']: e for e in evs}
    assert by_pid[1]['ts'] == 1000.0          # reference unchanged
    # rx timestamp 1600 on a clock 500us ahead -> 1100 on tx clock
    assert by_pid[2]['ts'] == pytest.approx(1100.0)
    # process labels carry the host names
    names = [e['args']['name'] for e in data['traceEvents']
             if e.get('ph') == 'M' and e.get('name') == 'process_name']
    assert any('hostA' in n for n in names)
    assert any('hostB' in n for n in names)


def test_telemetry_diff_flags_regressions(tmp_path):
    base = {'value': 10.0, 'gulps_per_s': 100.0,
            'wait_p99_ms': 4.0, 'counters': {'slo.violations': 0}}
    cur = {'value': 10.0, 'gulps_per_s': 50.0,      # -50% throughput
           'wait_p99_ms': 9.0,                      # +125% latency
           'counters': {'slo.violations': 3}}       # new violations
    b, c = tmp_path / 'b.json', tmp_path / 'c.json'
    b.write_text(json.dumps(base))
    c.write_text(json.dumps(cur))
    res = _tool('telemetry_diff.py', str(b), str(c))
    assert res.returncode == 0, res.stderr        # advisory: exit 0
    assert 'REGRESSED' in res.stdout
    assert 'gulps_per_s' in res.stdout
    assert 'violations' in res.stdout
    # strict mode turns regressions into a failing exit
    res = _tool('telemetry_diff.py', str(b), str(c), '--strict')
    assert res.returncode == 3
    # identical inputs: clean
    res = _tool('telemetry_diff.py', str(b), str(b), '--strict')
    assert res.returncode == 0
    assert '0 regression(s)' in res.stdout
    # zero-base watched counter (violations 0 -> 3): the --out report
    # must stay valid RFC-8259 JSON — no Infinity token from the
    # undefined % change
    out = tmp_path / 'report.json'
    res = _tool('telemetry_diff.py', str(b), str(c),
                '--out', str(out))
    assert res.returncode == 0, res.stderr

    def _no_const(name):
        raise AssertionError('non-standard JSON token %r' % name)

    rep = json.loads(out.read_text(), parse_constant=_no_const)
    viol = [f for f in rep['findings'] if 'violations' in f['path']]
    assert viol and viol[0]['pct'] is None
    assert viol[0]['severity'] == 'regression'


# ---------------------------------------------------------------------------
# end-to-end: trace context + SLO + boundary rendering across a bridge
# ---------------------------------------------------------------------------

def test_bridge_carries_trace_context_and_slo(monkeypatch):
    """Two pipelines over a loopback bridge in ONE process: the sink
    pipeline's sequences carry the ORIGIN pipeline's trace id, its
    GatherSink reports capture-to-commit exit ages, and pipeline2dot
    renders the bridge endpoints as cross-host boundary nodes."""
    import threading
    from tests.util import NumpySourceBlock

    rng = np.random.RandomState(5)
    NT = 8
    gulps = [rng.randn(NT, 4).astype(np.float32) for _ in range(4)]
    hdr = simple_header([-1, 4], 'f32', name='e2ectx', gulp_nframe=NT)

    with bf.Pipeline() as prx:
        bsrc = bf.blocks.bridge_source('127.0.0.1', 0)
        sink = GatherSink(bsrc)
    with bf.Pipeline() as ptx:
        nsrc = NumpySourceBlock(gulps, hdr, gulp_nframe=NT)
        bf.blocks.bridge_sink(nsrc, '127.0.0.1', bsrc.port)

    rx_errors = []

    def run_rx():
        try:
            prx.run()
        except BaseException as exc:
            rx_errors.append(exc)

    t = threading.Thread(target=run_rx, daemon=True)
    t.start()
    ptx.run()
    t.join(30)
    assert not rx_errors, rx_errors

    # the stream identity crossed the wire
    rx_ctx = sink.headers[0].get('_trace')
    assert rx_ctx and len(rx_ctx['id']) == 16
    # the sink pipeline reports capture-to-commit ages (acceptance:
    # snapshot() has an exit p99 for the sink pipeline)
    snap = bf.telemetry.snapshot()
    h = snap['histograms'].get('slo.exit_age_s')
    assert h and h['count'] == len(gulps) and h['p99'] > 0
    # the receiver's commits aged too (BridgeSource's output ring)
    assert any('BridgeSource' in k and k.endswith('.commit_age_s')
               for k in snap['histograms'])

    # pipeline2dot renders the endpoints as boundary nodes with the
    # transport's live figures
    res = _tool('pipeline2dot.py', str(os.getpid()))
    assert res.returncode == 0, res.stderr
    assert 'bridge sink <->' in res.stdout
    assert 'bridge source <->' in res.stdout
    assert 'cds' in res.stdout
    assert 'tx ' in res.stdout and 'rx ' in res.stdout
    # the per-endpoint stats dirs are not rendered as stray blocks
    assert '_bridge_transmit"' not in res.stdout


# ---------------------------------------------------------------------------
# BF_JAX_PROFILE one-shot (telemetry.profiling)
# ---------------------------------------------------------------------------

def test_profiled_dispatch_passthrough_without_env(monkeypatch):
    from bifrost_tpu.telemetry import profiling
    monkeypatch.delenv('BF_JAX_PROFILE', raising=False)
    profiling.reset()
    assert profiling.profiled_dispatch(lambda: 42) == 42
    assert counters.get('jaxprof.captures') == 0


def test_profiled_dispatch_one_shot(monkeypatch, tmp_path):
    from bifrost_tpu.telemetry import profiling
    monkeypatch.setenv('BF_JAX_PROFILE', str(tmp_path / 'prof'))
    profiling.reset()
    calls = []
    monkeypatch.setattr('jax.profiler.start_trace',
                        lambda d: calls.append(('start', d)))
    monkeypatch.setattr('jax.profiler.stop_trace',
                        lambda: calls.append(('stop',)))
    assert profiling.profiled_dispatch(lambda: 7) == 7
    # one-shot: the second dispatch runs unbracketed
    assert profiling.profiled_dispatch(lambda: 8) == 8
    assert calls == [('start', str(tmp_path / 'prof')), ('stop',)]
    assert counters.get('jaxprof.captures') == 1
