"""The one span recorder (telemetry/spans.py): always on, one site API
that feeds the span and the histogram from the same two stamps, spans
where H2D, D2H, the ring fill and the device wait happen, nesting by
thread, and the clock's public origin."""

from __future__ import annotations

import json
import threading
import time
from copy import deepcopy

import numpy as np
import pytest

import bifrost_tpu as bf
import bifrost_tpu.native as native_mod
from bifrost_tpu import xfer
from bifrost_tpu.pipeline import TransformBlock
from bifrost_tpu.ring import Ring
from bifrost_tpu.telemetry import counters, histograms, spans
from bifrost_tpu.testing import faults
from tests.util import NumpySourceBlock, GatherSink, simple_header

NGULP = 12
#: span name (or its suffix after the owner's name) -> histogram
SITES = {
    'h2d': 'xfer.h2d_s',
    'h2d.stage': 'xfer.h2d_stage_s',
    'h2d.put': 'xfer.h2d_put_s',
    'd2h': 'xfer.d2h_wait_s',
    'd2h.ready': 'xfer.d2h_ready_s',
    'd2h.asarray': 'xfer.d2h_asarray_s',
    'd2h.fill': 'xfer.d2h_fill_s',
    'proclog.write': 'proclog.write_s',
}
PARENTS = {
    'h2d': '.on_data', 'h2d.stage': 'h2d', 'h2d.put': 'h2d',
    'd2h.ready': 'd2h', 'd2h.asarray': 'd2h',
}


@pytest.fixture(autouse=True)
def clean_state(monkeypatch):
    for var in ('BF_TRACE_FILE', 'BF_SPAN_BUFFER', 'BF_WATCHDOG_SECS'):
        monkeypatch.delenv(var, raising=False)
    faults.clear()
    counters.reset()
    histograms.reset()
    spans.reconfigure()
    spans.reset()
    xfer.reset_engine()
    yield
    counters.reset()
    histograms.reset()
    spans.reconfigure()
    spans.reset()
    xfer.reset_engine()


@pytest.fixture(params=['native', 'python'])
def ring_core(request, monkeypatch):
    """Both ring cores share the WriteSpan / ReadSpan seam the ring
    spans are taken at (the trick of tests/test_ring_python_core.py)."""
    if request.param == 'python':
        monkeypatch.setattr(native_mod, '_lib', None)
        monkeypatch.setattr(native_mod, '_tried', True)
    elif not native_mod.available():
        pytest.skip('native core unavailable')
    return request.param


class Double(TransformBlock):
    """The stage: one jitted program per gulp on the device ring."""

    def define_valid_input_spaces(self):
        return ('tpu',)

    def on_sequence(self, iseq):
        import jax
        self._fn = jax.jit(lambda x: x * 2)
        return deepcopy(iseq.header)

    def on_data(self, ispan, ospan):
        ospan.set(self._fn(ispan.data))


def run_chain(ngulp=NGULP):
    """system -> copy('tpu') -> stage -> copy('system') -> sink."""
    gulps = [np.full((8, 16), float(k), np.float32) for k in range(ngulp)]
    with bf.Pipeline() as p:
        src = NumpySourceBlock(gulps, simple_header([-1, 16], 'f32'),
                               gulp_nframe=8)
        up = bf.blocks.copy(src, space='tpu')
        stage = Double(up)
        down = bf.blocks.copy(stage, space='system')
        sink = GatherSink(down)
        p.run()
    np.testing.assert_array_equal(sink.result(),
                                  2 * np.concatenate(gulps, axis=0))
    return {'up': up, 'stage': stage, 'down': down, 'sink': sink,
            'src': src}


def by_thread():
    out = {}
    for thread, ev in spans.events():
        out.setdefault(thread, []).append(ev)
    return out


def encloses(parent, child, slack_us=1.0):
    return parent[2] - slack_us <= child[2] and \
        child[2] + child[3] <= parent[2] + parent[3] + slack_us


def test_every_span_of_the_path_nests_in_its_parent(ring_core):
    blocks = run_chain()
    per = by_thread()
    names = {ev[0] for evs in per.values() for ev in evs}
    for name in SITES:
        assert name in names, name
    for b in ('up', 'stage', 'down', 'sink', 'src'):
        assert blocks[b].name + '.on_data' in names
    assert blocks['stage'].name + '.sync_wait' in names
    assert any(n.endswith('.reserve') for n in names)
    assert any(n.endswith('.acquire') for n in names)
    checked = 0
    for thread, evs in per.items():
        for ev in evs:
            want = PARENTS.get(ev[0])
            if want is None:
                continue
            parents = [p for p in evs if p is not ev and
                       p[0].endswith(want) and encloses(p, ev)]
            assert parents, '%s on %s lies in no %s' % (ev[0], thread,
                                                       want)
            checked += 1
    assert checked >= 5 * NGULP
    # categories: what waits is told apart from what works
    cats = {ev[0]: ev[1] for evs in per.values() for ev in evs}
    assert cats['d2h.ready'] == 'wait' and cats['d2h.asarray'] == 'xfer'
    assert cats[blocks['stage'].name + '.sync_wait'] == 'wait'


def test_span_durations_sum_to_the_histogram_per_site(ring_core):
    blocks = run_chain()
    sums = {}
    for _thread, ev in spans.events():
        sums[ev[0]] = sums.get(ev[0], 0.0) + ev[3] * 1e-6
    snap = histograms.snapshot()
    sites = dict(SITES)
    sites[blocks['stage'].name + '.sync_wait'] = \
        'block.%s.sync_wait_s' % blocks['stage'].name
    for b in ('up', 'stage', 'down'):
        for r in blocks[b].orings:
            sites['%s.reserve' % r.name] = 'ring.%s.reserve_s' % r.name
            sites['%s.acquire' % r.name] = 'ring.%s.acquire_s' % r.name
    for span_name, hist_name in sites.items():
        assert hist_name in snap, hist_name
        assert sums[span_name] == pytest.approx(snap[hist_name]['sum'],
                                                rel=1e-6, abs=1e-9), \
            span_name
    counts = {}
    for _thread, ev in spans.events():
        counts[ev[0]] = counts.get(ev[0], 0) + 1
    assert counts['d2h.fill'] == snap['xfer.d2h_fill_s']['count'] == NGULP


def test_ring_spans_carry_the_first_frame(ring_core):
    blocks = run_chain()
    ring = blocks['up'].orings[0].name
    for what in ('reserve', 'acquire'):
        frames = sorted(ev[4]['frame'] for _t, ev in spans.events()
                        if ev[0] == '%s.%s' % (ring, what) and ev[4])
        assert frames[:NGULP] == [8 * k for k in range(NGULP)], what


def test_fill_lands_on_the_reader_that_acquires_first(ring_core,
                                                      monkeypatch):
    """Where no completion thread is free for it (here: the engine has
    none), the fill is claimed by the reader that needs it first."""
    monkeypatch.setattr(xfer, '_D2H_WORKERS', 0)
    data = np.arange(8 * 16, dtype=np.float32).reshape(8, 16)
    hdr = simple_header([-1, 16], 'f32', gulp_nframe=8)
    ring = Ring(space='system')
    eng = xfer.TransferEngine(depth=16)
    got = []

    def read():
        with ring.open_earliest_sequence(guarantee=True) as rs:
            with rs.acquire(0, 8) as span:
                got.append(np.array(span.data.as_numpy(), copy=True))

    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 8, 24) as seq:
            with seq.reserve(8) as sp:
                fill = eng.host_fill(eng.to_device(data), 'f32',
                                     sp.data.as_numpy())
                sp.set_fill(fill)
                sp.commit(8)
            assert not fill.done            # nobody needed the bytes yet
            reader = threading.Thread(target=read, name='the-reader')
            reader.start()
            reader.join(20)
            assert not reader.is_alive()
    np.testing.assert_array_equal(got[0], data)
    per = by_thread()
    mine = [ev[0] for ev in per['the-reader']]
    acquire = '%s.acquire' % ring.name
    assert acquire in mine and 'd2h.fill' in mine and 'd2h' in mine
    # after the acquire stamp has closed, not inside it
    evs = {ev[0]: ev for ev in per['the-reader']}
    assert evs['d2h.fill'][2] >= evs[acquire][2] + evs[acquire][3] - 1.0
    assert evs['d2h.fill'][4]['bytes'] == data.nbytes
    assert all(ev[0] != 'd2h.fill' for t, evs_ in per.items()
               if t != 'the-reader' for ev in evs_)


def test_recording_needs_no_variable_and_the_bound_holds(monkeypatch):
    assert spans.trace_file() is None
    run_chain(ngulp=3)
    assert any(ev[0] == 'd2h.fill' for _t, ev in spans.events())
    assert 'no spans recorded' not in spans.flight_record()
    assert spans.flight_events()
    # the bound, and the count of what fell off it, per thread
    monkeypatch.setenv('BF_SPAN_BUFFER', '32')
    spans.reconfigure()
    spans.reset()

    def flood():
        for i in range(100):
            with spans.timed('flood', 'test', i=i):
                pass

    t = threading.Thread(target=flood, name='flooder')
    t.start()
    t.join()
    mine = [ev for thread, ev in spans.events() if thread == 'flooder']
    assert len(mine) == 32 and mine[0][4] == {'i': 68}
    assert spans.dropped_spans() == 68
    assert spans.dropped_by_thread()['flooder'] == 68


def test_a_retrace_counts_a_compilation_and_leaves_a_span():
    import jax
    spans.watch_jax()
    spans.watch_jax()                        # registered once
    fn = jax.jit(lambda x: x * 3 + 1)
    fn(np.ones((5,), np.float32)).block_until_ready()
    n1 = counters.get('jit.compiles')
    assert n1 >= 1
    fn(np.ones((5,), np.float32)).block_until_ready()     # cached
    assert counters.get('jit.compiles') == n1
    t0 = spans.now_us()
    fn(np.ones((7,), np.float32)).block_until_ready()     # retrace
    assert counters.get('jit.compiles') == n1 + 1
    mine = [ev for _t, ev in spans.events()
            if ev[0] == 'jit.compile' and ev[2] >= t0 - 1.0]
    assert len(mine) == 1 and mine[0][1] == 'jit'
    assert mine[0][4]['event'] == 'backend_compile'
    assert mine[0][2] + mine[0][3] <= spans.now_us()
    h = histograms.snapshot()['jit.compile_s']
    assert h['count'] == counters.get('jit.compiles')


def test_complex_readback_has_no_convert_span_and_staged_slots():
    """complex64 crosses to the host as it is; ``d2h.convert`` is the
    span of a transfer that was given a conversion of its own."""
    import jax.numpy as jnp
    eng = xfer.TransferEngine(zero_copy=False, stage_min=0)
    host = np.arange(64, dtype=np.float32).reshape(8, 8)
    eng.to_device(host)
    staged = [ev[4]['staged'] for _t, ev in spans.events()
              if ev[0] == 'h2d.stage']
    assert staged == [1]
    z = eng.to_host(jnp.asarray(host) * (1 + 2j))
    assert z.dtype == np.complex64
    np.testing.assert_array_equal(z, host * (1 + 2j))
    assert 'd2h.convert' not in [ev[0] for _t, ev in spans.events()]
    dev = jnp.asarray(host)
    dev.copy_to_host_async()
    doubled = xfer.TransferFuture([dev], lambda h: h[0] * 2).result()
    np.testing.assert_array_equal(doubled, host * 2)
    assert 'd2h.convert' in [ev[0] for _t, ev in spans.events()]
    snap = histograms.snapshot()
    parts = sum(snap[n]['sum'] for n in ('xfer.d2h_ready_s',
                                         'xfer.d2h_asarray_s',
                                         'xfer.d2h_convert_s'))
    assert parts <= snap['xfer.d2h_wait_s']['sum']


def test_depth_wait_span_holds_the_transfer_it_retires():
    eng = xfer.TransferEngine(depth=1)
    a = eng.to_device(np.ones((4, 4), np.float32))
    b = eng.to_device(np.ones((4, 4), np.float32))
    f1 = eng.to_host_async(a)
    eng.to_host_async(b)                     # pushes f1 past the bound
    assert f1.done
    evs = [ev for _t, ev in spans.events()]
    waits = [ev for ev in evs if ev[0] == 'd2h.depth_wait']
    assert len(waits) == 1 and waits[0][1] == 'wait'
    inner = [ev for ev in evs if ev[0] == 'd2h']
    assert len(inner) == 1 and encloses(waits[0], inner[0])


def test_waiting_for_a_peer_to_finish_the_same_fill_is_a_span():
    """A reader, the depth bound and every block's per-gulp drain race
    for one fill; the losers sit out the winner's work."""
    started = threading.Event()

    def slow(_host):
        started.set()
        time.sleep(0.05)
        return np.ones((4, 4), np.float32)

    out = np.zeros((4, 4), np.float32)
    fill = xfer.HostFill(xfer.TransferFuture([], slow), 'f32', out)
    winner = threading.Thread(target=fill.wait, name='the-winner')
    winner.start()
    assert started.wait(10)
    fill.wait()                              # the loser, on this thread
    winner.join(10)
    assert fill.done and out[0, 0] == 1.0
    per = by_thread()
    assert [ev[0] for ev in per['the-winner']
            if ev[0] in ('d2h.fill', 'd2h.peer_wait')] == ['d2h.fill']
    (wait,) = [ev for ev in per[threading.current_thread().name]
               if ev[0] == 'd2h.peer_wait']
    assert wait[1] == 'wait' and wait[3] >= 20e3
    h = histograms.snapshot()['xfer.d2h_peer_wait_s']
    assert h['count'] == 1 and h['sum'] == pytest.approx(wait[3] * 1e-6)
    # an uncontended lock leaves none
    spans.reset()
    xfer.TransferFuture([], lambda _h: 1).result()
    assert not [ev for _t, ev in spans.events() if ev[0] == 'd2h.peer_wait']


def test_timed_feeds_both_sinks_even_when_the_block_raises():
    h = histograms.get_or_create('t.site_s', unit='s')
    with pytest.raises(ValueError):
        with spans.timed('t.site', 'test', hist=h, k=1) as tm:
            tm.args['late'] = 2              # args may be added inside
            time.sleep(0.002)
            raise ValueError('boom')
    (ev,) = [ev for _t, ev in spans.events() if ev[0] == 't.site']
    assert ev[4] == {'k': 1, 'late': 2}
    assert h.count == 1
    assert ev[3] * 1e-6 == pytest.approx(h.total, rel=1e-9)
    assert ev[3] >= 2000.0
    # a histogram may be given by name
    with spans.timed('t.site2', 'test', hist='t.site2_s'):
        pass
    assert histograms.get('t.site2_s').count == 1


def test_the_clock_origin_is_public_and_exported(tmp_path):
    before = time.perf_counter()
    with spans.timed('t.clock', 'test'):
        pass
    after = time.perf_counter()
    (ev,) = [ev for _t, ev in spans.events() if ev[0] == 't.clock']
    start = spans.origin_s() + ev[2] * 1e-6
    assert before <= start <= after
    path = spans.export(str(tmp_path / 'clock.json'))
    data = json.loads(open(path).read())
    assert data['otherData']['bf_clock']['origin_s'] == spans.origin_s()
    threads = [e['args']['name'] for e in data['traceEvents']
               if e.get('ph') == 'M']
    assert threading.current_thread().name in threads


def test_a_full_collection_leaves_a_host_gc_span():
    import gc
    t0 = spans.now_us()
    gc.collect(1)                            # generation 1: no span
    assert not [ev for _t, ev in spans.events() if ev[0] == 'host.gc']
    gc.collect()
    (ev,) = [ev for _t, ev in spans.events() if ev[0] == 'host.gc']
    assert ev[1] == 'host' and ev[4] == {'gen': 2} and ev[2] >= t0


def test_block_loop_stamps_are_on_the_span_clock():
    """gulp_s is taken with perf_counter like every span: a block's
    spans of one gulp fit inside its loop's own time for it."""
    blocks = run_chain(ngulp=6)
    name = blocks['up'].name
    gulp = histograms.snapshot()['block.%s.gulp_s' % name]
    inside = sum(ev[3] for _t, ev in spans.events()
                 if ev[0] == name + '.on_data') * 1e-6
    assert gulp['count'] == 6
    assert inside <= gulp['sum']


# -- every span's CPU, from its thread's clock ------------------------------

def _burn(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize('what,low,high', [
    (_burn, 0.8, 1.2),            # a spin: on a CPU for all of it
    (time.sleep, 0.0, 0.05),      # a sleep: for none of it
], ids=['spin', 'sleep'])
def test_cpu_us_tells_a_spin_from_a_sleep(what, low, high):
    # a spin on a machine that is short of cores is kept off them for
    # a part of its span: three tries at one that was not
    for attempt in range(3):
        spans.reset()
        c0 = time.thread_time()
        with spans.timed('t.cpu', 'test'):
            what(0.1)
        spent_us = (time.thread_time() - c0) * 1e6
        (ev,) = [ev for _t, ev in spans.events() if ev[0] == 't.cpu']
        assert len(ev) == 6 and ev[3] >= 100e3
        # the thread's own clock, read by the span as by anybody
        assert ev[5] <= spent_us + 1.0 and ev[5] >= spent_us - 2000.0
        if low * ev[3] <= ev[5] <= high * ev[3]:
            return
    raise AssertionError('cpu_us %.0f of dur_us %.0f' % (ev[5], ev[3]))


def test_fields_0_to_4_are_where_they_were():
    h = histograms.get_or_create('t.fields_s', unit='s')
    t0 = spans.now_us()
    with spans.timed('t.fields', 'test', hist=h, k=1):
        pass
    (ev,) = [ev for _t, ev in spans.events() if ev[0] == 't.fields']
    name, cat, ts_us, dur_us, args = ev[:5]
    assert (name, cat, args) == ('t.fields', 'test', {'k': 1})
    assert t0 <= ts_us <= spans.now_us() and dur_us >= 0
    assert dur_us * 1e-6 == pytest.approx(h.total, rel=1e-9)
    assert ev[5] >= 0


def test_a_recorded_event_has_no_cpu_time_unless_given():
    spans.record('t.after', 'test', 1.0, 2.0)
    spans.record('t.after_args', 'test', 3.0, 4.0, {'k': 2})
    spans.record('t.after_cpu', 'test', 5.0, 6.0, cpu_us=1.5)
    got = {ev[0]: ev for _t, ev in spans.events() if ev[1] == 'test'}
    assert got['t.after'] == ('t.after', 'test', 1.0, 2.0, None, None)
    assert got['t.after_args'][4:] == ({'k': 2}, None)
    assert got['t.after_cpu'][5] == 1.5
    import gc
    gc.collect()
    (ev,) = [ev for _t, ev in spans.events() if ev[0] == 'host.gc']
    assert ev[5] is None


def test_an_interval_is_not_a_thread_s_time():
    """``h2d.hold`` starts in one call and ends in a later one, maybe
    another thread's: wall time and a histogram, no CPU time."""
    h = histograms.get_or_create('t.hold_s', unit='s')
    timer = spans.interval('t.hold', 'wait', h, bytes=8)
    timer.__enter__()
    _burn(0.01)
    t = threading.Thread(target=timer.__exit__, args=(None, None, None),
                         name='the-releaser')
    t.start()
    t.join(10)
    (thread, ev), = [(t, ev) for t, ev in spans.events()
                     if ev[0] == 't.hold']
    assert thread == 'the-releaser'
    assert ev[3] >= 10e3 and ev[4] == {'bytes': 8} and ev[5] is None
    assert h.count == 1


def test_the_chrome_export_has_tdur_where_a_span_has_cpu_time(tmp_path):
    with spans.timed('t.export', 'test'):
        _burn(0.005)
    spans.record('t.export_after', 'test', 1.0, 2.0, {'k': 1})
    data = json.loads(open(spans.export(str(tmp_path / 't.json'))).read())
    evs = {e['name']: e for e in data['traceEvents'] if e.get('ph') == 'X'}
    assert evs['t.export']['tdur'] == pytest.approx(
        evs['t.export']['dur'], rel=0.3)
    assert 'tdur' not in evs['t.export_after']
    assert evs['t.export_after']['args'] == {'k': 1}
    # the wire form of the fleet plane stays as it was
    assert all(len(e) == 6 and not isinstance(e[5], float)
               for e in spans.flight_events())
    assert 't.export' in spans.flight_record()


def test_the_completion_thread_s_rest_is_a_span_and_costs_nothing():
    """``d2h.idle``: the completion thread's wait for a fill to claim,
    asleep on the engine's condition (``d2h.cut``, its cutting up of
    the product it lands next: tests/test_xfer_async.py)."""
    eng = xfer.TransferEngine()
    data = [np.full((8, 16), float(k), np.float32) for k in range(3)]
    outs = [np.zeros_like(d) for d in data]
    for d, out in zip(data, outs):
        eng.host_fill(eng.to_device(d), 'f32', out).wait()
        time.sleep(0.05)           # the completion threads rest
    eng.drain(True)
    eng.close()
    for d, out in zip(data, outs):
        np.testing.assert_array_equal(out, d)
    idle = [(t, ev) for t, ev in spans.events() if ev[0] == 'd2h.idle']
    assert idle and all(t.startswith('xfer-d2h-') and ev[1] == 'wait'
                        for t, ev in idle)
    assert sum(ev[3] for _t, ev in idle) >= 50e3
    assert sum(ev[5] for _t, ev in idle) <= \
        0.2 * sum(ev[3] for _t, ev in idle)
    assert 'xfer.d2h_idle_s' not in histograms.snapshot()
