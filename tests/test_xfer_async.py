"""Async transfer engine (bifrost_tpu.xfer): staging aliasing safety,
out-of-order completion drain, deferred D2H ring fills, buffer
donation bit-exactness, and the sync_strict fallback."""

import gc
import sys
import threading
import time

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu import xfer
from bifrost_tpu.telemetry import counters, histograms, spans
from tests.util import NumpySourceBlock, GatherSink, simple_header


@pytest.fixture(autouse=True)
def _reset():
    counters.reset()
    yield
    xfer.reset_engine()


# ---------------------------------------------------------------------------
# staging aliasing safety (the bug the old defensive copy guarded)
# ---------------------------------------------------------------------------

def test_to_device_does_not_alias_recycled_host_memory():
    """A writer recycling its host buffer right after to_device must
    not corrupt the device array — the exact CPU-backend zero-copy bug
    the old defensive copy guarded against."""
    eng = xfer.TransferEngine()
    ringbuf = np.arange(64 * 1024, dtype=np.float32).reshape(64, 1024)
    want = ringbuf.copy()
    d = eng.to_device(ringbuf)
    ringbuf[...] = -1.0                 # writer recycles the gulp
    assert np.array_equal(np.asarray(d), want)


def test_to_device_alias_safe_under_inflight_compute():
    """Recycling the source while a dispatched computation is still
    running must not change its result (staging buffers are never
    reused while any consumer may read them)."""
    import jax
    eng = xfer.TransferEngine()
    fn = jax.jit(lambda x: (x @ x).sum())
    src = np.full((512, 512), 1.0, np.float32)
    d = eng.to_device(src)
    y = fn(d)                           # async dispatch reads d
    del d
    src[...] = 0.0                      # recycle immediately
    gc.collect()
    # a second transfer of the same shape must not steal the buffer
    eng.to_device(np.zeros((512, 512), np.float32))
    assert float(y) == 512.0 * 512 * 512


def test_staging_pool_recycles_only_completed_transfers():
    """Copying-backend protocol (forced via zero_copy=False): a slot
    returns to the pool only once its transfer is observed complete;
    a slot whose array died unobserved is dropped, not reused."""
    eng = xfer.TransferEngine(staging=2, zero_copy=False)
    a = np.ones((256, 256), np.float32)
    d1 = eng.to_device(a)
    d1.block_until_ready()
    assert counters.get('xfer.h2d_staged') == 1
    # d1 complete and still alive: its slot is reclaimable
    d2 = eng.to_device(a * 2)
    assert counters.get('xfer.h2d_staged') == 2
    pool = eng._pool
    assert pool._nalloc[((256, 256), 'float32')] <= 2
    # kill an array whose completion was never observed after this
    # point: the pool must DROP the slot (nalloc decremented), never
    # hand its buffer out for reuse
    slot_entry = [s for s in pool._busy if s.ref() is d2]
    assert slot_entry
    del d2
    gc.collect()
    assert slot_entry[0].recycled
    buf_id = id(slot_entry[0].buf)
    free = pool._free.get(((256, 256), 'float32'), [])
    assert all(id(b) != buf_id for b in free)


def test_drain_recycles_the_slot_of_a_transfer_it_sees_complete():
    """A block ships ahead, waits for its arrays and lets go of them
    before it stages again: the per-gulp drain in between is where the
    pool sees them complete.  Without it the slot is dropped with the
    array, and its replacement is a first touch of fresh pages."""
    key = ((256, 256), 'float32')
    a = np.ones((256, 256), np.float32)
    for drains in (True, False):
        eng = xfer.TransferEngine(staging=2, zero_copy=False)
        d = eng.to_device(a)
        d.block_until_ready()
        (slot,) = eng._pool._busy
        if drains:
            eng.drain()
        del d
        gc.collect()
        assert slot.recycled
        free = eng._pool._free.get(key, [])
        assert (len(free) == 1 and free[0] is slot.buf) if drains \
            else not free
        assert eng._pool._nalloc[key] == (1 if drains else 0)


# ---------------------------------------------------------------------------
# non-blocking D2H: futures, queue bound, out-of-order drain
# ---------------------------------------------------------------------------

def test_staging_pool_survives_donated_arrays():
    """Regression: the pool's reclaim scan must not poll is_ready() on
    an array that was donated (deleted) downstream — that crashes the
    runtime.  And deletion happens at DISPATCH time, proving nothing
    about the DMA, so the slot must be DROPPED (never reused)."""
    from bifrost_tpu.ops.common import donating_jit
    eng = xfer.TransferEngine(staging=2, zero_copy=False)
    a = np.ones((128, 128), np.float32)
    d = eng.to_device(a)
    d.block_until_ready()
    pool = eng._pool
    slot = [s for s in pool._busy if s.ref() is d][0]
    buf_id = id(slot.buf)
    fn = donating_jit(lambda x: x + 1.0, donate_argnums=(0,))
    y = fn(d)                       # d is now deleted, slot still bound
    assert d.is_deleted()
    d2 = eng.to_device(a * 3)       # triggers the reclaim scan
    assert np.array_equal(np.asarray(d2), a * 3)
    assert float(y[0, 0]) == 2.0
    # the donated slot was retired, not recycled into the free list
    assert slot.recycled
    assert all(id(b) != buf_id
               for bufs in pool._free.values() for b in bufs)


def test_to_device_empty_array():
    """Zero-size gulps must transfer cleanly (regression: the aligned
    allocator rejected empty shapes)."""
    eng = xfer.TransferEngine()
    for zc in (True, False):
        e = xfer.TransferEngine(zero_copy=zc)
        d = e.to_device(np.empty((0, 4), np.float32))
        assert np.asarray(d).shape == (0, 4)
    assert np.asarray(eng.to_device(np.float32(3.0))).shape == ()


def test_early_completed_fill_still_mirrors_ghost(monkeypatch):
    """Regression: with the async queue disabled but the fill path
    active (sync_strict=False scope + BF_XFER_ASYNC=0), fills complete
    BEFORE the span closes; the ghost mirror for wrapped spans must
    still run (at attach), or readers of wrapped bytes see stale
    data."""
    monkeypatch.setenv('BF_XFER_ASYNC', '0')
    # Python ring core: its commit-time ghost mirror is SKIPPED for
    # spans carrying a fill (the fill owns mirroring), so an
    # early-completed fill relies entirely on the attach-time mirror.
    # (The native core re-mirrors inside bft_ring_commit, which runs
    # after a synchronously-completed fill's write — covered there.)
    monkeypatch.setenv('BF_NO_NATIVE', '1')
    from bifrost_tpu.ring import Ring
    rng = np.random.RandomState(21)
    data = rng.randn(24, 16).astype(np.float32)
    hdr = simple_header([-1, 16], 'f32', gulp_nframe=8)
    ring = Ring(space='system')
    eng = xfer.TransferEngine()
    with ring.begin_writing() as w:
        # 20-frame buffer, 8-frame spans: the third span ([16, 24))
        # wraps and writes frames 20-23 through the ghost region
        with w.begin_sequence(hdr, 8, 20) as seq:
            for g0 in (0, 8, 16):
                dev = eng.to_device(data[g0:g0 + 8])
                with seq.reserve(8) as sp:
                    fill = eng.host_fill(dev, 'f32',
                                         sp.data.as_numpy())
                    assert fill.done   # completed BEFORE close/attach
                    sp.set_fill(fill)
                    sp.commit(8)
            # a reader whose span starts INSIDE the wrapped region
            # ([18, 22)) reads the mirrored start-of-buffer bytes —
            # the path only the attach-time mirror feeds (a reader
            # framed like the writer reads back through the ghost
            # area directly and would never notice a missing mirror)
            with ring.open_earliest_sequence(guarantee=False) as rs:
                with rs.acquire(18, 4) as span:
                    got = np.array(span.data.as_numpy(), copy=True)
    np.testing.assert_allclose(got, data[18:22], rtol=1e-6)


def test_out_of_order_completion_drain():
    """Futures may be resolved in any order; the engine's drain retires
    whatever completed without disturbing the rest."""
    eng = xfer.TransferEngine(depth=16)
    arrs = [np.full((32, 32), i, np.float32) for i in range(8)]
    futs = [eng.to_host_async(eng.to_device(a)) for a in arrs]
    # resolve a scattered subset first, then drain, then the rest
    for i in (5, 1, 6, 2):
        assert np.array_equal(futs[i].result(), arrs[i])
    eng.drain()
    for i in (7, 0, 3, 4):
        assert np.array_equal(futs[i].result(), arrs[i])
    assert eng.outstanding == 0


def test_async_queue_bound_forces_oldest():
    """More than ``depth`` outstanding transfers retire the oldest
    first — bounded backpressure, not unbounded growth."""
    eng = xfer.TransferEngine(depth=2)
    futs = [eng.to_host_async(eng.to_device(
        np.full((16,), i, np.float32))) for i in range(6)]
    # the first four must have been forced by the bound
    assert all(f.done for f in futs[:4])
    assert eng.outstanding <= 2


def test_complex_roundtrip_via_futures():
    eng = xfer.TransferEngine()
    c = (np.random.RandomState(0).randn(32, 16) +
         1j * np.random.RandomState(1).randn(32, 16)).astype(np.complex64)
    fut = eng.to_host_async(eng.to_device(c))
    got = fut.result()
    assert got.dtype == np.complex64
    np.testing.assert_allclose(got, c, rtol=1e-6)


# ---------------------------------------------------------------------------
# deferred D2H ring fills through a real pipeline
# ---------------------------------------------------------------------------

def _chain_stages():
    from bifrost_tpu.stages import FftStage, DetectStage, ReduceStage
    return [FftStage('fine_time', axis_labels='freq'),
            DetectStage('stokes', axis='pol'),
            ReduceStage('freq', 4)]


def _make_raw(nt=64, npol=2, nf=256, seed=7):
    rng = np.random.RandomState(seed)
    raw = np.zeros((nt, npol, nf), dtype=np.dtype([('re', 'i1'),
                                                   ('im', 'i1')]))
    raw['re'] = rng.randint(-64, 64, raw.shape)
    raw['im'] = rng.randint(-64, 64, raw.shape)
    return raw


def _run_chain(raw, ngulp=6, **scope):
    hdr = simple_header([-1, raw.shape[1], raw.shape[2]], 'ci8',
                        labels=['time', 'pol', 'fine_time'])
    with bf.Pipeline(**scope) as p:
        src = NumpySourceBlock([raw.copy() for _ in range(ngulp)], hdr,
                               gulp_nframe=raw.shape[0])
        b = bf.blocks.copy(src, space='tpu')
        fb = bf.blocks.fused(b, _chain_stages())
        b2 = bf.blocks.copy(fb, space='system')
        sink = GatherSink(b2)
        p.run()
    return sink.result(), fb


def test_async_d2h_fills_deliver_correct_data():
    """CopyBlock's deferred-fill D2H must deliver byte-identical data
    to the synchronous path, and must actually run async (d2h_async
    counter) with hard syncs bounded by sync_depth."""
    raw = _make_raw()
    out_async, _ = _run_chain(raw, ngulp=8, sync_depth=4)
    snap = counters.snapshot()
    assert snap.get('xfer.d2h_async', 0) >= 8
    waits = snap.get('pipeline.sync_waits', 0)
    dev_gulps = snap.get('pipeline.gulps_device', 1)
    assert waits <= dev_gulps / 4.0 + 1
    counters.reset()
    out_sync, _ = _run_chain(raw, ngulp=8, sync_depth=4,
                             sync_strict=True)
    assert np.array_equal(out_async, out_sync)


def test_sync_strict_fallback_is_synchronous():
    """sync_strict=True must route every D2H through the blocking path
    (no deferred fills, no async queue)."""
    raw = _make_raw(seed=3)
    _run_chain(raw, ngulp=4, sync_strict=True)
    assert counters.get('xfer.d2h_async') == 0


def test_strict_env_disables_async(monkeypatch):
    monkeypatch.setenv('BF_SYNC_STRICT', '1')
    assert not xfer.async_enabled()
    eng = xfer.TransferEngine()
    fut = eng.to_host_async(eng.to_device(np.ones(4, np.float32)))
    assert fut.done                     # completed synchronously


def test_partial_commit_fill_completes_synchronously():
    """A partially-committed span carrying a fill must complete it at
    close (the truncated tail's bytes roll back and become
    re-reservable — a deferred write there would corrupt the next
    span)."""
    from bifrost_tpu.ring import Ring
    rng = np.random.RandomState(8)
    data = rng.randn(8, 16).astype(np.float32)
    fresh = rng.randn(8, 16).astype(np.float32)
    hdr = simple_header([-1, 16], 'f32', gulp_nframe=8)
    ring = Ring(space='system')
    eng = xfer.TransferEngine(depth=16)
    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 8, 24) as seq:
            dev = eng.to_device(data)
            with seq.reserve(8) as sp:
                fill = eng.host_fill(dev, 'f32', sp.data.as_numpy())
                sp.set_fill(fill)
                sp.commit(4)            # partial: tail rolls back
            assert fill.done            # completed at close, not later
            # the rolled-back frames are re-reserved by the next span;
            # the old fill must not clobber them afterwards
            with seq.reserve(8) as sp2:
                sp2.data.as_numpy()[...] = fresh
                sp2.commit(8)
            eng.drain(block=True)
            with ring.open_earliest_sequence(guarantee=False) as rs:
                with rs.acquire(0, 12) as span:
                    got = np.array(span.data.as_numpy(), copy=True)
    np.testing.assert_allclose(got[:4], data[:4], rtol=1e-6)
    np.testing.assert_allclose(got[4:12], fresh, rtol=1e-6)


def test_host_fill_wraparound_ghost():
    """A deferred fill landing in a wrapped span must still mirror the
    ghost overflow so readers of the wrapped bytes see the data (the
    commit-time mirror ran before the bytes existed)."""
    # many small gulps through a deliberately tight ring forces wraps
    rng = np.random.RandomState(11)
    gulps = [rng.randn(8, 16).astype(np.float32) for _ in range(12)]
    hdr = simple_header([-1, 16], 'f32')
    with bf.Pipeline(buffer_nframe=20) as p:
        src = NumpySourceBlock(gulps, hdr, gulp_nframe=8)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.copy(b, space='system')
        sink = GatherSink(b)
        p.run()
    np.testing.assert_allclose(sink.result(),
                               np.concatenate(gulps, axis=0),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# completion threads: who completes a deferred fill, and how many at once
# ---------------------------------------------------------------------------

#: seconds any one wait of these tests may last: a deadlock fails here,
#: not at the suite's limit
SOON = 10.0


def within(fn, *args):
    """``fn(*args)`` on a thread of its own, which has to end SOON;
    returns its value or raises what it raised."""
    box = []

    def run():
        try:
            box.append((True, fn(*args)))
        except BaseException as exc:
            box.append((False, exc))

    t = threading.Thread(target=run, name='within')
    t.start()
    t.join(SOON)
    assert not t.is_alive(), '%s did not return in %g s' % (fn, SOON)
    ok, value = box[0]
    if not ok:
        raise value
    return value


class GatedFuture(object):
    """A transfer whose ``result()`` waits for the test's word."""

    def __init__(self, value, fail=None):
        self.value = value
        self.fail = fail
        self.gate = threading.Event()
        self.entered = threading.Event()
        self.thread = None
        self.done = False
        self.error = None

    def ready(self):
        return self.gate.is_set()

    def result(self):
        self.thread = threading.current_thread().name
        self.entered.set()
        assert self.gate.wait(SOON), 'the gate never opened'
        self.done = True
        if self.fail is not None:
            self.error = self.fail
            raise self.fail
        return self.value


@pytest.fixture
def gated(monkeypatch):
    """An engine with two completion threads whose transfers are
    GatedFutures, in order of issue; closed (gates opened first) when
    the test ends."""
    monkeypatch.setattr(xfer, '_D2H_WORKERS', 2)
    eng = xfer.TransferEngine(depth=16)
    eng.futures = []

    def future_for(value, pieces=False):
        fut = GatedFuture(value)
        eng.futures.append(fut)
        return fut

    eng._future_for = future_for
    yield eng
    for fut in eng.futures:
        fut.gate.set()
    within(eng.close)


def _ring_of(nframe_buf, nframe_gulp=8):
    from bifrost_tpu.ring import Ring
    ring = Ring(space='system')
    hdr = simple_header([-1, 16], 'f32', gulp_nframe=nframe_gulp)
    return ring, hdr


def test_two_fills_of_different_spans_complete_at_the_same_time(gated):
    a, b = (np.full((8, 16), v, np.float32) for v in (1.0, 2.0))
    out_a, out_b = np.zeros_like(a), np.zeros_like(b)
    fa = gated.host_fill(a, 'f32', out_a)
    fb = gated.host_fill(b, 'f32', out_b)
    # both are inside their transfer before either is let go
    for fut in gated.futures:
        assert fut.entered.wait(SOON)
    assert sorted(f.thread for f in gated.futures) == \
        ['xfer-d2h-0', 'xfer-d2h-1']
    assert not fa.done and not fb.done
    gated.futures[1].gate.set()              # the younger lands first
    within(fb.wait)
    assert fb.done and not fa.done
    gated.futures[0].gate.set()
    within(fa.wait)
    assert np.array_equal(out_a, a) and np.array_equal(out_b, b)
    assert counters.get('xfer.fills_by_worker') == 2
    assert counters.get('xfer.fills_by_caller') == 0


def test_drain_neither_completes_nor_waits_for_a_claimed_fill(gated):
    data = np.ones((8, 16), np.float32)
    fill = gated.host_fill(data, 'f32', np.zeros_like(data))
    assert gated.futures[0].entered.wait(SOON)       # claimed, at work
    t0 = time.perf_counter()
    assert within(gated.drain) == 0
    assert time.perf_counter() - t0 < SOON / 2
    assert not fill.done and gated.outstanding == 1
    gated.futures[0].gate.set()
    within(fill.wait)
    assert within(gated.drain) == 1 and gated.outstanding == 0


def test_drain_leaves_an_unclaimed_fill_alone(gated, monkeypatch):
    monkeypatch.setattr(xfer, '_D2H_WORKERS', 0)
    data = np.ones((8, 16), np.float32)
    fill = gated.host_fill(data, 'f32', np.zeros_like(data))
    gated.futures[0].gate.set()              # finished on its own
    assert within(gated.drain) == 0
    assert not fill.done and not gated.futures[0].entered.is_set()
    assert within(gated.drain, True) == 1    # block=True completes it
    assert fill.done and counters.get('xfer.fills_by_caller') == 1


def test_reader_that_arrives_first_claims_the_fill(gated):
    """Both completion threads are held on other transfers; the reader
    of a wrapped span completes its fill itself, ghost mirror and
    all, and waits for nobody."""
    rng = np.random.RandomState(5)
    data = rng.randn(24, 16).astype(np.float32)
    ring, hdr = _ring_of(20)
    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 8, 20) as seq:
            for g0 in (0, 8):
                with seq.reserve(8) as sp:
                    sp.set_fill(gated.host_fill(data[g0:g0 + 8], 'f32',
                                                sp.data.as_numpy()))
                    sp.commit(8)
            for fut in gated.futures:            # landed, by the workers
                assert fut.entered.wait(SOON)    # (each has one: a drain
                fut.gate.set()                   # that came first would)
            within(gated.drain, True)
            busy = [gated.host_fill(data[:8], 'f32', np.zeros((8, 16),
                                                              np.float32))
                    for _ in range(2)]
            for fut in gated.futures[2:]:
                assert fut.entered.wait(SOON)    # both threads held
            # [16, 24) wraps: frames 20-23 go through the ghost region
            with seq.reserve(8) as sp:
                sp.set_fill(gated.host_fill(data[16:], 'f32',
                                            sp.data.as_numpy()))
                sp.commit(8)
            last = gated.futures[-1]
            last.gate.set()
            time.sleep(0.05)
            assert not last.entered.is_set()     # nobody took it

            def read():
                with ring.open_earliest_sequence(guarantee=False) as rs:
                    with rs.acquire(18, 4) as span:
                        return np.array(span.data.as_numpy(), copy=True)

            histograms.reset()
            got = within(read)
    assert last.thread == 'within'
    assert np.array_equal(got, data[18:22])
    assert counters.get('xfer.fills_by_caller') == 1
    assert not any(f.done for f in busy)
    assert histograms.get_or_create('xfer.d2h_peer_wait_s').count == 0


def test_reader_that_arrives_second_waits_in_peer_wait(gated):
    histograms.reset()
    spans.reset()
    data = np.random.RandomState(6).randn(8, 16).astype(np.float32)
    ring, hdr = _ring_of(24)
    got = []

    def read():
        with ring.open_earliest_sequence(guarantee=True) as rs:
            with rs.acquire(0, 8) as span:
                got.append(np.array(span.data.as_numpy(), copy=True))

    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 8, 24) as seq:
            with seq.reserve(8) as sp:
                sp.set_fill(gated.host_fill(data, 'f32',
                                            sp.data.as_numpy()))
                sp.commit(8)
            assert gated.futures[0].entered.wait(SOON)   # a worker has it
            reader = threading.Thread(target=read, name='the-reader')
            reader.start()
            reader.join(0.2)
            assert reader.is_alive() and not got         # it waits
            gated.futures[0].gate.set()
            reader.join(SOON)
            assert not reader.is_alive()
    assert np.array_equal(got[0], data)
    mine = [ev[0] for t, ev in spans.events() if t == 'the-reader']
    assert 'd2h.peer_wait' in mine and 'd2h.fill' not in mine
    filled = [t for t, ev in spans.events() if ev[0] == 'd2h.fill']
    assert filled == [gated.futures[0].thread]
    assert filled[0].startswith('xfer-d2h-')
    assert histograms.get_or_create('xfer.d2h_peer_wait_s').count == 1
    assert counters.get('xfer.fills_by_worker') == 1


@pytest.mark.parametrize('fails', ['before_the_span_closes',
                                   'after_the_span_closed'])
def test_failure_on_a_worker_poisons_the_ring(gated, fails):
    boom = RuntimeError('the transfer failed')
    data = np.ones((8, 16), np.float32)
    ring, hdr = _ring_of(24)
    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 8, 24) as seq:
            with seq.reserve(8) as sp:
                fill = gated.host_fill(data, 'f32', sp.data.as_numpy())
                gated.futures[0].fail = boom
                if fails == 'before_the_span_closes':
                    gated.futures[0].gate.set()
                    assert fill._landed.wait(SOON)
                sp.set_fill(fill)
                sp.commit(8)
            gated.futures[0].gate.set()
            assert fill._landed.wait(SOON)
            assert gated.futures[0].thread.startswith('xfer-d2h-')
            assert fill.done and fill.error is boom
            assert ring.poisoned
            assert counters.get('xfer.fill_errors') == 1
            # raised on the thread that drains next, once retired
            with pytest.raises(RuntimeError, match='the transfer failed'):
                within(gated.drain)
            assert within(gated.drain) == 0
            with pytest.raises(RuntimeError, match='the transfer failed'):
                within(fill.wait)


def test_reset_engine_joins_the_completion_threads():
    eng = xfer.engine()
    data = np.arange(128, dtype=np.float32).reshape(8, 16)
    out = np.zeros_like(data)
    fill = eng.host_fill(eng.to_device(data), 'f32', out)
    workers = list(eng._workers)
    assert [t.name for t in workers] == \
        ['xfer-d2h-%d' % i for i in range(xfer._D2H_WORKERS)]
    assert all(t.daemon for t in workers)
    within(xfer.reset_engine)
    assert fill.done and np.array_equal(out, data)
    assert not any(t.is_alive() for t in workers)
    # and those of engines the tests before this one let go of
    gc.collect()
    deadline = time.monotonic() + SOON
    while time.monotonic() < deadline and any(
            t.name.startswith('xfer-d2h-') for t in threading.enumerate()):
        time.sleep(0.01)
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith('xfer-d2h-')]
    # a closed engine starts none again: its fills stay with callers
    before = counters.get('xfer.fills_by_caller')
    late = eng.host_fill(eng.to_device(data), 'f32', np.zeros_like(data))
    assert not eng._workers
    within(late.wait)
    assert counters.get('xfer.fills_by_caller') == before + 1


@pytest.mark.parametrize('var', ['BF_XFER_ASYNC', 'BF_SYNC_STRICT'])
def test_synchronous_fills_never_reach_a_worker(var, monkeypatch):
    monkeypatch.setenv(var, '0' if var == 'BF_XFER_ASYNC' else '1')
    eng = xfer.TransferEngine()
    data = np.arange(128, dtype=np.float32).reshape(8, 16)
    out = np.zeros_like(data)
    fill = eng.host_fill(eng.to_device(data), 'f32', out)
    assert fill.done and np.array_equal(out, data)
    assert not eng._workers and not eng._fills
    assert counters.get('xfer.fills_by_caller') == 1
    assert counters.get('xfer.fills_by_worker') == 0


def test_sync_strict_scope_never_reaches_a_worker():
    _run_chain(_make_raw(seed=4), ngulp=4, sync_strict=True)
    assert counters.get('xfer.fills_by_worker') == 0
    assert counters.get('xfer.fills_by_caller') == 0
    assert not xfer.engine()._workers


def test_every_fill_is_completed_once_by_one_side():
    """fills_by_worker + fills_by_caller = fills issued, through a real
    pipeline (which side takes a fill is a race; that one does is
    not)."""
    raw = _make_raw(seed=6)
    out_async, _ = _run_chain(raw, ngulp=8)
    snap = counters.snapshot()
    assert snap.get('xfer.d2h_async', 0) == 8
    assert snap.get('xfer.fills_by_worker', 0) + \
        snap.get('xfer.fills_by_caller', 0) == 8
    counters.reset()
    out_sync, _ = _run_chain(raw, ngulp=8, sync_strict=True)
    assert np.array_equal(out_async, out_sync)


def test_claim_survives_many_racing_threads():
    """More waiters than cores on every fill, with the completion
    threads and a drain loop beside them, at a short switch interval:
    each fill is completed exactly once and every waiter sees its
    bytes."""
    nfill, nwaiter = 200, 8
    eng = xfer.TransferEngine(depth=nfill)
    rng = np.random.RandomState(9)
    data = [rng.randn(4, 16).astype(np.float32) for _ in range(nfill)]
    outs = [np.zeros_like(d) for d in data]
    fills = []
    wrong = []
    go = threading.Event()

    def waiter(seed):
        go.wait(SOON)
        for i in np.random.RandomState(seed).permutation(nfill):
            while i >= len(fills):
                time.sleep(0)
            fills[i].wait()
            if not np.array_equal(outs[i], data[i]):
                wrong.append(i)

    threads = [threading.Thread(target=waiter, args=(k,))
               for k in range(nwaiter)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        go.set()
        for d, o in zip(data, outs):
            fills.append(eng.host_fill(eng.to_device(d), 'f32', o))
            eng.drain()
        for t in threads:
            t.join(3 * SOON)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
        within(eng.close)
    assert not wrong
    assert counters.get('xfer.fills_by_worker') + \
        counters.get('xfer.fills_by_caller') == nfill
    assert counters.get('xfer.d2h_issued') == nfill
    assert histograms.get_or_create('xfer.d2h_fill_s').count >= nfill


# ---------------------------------------------------------------------------
# products that cross in pieces
# ---------------------------------------------------------------------------

def _span_names():
    from bifrost_tpu.telemetry import spans
    return [ev[0] for _t, ev in spans.events()]


#: float32 words that arithmetic would not bring through: -0.0, both
#: infinities, quiet and signalling NaNs of several payloads and both
#: signs, denormals
_SPECIAL_WORDS = np.array(
    [0x80000000, 0x7f800000, 0xff800000, 0x7fc00000, 0x7fc00001,
     0xffc12345, 0x7f800001, 0xff8abcde, 0x7fffffff, 0x00000001,
     0x807fffff, 0x3f800000], np.uint32)


def _product(dtype, nframe, nchan):
    """(host data of bifrost dtype ``dtype``, how it reaches the
    device) for a product of ``(nframe, nchan)``."""
    import jax
    from bifrost_tpu.devrep import to_device_rep, ComplexPlanes
    from bifrost_tpu.dtype import DataType
    rng = np.random.RandomState(11)
    if dtype == 'f32':
        data = rng.randn(nframe, nchan).astype(np.float32)
    elif dtype == 'cf32':
        data = (rng.randn(nframe, nchan) +
                1j * rng.randn(nframe, nchan)).astype(np.complex64)
    elif dtype in ('cf32_special', 'cf32_planes'):
        # H2D recombines complex with arithmetic: these go as they
        # are, whole or as the two planes a block computed them in
        words = rng.choice(_SPECIAL_WORDS, (nframe, nchan, 2))
        data = words.view(np.complex64)[..., 0]
        if dtype == 'cf32_special':
            return data, jax.device_put
        return data, lambda gulp: ComplexPlanes(
            jax.device_put(np.ascontiguousarray(gulp.real)),
            jax.device_put(np.ascontiguousarray(gulp.imag)))
    elif dtype == 'cf64':
        return (rng.randn(nframe, nchan) +
                1j * rng.randn(nframe, nchan)), jax.device_put
    else:
        data = np.zeros((nframe, nchan), DataType('ci8').as_numpy_dtype())
        data['re'] = rng.randint(-100, 100, (nframe, nchan))
        data['im'] = rng.randint(-100, 100, (nframe, nchan))
    return data, lambda gulp: to_device_rep(gulp, dtype)


def _words(a):
    """The bytes of ``a``, for a comparison that tells NaNs apart."""
    return np.ascontiguousarray(a).view(np.uint8)


@pytest.mark.parametrize('dtype', ['f32', 'ci8', 'cf32', 'cf32_special',
                                   'cf32_planes', 'cf64'])
def test_large_product_crosses_in_pieces(dtype, monkeypatch):
    """A product over twice the piece size is cut on the device and
    lands piece by piece, bit for bit, in a span that wraps (ghost
    mirror included), whatever its dtype: a complex one as real
    (re, im) pairs that the host sees as complex again, with no
    conversion of its own (complex128 on the CPU backend alone),
    whether it reaches the engine as a complex array or as its two
    planes."""
    import jax
    from bifrost_tpu.telemetry import spans
    monkeypatch.setattr(xfer, '_D2H_PIECE_BYTES', 128)
    counters.reset()
    spans.reset()
    nframe, nchan = 24, 32
    eng = xfer.engine()
    from bifrost_tpu.ring import Ring
    ring = Ring(space='system')
    hdr = simple_header([-1, nchan], dtype.split('_')[0], gulp_nframe=8)
    fills = []
    with jax.enable_x64(dtype == 'cf64'):
        data, put = _product(dtype, nframe, nchan)
        with ring.begin_writing() as w:
            with w.begin_sequence(hdr, 8, 20) as seq:
                for g0 in (0, 8, 16):            # [16, 24) wraps at 20
                    with seq.reserve(8) as sp:
                        fill = eng.host_fill(
                            put(data[g0:g0 + 8]), hdr['_tensor']['dtype'],
                            sp.data.as_numpy())
                        sp.set_fill(fill)
                        sp.commit(8)
                        fills.append(fill)
                    if g0 == 0:
                        with ring.open_earliest_sequence(
                                guarantee=False) as rs:
                            with rs.acquire(0, 8) as span:
                                first = np.array(span.data.as_numpy())
                with ring.open_earliest_sequence(guarantee=False) as rs:
                    with rs.acquire(18, 4) as span:
                        got = np.array(span.data.as_numpy(), copy=True)
    assert first.dtype == got.dtype == data.dtype
    assert np.array_equal(_words(first), _words(data[:8]))
    assert np.array_equal(_words(got), _words(data[18:22]))
    # 8 frames of 128 (f32), 64 (ci8), 256 (cf32) or 512 (cf64) bytes in
    # pieces of at most 128: one frame a piece, or two (the ci8 gulp
    # crosses as its int16 words, one row of 256: 64 words a piece)
    assert all(isinstance(f.future, xfer._PieceFuture) for f in fills)
    assert fills[0].future._step == (64 if dtype == 'ci8' else 1)
    assert counters.get('xfer.d2h_piece_bytes') == \
        counters.get('xfer.d2h_bytes') == 3 * fills[0].nbytes
    # the complex ones as real pairs; the counter is there for a
    # reader whatever it counts
    assert counters.snapshot()['xfer.d2h_pair_bytes'] == \
        (3 * fills[0].nbytes if dtype.startswith('cf') else 0)
    assert counters.snapshot()['xfer.d2h_plane_bytes'] == \
        (3 * fills[0].nbytes if dtype == 'cf32_planes' else 0)
    assert 'd2h.convert' not in _span_names()


def _special_planes(shape, seed):
    """(re, im) host planes of float32 words arithmetic would not
    bring through, and the complex64 they stand for."""
    rng = np.random.RandomState(seed)
    re, im = (rng.choice(_SPECIAL_WORDS, shape).view(np.float32)
              for _ in range(2))
    both = np.empty(shape + (2,), np.float32)
    both[..., 0], both[..., 1] = re, im
    return re, im, both.view(np.complex64)[..., 0]


@pytest.mark.parametrize('rows', [False, True])
@pytest.mark.parametrize('shape,axis,step', [((10, 16), 0, 4),
                                             ((1, 7, 4, 2, 4, 2), 1, 2)])
def test_cut_from_planes_is_the_cut_of_the_complex_array(shape, axis,
                                                         step, rows):
    """Every piece cut from planes is, word for word, the piece cut
    from the complex64 array they stand for (NaN payloads, -0.0,
    infinities, denormals), the last short one too, with ``rows``
    either way; and it is the product's own bytes in their order."""
    import jax
    from bifrost_tpu.devrep import ComplexPlanes
    re, im, data = _special_planes(shape, seed=31)
    pair = ComplexPlanes(jax.device_put(re), jax.device_put(im))
    whole = jax.device_put(data)
    assert np.array_equal(_words(np.asarray(pair.joined())), _words(data))
    full, rest = divmod(shape[axis], step)
    cuts = [(0, step, full)] + ([(full * step, rest, 1)] if rest else [])
    lead = (slice(None),) * axis
    for start, n, count in cuts:
        mine = xfer._cut(pair, start, axis, n, count, rows)
        theirs = xfer._cut(whole, start, axis, n, count, rows)
        assert len(mine) == len(theirs) == count
        for j, (a, b) in enumerate(zip(mine, theirs)):
            assert a.dtype == b.dtype == np.uint32 and a.shape == b.shape
            assert np.array_equal(np.asarray(a), np.asarray(b))
            want = data[lead + (slice(start + j * n,
                                      start + (j + 1) * n),)]
            assert np.array_equal(_words(np.asarray(a)).ravel(),
                                  _words(want).ravel())


def test_cut_of_planes_names_no_complex_type():
    """The program that cuts planes has no complex type in it, which
    is what spares the chip the split of the whole product (the cut of
    the complex64 array names one: the control)."""
    import jax
    from bifrost_tpu.devrep import device_arrays
    re, im, data = _special_planes((1, 16, 4, 2, 4, 2), seed=3)
    planes = (jax.device_put(re), jax.device_put(im))
    xfer._cut(jax.device_put(data), 0, 1, 2, 2, True)    # builds _cut_fn
    text = xfer._cut_fn.lower(planes, 0, 1, 2, 2, True).as_text()
    assert 'complex' not in text and 'ui32' in text
    control = xfer._cut_fn.lower(
        device_arrays(jax.device_put(data)), 0, 1, 2, 2, True).as_text()
    assert 'complex' in control


def test_small_planes_cross_whole_as_complex64(monkeypatch):
    """Planes under the piece threshold, or asked for outside a ring
    fill, are joined and cross as the complex64 they stand for."""
    import jax
    from bifrost_tpu.devrep import ComplexPlanes, from_device_rep
    monkeypatch.setattr(xfer, '_D2H_PIECE_BYTES', 1 << 20)
    re, im, data = _special_planes((8, 16), seed=9)
    pair = ComplexPlanes(jax.device_put(re), jax.device_put(im))
    eng = xfer.engine()
    counters.reset()
    out = np.zeros_like(data)
    fill = eng.host_fill(pair, 'cf32', out)
    fill.wait()
    assert not isinstance(fill.future, xfer._PieceFuture)
    assert np.array_equal(_words(out), _words(data))
    assert counters.get('xfer.d2h_plane_bytes') == 0
    assert counters.get('xfer.d2h_bytes') == data.nbytes
    for got in (eng.to_host(pair), eng.to_host_async(pair).result(),
                from_device_rep(pair, 'cf32', np.zeros_like(data))):
        assert got.dtype == np.complex64
        assert np.array_equal(_words(got), _words(data))


@pytest.mark.parametrize('ctype', [np.float32, np.complex64])
def test_small_products_cross_whole_and_uneven_ones_in_pieces(
        ctype, monkeypatch):
    import jax
    from bifrost_tpu.telemetry import spans
    item = np.dtype(ctype).itemsize
    dtype = 'cf32' if item == 8 else 'f32'
    monkeypatch.setattr(xfer, '_D2H_PIECE_BYTES', 64 * item)
    eng = xfer.engine()

    def product(shape):
        real = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
        return real if item == 4 else (real - 1j * real).astype(ctype)
    for shape, pieces in (((4, 16), None),   # 64 items: under two pieces
                          ((7, 100), 7),     # a frame over a piece: one each
                          ((10, 16), 3)):    # four frames a piece: 4, 4, 2
        counters.reset()
        spans.reset()
        data = product(shape)
        out = np.zeros_like(data)
        fill = eng.host_fill(jax.device_put(data), dtype, out)
        fill.wait()
        assert isinstance(fill.future, xfer._PieceFuture) == bool(pieces)
        assert np.array_equal(out, data)
        assert counters.get('xfer.d2h_piece_bytes') == \
            (data.nbytes if pieces else 0)
        # a complex product under the threshold crosses as it is
        assert counters.get('xfer.d2h_pair_bytes') == \
            (data.nbytes if pieces and item == 8 else 0)
        if pieces:
            # one group: every piece was on its way before the first
            # was taken, and the last one is the remainder
            assert _span_names().count('d2h.fill') == \
                (1 if shape[0] % fill.future._step == 0 else 2)
    # and a future asked for outside a ring fill is one array
    big = product((64, 16))
    whole = []
    cross = xfer._cross

    def spy(arrays, *args):
        whole.extend(a.dtype for a in arrays)
        return cross(arrays, *args)
    monkeypatch.setattr(xfer, '_cross', spy)
    assert np.array_equal(eng.to_host_async(eng.to_device(big)).result(),
                          big)
    assert np.array_equal(eng.to_host(eng.to_device(big)), big)
    assert whole == [np.dtype(ctype)] * 2        # crossed as they are
    # as is the result of a piece future nobody landed, whose pieces
    # crossed as reals (a complex one's as the words of its reals)
    del whole[:]
    fut = eng._future_for(jax.device_put(big), np.zeros_like(big))
    assert isinstance(fut, xfer._PieceFuture)
    got = fut.result()
    assert got.dtype == big.dtype and np.array_equal(got, big)
    assert set(whole) == {np.dtype(np.uint32 if item == 8
                                   else np.float32)}


@pytest.mark.parametrize('shape,itemsize,want', [
    ((1024, 64, 1, 864), 1, (0, 256)),        # beamform-tab: 4 x 256
    ((16384, 4, 1024), 4, (0, 1024)),         # gpuspec: 16 x 1024
    ((1, 1024, 256, 2, 256, 2), 8, (1, 8)),   # xcorr: 128 x 8 channels
    ((1, 64, 4, 1 << 20), 4, (1, 1)),         # gpuspec-hsr: 64 x 1
    ((1000, 64, 1, 864), 1, (0, 250)),
    ((7, 6 << 20), 1, (0, 2)),                # 2, 2, 2, 1: no divisor
    ((512, 1024), 4, None),                   # 2 MiB crosses whole
], ids=['beamform_tab', 'gpuspec', 'xcorr', 'gpuspec_hsr', 'thousand',
        'seven_rows', 'small'])
def test_piece_plan_cuts_pieces_of_one_length(shape, itemsize, want):
    """As few pieces as fit 16 MiB each, evened out: where the axis
    divides, one program cuts them all (the rule of PR 27 left a
    1024-row u8 product a fourth piece of 136 rows, and a second cut
    program a gulp: PR 35); the served cells' plans are what they
    were."""
    class Product(object):
        class sharding(object):
            device_set = {0}
    arr = Product()
    arr.shape, arr.nbytes = shape, int(np.prod(shape)) * itemsize
    plan = xfer._piece_plan(arr)
    assert plan == want
    if plan is not None:
        axis, step = plan
        assert step * arr.nbytes // shape[axis] <= xfer._D2H_PIECE_BYTES
        old = max(xfer._D2H_PIECE_BYTES * shape[axis] // arr.nbytes, 1)
        assert -(-shape[axis] // step) == -(-shape[axis] // old)


#: a single-frame product of twelve channels of 512 bytes (complex64)
#: or 256 (float32): LARGE, and six groups of two pieces, once the
#: constants are what ``_small_constants`` makes them
_FRAME = (1, 12, 4, 2, 4, 2)


def _small_constants(monkeypatch, ahead=2, inflight=None):
    from bifrost_tpu import memory
    monkeypatch.setattr(xfer, '_D2H_GROUP', 2)
    monkeypatch.setattr(xfer, '_D2H_AHEAD', ahead)
    monkeypatch.setattr(memory, 'LARGE_SPAN_BYTES', 2048)
    if inflight is not None:
        monkeypatch.setattr(memory, 'INFLIGHT_BYTES', inflight)


def _frame_product(form, seed, monkeypatch):
    """``(host data, what reaches the engine, its bifrost dtype)`` of
    one ``_FRAME`` product: a complex64 array (``cf32``), the two
    planes of one (``planes``) or a float32 array (``f32``), with
    pieces of one channel."""
    import jax
    from bifrost_tpu.devrep import ComplexPlanes
    rng = np.random.RandomState(seed)
    monkeypatch.setattr(xfer, '_D2H_PIECE_BYTES',
                        256 if form == 'f32' else 512)
    if form == 'f32':
        data = rng.randn(*_FRAME).astype(np.float32)
        return data, jax.device_put(data), 'f32'
    data = (rng.randn(*_FRAME) + 1j * rng.randn(*_FRAME)) \
        .astype(np.complex64)
    if form == 'cf32':
        return data, jax.device_put(data), 'cf32'
    return data, ComplexPlanes(
        jax.device_put(np.ascontiguousarray(data.real)),
        jax.device_put(np.ascontiguousarray(data.imag))), 'cf32'


class _Schedule(object):
    """What the engine asks of the device, in order, through patched
    ``_cut``, ``_start_readback`` and ``_cross``: ``cuts`` (thread,
    first index), ``hints`` (groups whose readback has been started)
    and ``takes`` (groups whose crossing has begun); per take the cuts
    and hints issued by then, and the device pieces still alive."""

    def __init__(self, monkeypatch):
        import weakref
        self.cuts, self.hints, self.takes = [], 0, 0
        self.at_take, self.alive = [], []
        cut, cross = xfer._cut, xfer._cross
        start = xfer.TransferEngine._start_readback

        def counting_cut(arr, start_, *rest):
            pieces = cut(arr, start_, *rest)
            self.cuts.append((threading.current_thread().name,
                              int(start_)))
            self.alive.extend(weakref.ref(p) for p in pieces)
            return pieces

        def counting_start(arrays):
            self.hints += 1
            self.most_ahead = max(self.most_ahead,
                                  self.hints - self.takes)
            return start(arrays)

        def counting_cross(arrays, *rest):
            self.at_take.append((len(self.cuts), self.hints, self.live()))
            self.takes += 1
            return cross(arrays, *rest)
        self.most_ahead = 0
        monkeypatch.setattr(xfer, '_cut', counting_cut)
        monkeypatch.setattr(xfer, '_cross', counting_cross)
        monkeypatch.setattr(xfer.TransferEngine, '_start_readback',
                            staticmethod(counting_start))

    def live(self):
        gc.collect()
        return sum(1 for ref in self.alive if ref() is not None)


@pytest.mark.parametrize('form', ['cf32', 'planes', 'f32'])
@pytest.mark.parametrize('ahead', [1, 2, 3])
def test_single_frame_large_product_streams_in_groups(ahead, form,
                                                      monkeypatch):
    """A product whose leading axis is one frame (an integration of a
    correlator) is cut along the first axis that can be cut, and a
    LARGE one lands a group at a time with the readback of
    ``_D2H_AHEAD`` groups started beside the one being taken and
    never more, however it comes.  When it is CUT follows from what a
    cut costs: a complex64 array group by group, ``_D2H_AHEAD`` ahead
    (each cut program splits the whole of it first); its two planes,
    or an array of real words, all at once when the landing starts,
    and nothing before."""
    _small_constants(monkeypatch, ahead)
    data, product, dtype = _frame_product(form, 5, monkeypatch)
    whole = form != 'cf32'
    seen = _Schedule(monkeypatch)
    eng = xfer.engine()
    out = np.zeros_like(data)
    fut = eng._future_for(product, out)
    del product
    assert (fut._axis, fut._step, fut._group) == (1, 1, 2)
    assert fut._whole == whole
    # by the caller: the look-ahead's groups, or nothing yet
    assert len(seen.cuts) == len(fut._ahead) == seen.hints == \
        (0 if whole else ahead)
    assert not (whole and fut.ready())
    landed = []

    def put(group, last):
        landed.append((len(group), last))
        for host, where in group:
            assert host.dtype == data.dtype
            out[where] = host
    fut.land(put)
    assert np.array_equal(out, data)
    assert landed == [(2, False)] * 5 + [(2, True)]
    assert [start for _who, start in seen.cuts] == [0, 2, 4, 6, 8, 10]
    for k, (cuts, hints, _live) in enumerate(seen.at_take):
        # every cut before the first take, or the look-ahead's alone
        assert cuts == (6 if whole else min(k + 1 + ahead, 6))
        # the group being taken and the look-ahead are on their way
        assert hints == min(k + 1 + ahead, 6)
    assert seen.most_ahead == min(ahead + 1, 6)
    # a group's pieces go with it (on this backend once the host's
    # views of them have, with the ``put`` before): those still to
    # come remain, all of them or the look-ahead's
    assert [live for _c, _h, live in seen.at_take] == \
        [2 * ((6 - k) if whole else min(1 + ahead, 6 - k))
         for k in range(6)]
    del landed, put
    assert seen.live() == 0
    nbytes = data.nbytes
    assert counters.get('xfer.d2h_piece_bytes') == \
        counters.get('xfer.d2h_bytes') == nbytes
    assert counters.get('xfer.d2h_pair_bytes') == \
        (0 if form == 'f32' else nbytes)
    assert counters.snapshot()['xfer.d2h_plane_bytes'] == \
        (nbytes if form == 'planes' else 0)
    assert counters.snapshot()['xfer.d2h_cutup_bytes'] == \
        (nbytes if whole else 0)
    assert fut.done and fut._arrays == [] and not fut._ahead


def test_a_product_under_the_large_size_is_never_cut_up(monkeypatch):
    """What is counted as cut up is LARGE: a smaller product in pieces
    is one group, cut when the future is made, as before."""
    _small_constants(monkeypatch)
    from bifrost_tpu import memory
    monkeypatch.setattr(memory, 'LARGE_SPAN_BYTES', 1 << 20)
    data, product, dtype = _frame_product('planes', 6, monkeypatch)
    seen = _Schedule(monkeypatch)
    out = np.zeros_like(data)
    fill = xfer.engine().host_fill(product, dtype, out)
    assert not fill.future._whole and fill.future._group == 12
    assert [start for _who, start in seen.cuts] == [0]
    fill.wait()
    assert np.array_equal(out, data)
    assert counters.snapshot()['xfer.d2h_cutup_bytes'] == 0
    assert counters.get('xfer.d2h_plane_bytes') == data.nbytes


@pytest.mark.parametrize('form', ['planes', 'f32'])
def test_the_next_product_is_cut_up_before_the_last_one_is_announced(
        form, monkeypatch):
    """The order that keeps a producer's programs behind the cuts.  A
    product that finds nothing landing is cut up by ``host_fill``
    itself, before it returns; one that finds a landing under way
    (and waits for it: the byte bound) is cut up by the completion
    thread once the bytes of the one before have landed and BEFORE
    anybody who waits for them is told, so whatever the caller lets
    go by returning is dispatched behind all of the cuts."""
    _small_constants(monkeypatch, inflight=2048)
    seen = _Schedule(monkeypatch)
    eng = xfer.engine()
    gate, entered = threading.Event(), threading.Event()
    cross = xfer._cross

    def gated_cross(arrays, *rest):
        entered.set()
        assert gate.wait(SOON), 'the gate never opened'
        return cross(arrays, *rest)
    monkeypatch.setattr(xfer, '_cross', gated_cross)
    a, pa, dtype = _frame_product(form, 7, monkeypatch)
    b, pb, dtype = _frame_product(form, 8, monkeypatch)
    outs = [np.zeros_like(a), np.zeros_like(b)]
    fills = [eng.host_fill(pa, dtype, outs[0])]
    del pa
    # nothing was landing: cut up here, all of it, nothing taken yet
    assert seen.cuts == [('MainThread', s) for s in range(0, 12, 2)]
    assert entered.wait(SOON)            # the worker is at its first take
    order = []

    def second():
        fills.append(eng.host_fill(pb, dtype, outs[1]))
        order.append(('returned', len(seen.cuts)))
    t = threading.Thread(target=second)
    t.start()
    t.join(0.2)
    assert t.is_alive() and len(seen.cuts) == 6      # held up, uncut
    del pb
    gate.set()
    t.join(SOON)
    assert not t.is_alive()
    # every cut of the second product was issued by the completion
    # thread before the caller was let go
    assert order == [('returned', 12)]
    assert seen.cuts[6:] == [('xfer-d2h-0', s) for s in range(0, 12, 2)]
    for fill in fills:
        within(fill.wait)
    assert np.array_equal(outs[0], a) and np.array_equal(outs[1], b)
    assert counters.get('xfer.fills_by_worker') == 2
    assert counters.get('xfer.d2h_cutup_bytes') == a.nbytes + b.nbytes
    assert seen.live() == 0
    # the completion thread's cutting of the product it lands next is
    # a span of its own, and its rest before the first product another
    mine = {ev[0]: (t, ev) for t, ev in spans.events()
            if ev[0] in ('d2h.cut', 'd2h.idle')}
    thread, cut = mine['d2h.cut']
    assert thread == 'xfer-d2h-0' and cut[1] == 'xfer'
    assert cut[4] == {'bytes': outs[1].nbytes}
    assert mine['d2h.idle'][1][1] == 'wait'


def test_fault_in_the_middle_of_a_cut_up_product_poisons_the_ring(
        monkeypatch):
    """A transfer that fails at its third group, every group cut by
    then: the fill records it and poisons the ring, the groups before
    it landed once and none after, and no piece stays on the device."""
    from bifrost_tpu.ring import Ring
    from bifrost_tpu.testing import faults
    _small_constants(monkeypatch)
    data, product, dtype = _frame_product('planes', 9, monkeypatch)
    seen = _Schedule(monkeypatch)
    eng = xfer.engine()
    ring = Ring(space='system')
    hdr = simple_header([-1] + list(_FRAME[1:]), 'cf32', gulp_nframe=1)
    puts = []
    put = xfer.HostFill._put

    def counting_put(self, group, last):
        puts.extend(where for _host, where in group)
        return put(self, group, last)
    monkeypatch.setattr(xfer.HostFill, '_put', counting_put)
    with faults.injected('xfer.result', after=2, count=1):
        with ring.begin_writing() as w:
            with w.begin_sequence(hdr, 1, 3) as seq:
                with seq.reserve(1) as sp:
                    view = sp.data.as_numpy()
                    view[...] = 0
                    fill = eng.host_fill(product, dtype, view)
                    del product
                    sp.set_fill(fill)
                    sp.commit(1)
                assert fill._landed.wait(SOON)
                assert isinstance(fill.error, faults.FaultInjected)
                assert ring.poisoned
                landed = np.array(view)
    assert len(seen.cuts) == 6 and seen.takes == 3    # the third failed
    # groups 0 and 1 (channels 0-3) once each, nothing else
    assert [w[1] for w in puts] == [slice(k, k + 1) for k in range(4)]
    assert np.array_equal(landed[:, :4], data[:, :4])
    assert not landed[:, 4:].any()
    assert counters.get('xfer.fill_errors') == 1
    assert fill.future.done and not fill.future._ahead
    with pytest.raises(faults.FaultInjected):
        within(fill.wait)
    with pytest.raises(faults.FaultInjected):
        within(eng.drain)
    # the three groups behind the failed one are let go; its own two
    # pieces live as long as the error's traceback does
    assert seen.live() <= 2


def test_a_cut_that_fails_ahead_of_the_landing_is_reported_by_it(
        monkeypatch):
    """``HostFill.cut_up`` swallows a failing cut (it runs for a fill
    that is not the caller's business yet); the landing meets it
    again and fails the fill with it."""
    _small_constants(monkeypatch)
    data, product, dtype = _frame_product('f32', 10, monkeypatch)
    boom = RuntimeError('the cut failed')
    calls = []

    def failing_cut(*args):
        calls.append(threading.current_thread().name)
        raise boom
    monkeypatch.setattr(xfer, '_cut', failing_cut)
    out = np.zeros_like(data)
    fill = xfer.engine().host_fill(product, dtype, out)
    assert calls[0] == 'MainThread'          # tried, and kept quiet
    with pytest.raises(RuntimeError, match='the cut failed'):
        within(fill.wait)
    assert len(calls) == 2 and fill.error is boom
    assert not out.any()


def test_fills_in_flight_are_bounded_by_bytes(gated, monkeypatch):
    """The depth bound counts bytes as well as fills: the unfinished
    fills hold at most INFLIGHT_BYTES besides the newest, so a third
    product of 600 bytes retires the first, and one that is over the
    bound alone retires every fill before it."""
    from bifrost_tpu import memory
    monkeypatch.setattr(memory, 'INFLIGHT_BYTES', 1500)
    small = [np.full((150,), v, np.float32) for v in (1.0, 2.0, 3.0)]
    outs = [np.zeros_like(a) for a in small]
    fills = [gated.host_fill(a, 'f32', o)
             for a, o in zip(small[:2], outs[:2])]
    assert gated.outstanding == 2        # 1200 bytes: both in flight

    def third():
        fills.append(gated.host_fill(small[2], 'f32', outs[2]))
    t = threading.Thread(target=third)
    t.start()
    t.join(0.2)
    assert t.is_alive()                  # held up by the first fill
    gated.futures[0].gate.set()
    t.join(SOON)
    assert not t.is_alive() and fills[0].done and not fills[1].done
    assert np.array_equal(outs[0], small[0])
    big = np.zeros((500,), np.float32)   # 2000 bytes: over it alone
    for fut in gated.futures[1:3]:
        fut.gate.set()
    within(gated.host_fill, big, 'f32', np.zeros_like(big))
    assert all(f.done for f in fills)
    assert [len(gated._fills), gated._fills[0].nbytes] == [1, 2000]


def test_product_on_a_mesh_crosses_whole(monkeypatch):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if len(jax.devices()) < 2:
        pytest.skip('one device')
    monkeypatch.setattr(xfer, '_D2H_PIECE_BYTES', 256)
    mesh = Mesh(np.array(jax.devices()[:2]), ('t',))
    data = np.arange(64 * 16, dtype=np.float32).reshape(64, 16)
    arr = jax.device_put(data, NamedSharding(mesh, P('t')))
    out = np.zeros_like(data)
    fill = xfer.engine().host_fill(arr, 'f32', out)
    fill.wait()
    assert not isinstance(fill.future, xfer._PieceFuture)
    assert np.array_equal(out, data)


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------

def test_fused_chain_donation_bitexact_and_reported():
    """Acceptance: the donating fused chain reports donated inputs in
    its plan record and its output is bit-exact vs the non-donating
    path."""
    raw = _make_raw(seed=5)
    out_plain, fb_plain = _run_chain(raw, donate=False)
    assert 'donate_argnums' not in (fb_plain.impl_info or {})
    counters.reset()
    out_donate, fb_donate = _run_chain(raw, donate=True)
    assert (fb_donate.impl_info or {}).get('donate_argnums') == [0]
    assert counters.get('donation.hits') > 0
    assert np.array_equal(out_plain, out_donate)


@pytest.mark.parametrize('form', ['pairs', 'words'])
def test_donation_roundtrip_ci8_planes(form):
    """ci8 device-rep gulps (int8 re/im pairs, made from the words a
    gulp on one device is held as, or the int16 words themselves)
    survive a donating identity-ish computation bit-exactly."""
    from bifrost_tpu.devrep import (to_device_rep, from_device_rep,
                                    ComplexWords)
    from bifrost_tpu.ops.common import donating_jit
    raw = _make_raw(nt=16, nf=32, seed=9)
    chunk = to_device_rep(raw, 'ci8')
    assert isinstance(chunk, ComplexWords)
    ref = np.asarray(chunk).copy()
    dev = chunk.pairs() if form == 'pairs' else chunk.words
    one = dev.dtype.type(1)
    fn = donating_jit(lambda x: (x + one) - one, donate_argnums=(0,))
    out = fn(dev)
    assert dev.is_deleted()             # donated input is consumed
    assert chunk.is_deleted() == (form == 'words')
    if form == 'words':
        out = ComplexWords(out, raw.shape)
    assert np.array_equal(np.asarray(out), ref)
    back = np.zeros_like(raw)
    from_device_rep(out, 'ci8', back)
    assert np.array_equal(back, raw)


def test_donation_roundtrip_cf16_planes():
    """cf16 device-rep (complex64) round trip through a donating jit
    stays bit-exact."""
    from bifrost_tpu.devrep import to_device_rep, from_device_rep
    from bifrost_tpu.ops.common import donating_jit
    rng = np.random.RandomState(2)
    raw = np.zeros((16, 8), dtype=np.dtype([('re', 'f2'), ('im', 'f2')]))
    raw['re'] = rng.randn(16, 8).astype(np.float16)
    raw['im'] = rng.randn(16, 8).astype(np.float16)
    dev = to_device_rep(raw, 'cf16')
    ref = np.asarray(dev).copy()
    fn = donating_jit(lambda x: x * 1.0, donate_argnums=(0,))
    out = fn(dev)
    assert np.array_equal(np.asarray(out), ref)
    back = np.zeros_like(raw)
    from_device_rep(out, 'cf16', back)
    assert np.array_equal(back['re'], raw['re'])
    assert np.array_equal(back['im'], raw['im'])


def test_donation_denied_for_shared_chunks():
    """A ring chunk set WITHOUT owned=True (e.g. a source publishing a
    reused array) must never be taken for donation."""
    import jax.numpy as jnp
    from bifrost_tpu.ring import Ring
    ring = Ring(space='tpu')
    hdr = simple_header([-1, 4], 'f32', gulp_nframe=8)
    arr = jnp.ones((8, 4), jnp.float32)
    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 8, 24) as seq:
            with seq.reserve(8) as sp:
                sp.set(arr)             # owned defaults to False
                sp.commit(8)
            with ring.open_earliest_sequence(guarantee=True) as rs:
                with rs.acquire(0, 8) as ispan:
                    assert ispan.take_data() is None
                    assert np.array_equal(np.asarray(ispan.data),
                                          np.ones((8, 4), np.float32))


def test_donation_denied_with_second_reader():
    """Exclusivity: with two readers holding spans, take_data must
    refuse even owned chunks."""
    import jax.numpy as jnp
    from bifrost_tpu.ring import Ring
    ring = Ring(space='tpu')
    hdr = simple_header([-1, 4], 'f32', gulp_nframe=8)
    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, 8, 24) as seq:
            with seq.reserve(8) as sp:
                sp.set(jnp.ones((8, 4), jnp.float32), owned=True)
                sp.commit(8)
            with ring.open_earliest_sequence(guarantee=True) as r1, \
                    ring.open_earliest_sequence(guarantee=True) as r2:
                with r1.acquire(0, 8) as s1, r2.acquire(0, 8) as s2:
                    assert s1.take_data() is None
                    assert s2.take_data() is None


def test_stage_block_donation_bitexact():
    """Unfused _StageBlock chains donate too: outputs bit-exact vs the
    non-donating run."""
    from bifrost_tpu.stages import FftStage, DetectStage

    def run(donate):
        raw = _make_raw(seed=13)
        hdr = simple_header([-1, 2, 256], 'ci8',
                            labels=['time', 'pol', 'fine_time'])
        with bf.Pipeline(donate=donate) as p:
            src = NumpySourceBlock([raw.copy() for _ in range(4)], hdr,
                                   gulp_nframe=64)
            b = bf.blocks.copy(src, space='tpu')
            b = bf.blocks.fft(b, 'fine_time', axis_labels='freq')
            b = bf.blocks.detect(b, 'stokes', axis='pol')
            b = bf.blocks.copy(b, space='system')
            sink = GatherSink(b)
            p.run()
        return sink.result()

    out0 = run(False)
    counters.reset()
    out1 = run(True)
    assert counters.get('donation.hits') > 0
    assert np.array_equal(out0, out1)
