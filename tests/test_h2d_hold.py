"""H2D from the ring span (bifrost_tpu.xfer, "From the ring span" in
docs/transfer.md): a host gulp crosses from the read span it lies in,
and the span stays open until the transfer has consumed it.

The CPU backend aliases host memory, so it never takes this path; the
protocol is driven here through a stand-in for ``device_put`` that
copies (:class:`Link`): the device array gets the bytes at once, and
the "runtime" keeps the host array it was handed until the test says
the transfer has landed, which is when a real DMA would stop reading.
What it read must still be there then: a span released early is
overwritten by these tests' writers at once, and the link says so."""

import threading
import time
from collections import deque

import numpy as np
import pytest

import bifrost_tpu as bf
import bifrost_tpu.native as native_mod
from bifrost_tpu import xfer
from bifrost_tpu.ring import Ring, WouldBlock
from bifrost_tpu.telemetry import counters, histograms, spans
from bifrost_tpu.testing import faults
from tests.test_xfer_async import within
from tests.util import NumpySourceBlock, GatherSink, simple_header

#: seconds a thread of these tests is given to end
SOON = 20.0
NCHAN = 64
GULP = 8                                   # frames a gulp


@pytest.fixture(autouse=True)
def _reset():
    counters.reset()
    yield
    faults.clear()
    xfer.reset_engine()


@pytest.fixture(params=['native', 'python'])
def core(request, monkeypatch):
    """Both ring cores count a reader's second open span."""
    if request.param == 'python':
        monkeypatch.setattr(native_mod, '_lib', None)
        monkeypatch.setattr(native_mod, '_tried', True)
    elif not native_mod.available():
        pytest.skip('no native core')
    return request.param


class Link(object):
    """``device_put`` of a backend that copies, and its DMA."""

    def __init__(self):
        self.flying = deque()
        self.lock = threading.Lock()
        self.torn = []                     # transfers that read a torn gulp
        self.puts = 0

    def put(self, arr, device=None):
        import jax
        snap = np.array(arr)               # what the device will hold
        with self.lock:
            self.flying.append((arr, snap))
            self.puts += 1
        return jax.device_put(snap, device)

    def land(self, n=1):
        """The oldest ``n`` transfers complete: the runtime has read
        the host memory up to now, and lets go of it."""
        for _ in range(n):
            with self.lock:
                arr, snap = self.flying.popleft()
            if not np.array_equal(arr, snap):
                self.torn.append(self.puts)
            del arr

    def land_in(self, seconds, stop):
        """A thread's body: every transfer lands ``seconds`` after it
        was put, oldest first, until ``stop``."""
        while not stop.is_set() or self.flying:
            if self.flying:
                time.sleep(seconds)
                self.land()
            else:
                time.sleep(seconds / 4)


def engine(link, **kwargs):
    eng = xfer.TransferEngine(zero_copy=False, stage_min=0, **kwargs)
    eng._put = link.put
    return eng


def gulp(k):
    rng = np.random.RandomState(100 + k)
    return rng.randint(-2 ** 15, 2 ** 15, (GULP, NCHAN)).astype(np.int16)


def open_ring(depth, ngulp=0):
    """A host ring ``depth`` gulps deep with ``ngulp`` written, its
    writer and write sequence left open."""
    ring = Ring(space='system')
    hdr = simple_header([-1, NCHAN], 'i16', gulp_nframe=GULP)
    w = ring.begin_writing()
    w.__enter__()
    seq = w.begin_sequence(hdr, GULP, depth * GULP)
    seq.__enter__()
    for k in range(ngulp):
        write(seq, k)
    return ring, w, seq


def write(seq, k, nonblocking=False):
    with seq.reserve(GULP, nonblocking=nonblocking) as sp:
        sp.data.as_numpy()[...] = gulp(k)
        sp.commit(GULP)


def close_ring(w, seq):
    seq.__exit__(None, None, None)
    w.__exit__(None, None, None)


def ship(eng, rseq, k):
    """Gulp ``k`` of the reader's sequence to the device, as
    ``CopyBlock`` does it: its own span is released on return."""
    with rseq.acquire(k * GULP, GULP) as span:
        return eng.to_device(span.data.as_numpy(), span=span)


def held():
    return counters.gauges().get('xfer.h2d_spans_held')


def test_span_stays_open_until_the_transfer_lands(core):
    """Not released before the runtime lets go of the memory, and the
    writer waits for it meanwhile; the counters, the gauge, the span
    and its histogram say what happened."""
    link = Link()
    eng = engine(link)
    depth = 2
    hist = histograms.get_or_create('xfer.h2d_hold_s', unit='s')
    before = hist.count
    ring, w, seq = open_ring(depth, ngulp=depth)
    with ring.open_earliest_sequence(guarantee=True) as rseq:
        dev = ship(eng, rseq, 0)
        assert np.array_equal(np.asarray(dev), gulp(0))
        assert counters.get('xfer.h2d_direct') == 1
        assert counters.get('xfer.h2d_direct_bytes') == gulp(0).nbytes
        assert counters.get('xfer.h2d_bytes') == gulp(0).nbytes
        assert not counters.get('xfer.h2d_staged')
        assert not counters.get('xfer.h2d_unstaged')
        assert held() == 1
        # the block's own span is released; the writer still cannot
        # have gulp 0's place, now or after a look at the holds
        with pytest.raises(WouldBlock):
            write(seq, depth, nonblocking=True)
        eng._reap()
        assert held() == 1
        with pytest.raises(WouldBlock):
            write(seq, depth, nonblocking=True)
        link.land()
        within(eng.release_held, rseq)
        assert held() == 0
        write(seq, depth, nonblocking=True)
    assert hist.count == before + 1
    names = [ev[0] for _t, ev in spans.events()]
    assert 'h2d.hold' in names
    assert not link.torn
    close_ring(w, seq)


def test_two_spans_held_are_released_in_order(core, monkeypatch):
    """The transfer of gulp k+1 is issued while gulp k's is in flight:
    two spans held, and the first released first even where the
    second's transfer is seen complete before it."""
    link = Link()
    eng = engine(link)
    ring, w, seq = open_ring(4, ngulp=3)
    order = []
    release = xfer._Hold.release
    monkeypatch.setattr(
        xfer._Hold, 'release',
        lambda self: (order.append(self.span.frame_offset),
                      release(self))[1])
    with ring.open_earliest_sequence(guarantee=True) as rseq:
        ship(eng, rseq, 0)
        second = threading.Thread(target=ship, args=(eng, rseq, 1))
        second.start()                     # ships, then waits for gulp 0's
        deadline = time.time() + SOON
        while held() != 2 and time.time() < deadline:
            time.sleep(0.001)
        assert held() == 2 and link.puts == 2
        # gulp 1's transfer lands first: nothing is released yet
        with link.lock:
            first = link.flying.popleft()
        link.land()
        eng._reap()
        assert held() == 2 and order == []
        del first
        second.join(SOON)
        assert not second.is_alive()
        within(eng.release_held, rseq)
    assert order == [0, GULP] and held() == 0
    close_ring(w, seq)


def test_ring_two_spans_deep_makes_progress(core):
    """No deadlock at the least depth the path takes: the transfer
    before the newest is waited for before the block goes to wait for
    the next span, and every gulp arrives once, in order, whole."""
    link = Link()
    eng = engine(link)
    ring, w, seq = open_ring(2)
    ngulp = 24
    stop = threading.Event()
    lander = threading.Thread(target=link.land_in, args=(0.002, stop))
    lander.start()

    def source():
        for k in range(ngulp):
            write(seq, k)
        close_ring(w, seq)

    def read(rseq):
        got = []
        for span in rseq.read(GULP):
            if span.nframe:
                got.append(eng.to_device(span.data.as_numpy(),
                                         span=span))
        eng.release_held(rseq)
        return got

    src = threading.Thread(target=source)
    # the reader is there before the first gulp: nothing is lapped
    with ring.open_earliest_sequence(guarantee=True) as rseq:
        src.start()
        try:
            got = within(read, rseq)
        finally:
            stop.set()
            src.join(SOON)
            lander.join(SOON)
    assert len(got) == ngulp
    for k, dev in enumerate(got):
        assert np.array_equal(np.asarray(dev), gulp(k))
    assert counters.get('xfer.h2d_direct') == ngulp
    assert not link.torn and held() == 0


def test_a_put_that_raises_releases_the_span(core):
    """The fault point fires on this path as on the staged one; the
    second span is released at once (no transfer reads it) and the
    error goes to the caller, whose policy poisons as it always did."""
    link = Link()
    eng = engine(link)
    depth = 2
    ring, w, seq = open_ring(depth, ngulp=depth)
    with ring.open_earliest_sequence(guarantee=True) as rseq:
        with faults.injected('xfer.h2d', count=1):
            with pytest.raises(faults.FaultInjected):
                ship(eng, rseq, 0)
        assert link.puts == 0 and not held()
        assert not counters.get('xfer.h2d_direct')
        write(seq, depth, nonblocking=True)    # gulp 0's place is free
        ship(eng, rseq, 1)                     # and the path still works
        link.land()
        within(eng.release_held, rseq)
    close_ring(w, seq)


def test_sequence_end_and_shutdown_release_everything_held(core):
    """``release_held`` (the block's, before its reader moves on or
    closes) and a blocking ``drain`` (the engine's shutdown) both wait
    for every transfer and release every span."""
    link = Link()
    eng = engine(link)
    ring, w, seq = open_ring(4, ngulp=3)
    with ring.open_earliest_sequence(guarantee=True) as rseq:
        ship(eng, rseq, 0)
        assert held() == 1
        waiter = threading.Thread(target=eng.release_held, args=(rseq,))
        waiter.start()
        waiter.join(0.05)
        assert waiter.is_alive() and held() == 1    # it waits
        link.land()
        waiter.join(SOON)
        assert not waiter.is_alive() and held() == 0
        ship(eng, rseq, 1)
        drainer = threading.Thread(target=eng.drain, args=(True,))
        drainer.start()
        drainer.join(0.05)
        assert drainer.is_alive() and held() == 1
        link.land()
        drainer.join(SOON)
        assert not drainer.is_alive() and held() == 0
        # every span is back: the writer may lap the ring
        for k in range(3, 8):
            write(seq, k, nonblocking=k < 7)
            rseq.acquire((k - 3) * GULP, GULP).release()
    close_ring(w, seq)


def _copies():
    """Ships that went through a buffer of the engine's: a slot, or a
    fresh one (strict mode, a small gulp, a backend that aliases)."""
    return (counters.get('xfer.h2d_staged') or 0) + \
        (counters.get('xfer.h2d_unstaged') or 0)


def _staged(eng, arr, span):
    before = _copies()
    dev = eng.to_device(arr, span=span)
    assert np.array_equal(np.asarray(dev), np.asarray(arr))
    assert _copies() == before + 1
    assert not counters.get('xfer.h2d_direct') and not held()
    return dev


@pytest.mark.parametrize('why', [
    'unguaranteed', 'wrapped', 'strict', 'zero_copy', 'small',
    'one_span_deep', 'not_the_span', 'ringlets'])
def test_everything_else_is_staged(why, monkeypatch):
    """Each condition the engine can observe sends the gulp through a
    staging slot, as before: the caller may recycle the memory on
    return."""
    link = Link()
    eng = xfer.TransferEngine(zero_copy=why == 'zero_copy', stage_min=0)
    eng._put = link.put
    if why == 'strict':
        monkeypatch.setenv('BF_SYNC_STRICT', '1')
    if why == 'small':
        eng.stage_min = 1 << 20
    depth = 1 if why == 'one_span_deep' else 3
    ring = Ring(space='system')
    shape = [2, -1, NCHAN] if why == 'ringlets' else [-1, NCHAN]
    hdr = simple_header(shape, 'i16', gulp_nframe=GULP)
    with ring.begin_writing() as w:
        with w.begin_sequence(hdr, GULP, depth * GULP) as seq:
            def put(k):
                with seq.reserve(GULP) as sp:
                    sp.data.as_numpy()[...] = gulp(k).reshape(
                        sp.data.as_numpy().shape[-2:])
                    sp.commit(GULP)
            put(0)
            guarantee = why != 'unguaranteed'
            with ring.open_earliest_sequence(guarantee=guarantee) as rseq:
                if why == 'wrapped':
                    # follow the writer once round the ring, then take
                    # the gulp that straddles its end: it is read from
                    # the ghost region, a copy the core keeps
                    fb = NCHAN * 2
                    nframe = ring.total_span // fb
                    k = 0
                    while (k + 1) * GULP < nframe + GULP:
                        rseq.acquire(k * GULP, GULP).release()
                        k += 1
                        put(k)
                    with rseq.acquire(nframe - GULP // 2, GULP) as span:
                        assert span._begin % ring.total_span + \
                            span._nbyte > ring.total_span
                        _staged(eng, span.data.as_numpy(), span)
                    return
                with rseq.acquire(0, GULP) as span:
                    arr = span.data.as_numpy()
                    if why == 'one_span_deep':
                        assert ring.total_span < 2 * span._nbyte
                    if why == 'not_the_span':
                        arr = np.array(arr)        # the caller's own copy
                    if why == 'ringlets':
                        arr = np.ascontiguousarray(arr)
                    _staged(eng, arr, span)


def test_a_sharding_is_staged():
    """A gulp bound for a mesh goes the sharded way, span or no span."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip('one device')
    link = Link()
    eng = engine(link)
    ring, w, seq = open_ring(3, ngulp=1)
    sharding = NamedSharding(Mesh(np.array(devs[:2]), ('t',)), P('t'))
    with ring.open_earliest_sequence(guarantee=True) as rseq:
        with rseq.acquire(0, GULP) as span:
            dev = eng.to_device(span.data.as_numpy(), sharding=sharding,
                                span=span)
    assert np.array_equal(np.asarray(dev), gulp(0))
    assert _copies() == 2
    assert not counters.get('xfer.h2d_direct') and not held()
    close_ring(w, seq)


def _pipeline(link, gulps, hdr, monkeypatch, **scope):
    """source -> copy('tpu') -> copy('system') -> sink on the process
    engine, its ``device_put`` the stand-in's."""
    xfer.reset_engine()
    eng = xfer.TransferEngine(zero_copy=False, stage_min=0)
    eng._put = link.put
    monkeypatch.setattr(xfer, '_engine', eng)
    with bf.Pipeline(**scope) as p:
        src = NumpySourceBlock(gulps, hdr, gulp_nframe=GULP)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.copy(b, space='system')
        sink = GatherSink(b)
        p.run()
    return sink


@pytest.mark.parametrize('dtype', ['i16', 'ci8', 'f32'])
def test_pipeline_delivers_every_gulp_once_in_order(core, dtype,
                                                    monkeypatch):
    """A ``Pipeline`` whose H2D block ships from its input ring: the
    source overwrites every released span at once (a ring two or
    three spans deep, forty gulps), the transfers land late, and the
    sink has every gulp once, in order, byte for byte."""
    link = Link()
    rng = np.random.RandomState(7)
    ngulp = 40
    if dtype == 'ci8':
        raw = rng.randint(-64, 64, (ngulp, GULP, NCHAN, 2)).astype(np.int8)
        gulps = [g.view(np.dtype([('re', 'i1'), ('im', 'i1')]))[..., 0]
                 for g in raw]
    elif dtype == 'i16':
        gulps = list(rng.randint(-2 ** 15, 2 ** 15,
                                 (ngulp, GULP, NCHAN)).astype(np.int16))
    else:
        gulps = list(rng.randn(ngulp, GULP, NCHAN).astype(np.float32))
    hdr = simple_header([-1, NCHAN], dtype)
    stop = threading.Event()
    lander = threading.Thread(target=link.land_in, args=(0.003, stop))
    lander.start()
    try:
        sink = within(_pipeline, link, gulps, hdr, monkeypatch)
    finally:
        stop.set()
        lander.join(SOON)
    want = np.concatenate(gulps, axis=0)
    got = sink.result()
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    assert counters.get('xfer.h2d_direct') == ngulp
    assert counters.get('xfer.h2d_direct_bytes') == \
        counters.get('xfer.h2d_bytes') == want.nbytes
    assert not counters.get('xfer.h2d_staged')
    assert not link.torn and held() == 0


def test_pipeline_failure_in_the_put_poisons_as_the_staged_path_does(
        monkeypatch):
    """A ``device_put`` that raises in the middle of a stream: the
    block fails as it does on the staged path, nothing stays held and
    the pipeline ends."""
    link = Link()
    gulps = [gulp(k) for k in range(12)]
    hdr = simple_header([-1, NCHAN], 'i16')
    stop = threading.Event()
    lander = threading.Thread(target=link.land_in, args=(0.002, stop))
    lander.start()
    try:
        with faults.injected('xfer.h2d', after=3, count=1):
            with pytest.raises(Exception) as err:
                within(_pipeline, link, gulps, hdr, monkeypatch)
    finally:
        stop.set()
        lander.join(SOON)
    assert 'xfer.h2d' in str(err.value) or \
        isinstance(err.value, faults.FaultInjected) or \
        'poison' in str(err.value).lower()
    assert counters.get('xfer.h2d_direct') == 3
    assert not held() and not link.torn


def test_drop_oldest_sheds_round_a_held_span(core):
    """Under ``drop_oldest`` the writer sheds what the reader has not
    opened and waits for what a transfer still reads: the held span
    is never torn, and is released once the transfer lands."""
    link = Link()
    eng = engine(link)
    ring, w, seq = open_ring(3, ngulp=3)
    ring.set_overload_policy('drop_oldest')
    with ring.open_earliest_sequence(guarantee=True) as rseq:
        ship(eng, rseq, 0)
        # the writer wants gulp 0's place: it can shed nothing open
        t = threading.Thread(target=write, args=(seq, 3))
        t.start()
        t.join(0.05)
        assert t.is_alive()
        link.land()
        within(eng.release_held, rseq)
        t.join(SOON)
        assert not t.is_alive()
    assert not link.torn and held() == 0
    close_ring(w, seq)


def test_the_lease_outlives_every_view_of_it():
    """What makes the handle safe: the finalizer fires when the last
    array made from the lent memory dies, whatever views were made of
    it meanwhile, and not before."""
    mem = np.arange(4096, dtype=np.int16)

    class Span(object):
        sequence = None

        def release(self):
            pass

    hold = xfer._Hold(Span(), mem)
    arr = hold.lend()
    assert arr.ctypes.data == mem.ctypes.data and arr.base is not mem
    kept = [arr[16:], arr.view(np.int8), np.asarray(arr, np.int16)]
    del arr
    assert not hold.consumed()
    while kept:
        assert not hold.consumed()
        kept.pop()
    assert hold.consumed()
