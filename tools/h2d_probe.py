#!/usr/bin/env python3
"""What the runtime's host-to-device transfer costs a ci8 gulp by the
form it is handed over in, with no pipeline (PERF.md section 6,
PR 34).

One gulp of each served cell, drawn once (int8 uniform in [-64, 64),
as the benchmark draws them): ``gpuspec`` (16384, 2, 4096) complex
samples, 268 MB; ``xcorr`` (512, 1024, 256, 2), 537 MB;
``gpuspec-hsr`` (1, 64, 2, 1048576), 268 MB.  The same bytes go to the
device, ``jax.device_put`` from a touched, 128-byte-aligned buffer,
one transfer at a time, as

(a) ``a_pairs``: int8 with a trailing (re, im) axis, the device
    representation until PR 34;
(b) ``b_words``: one int16 a complex sample (``buf.view(np.int16)``:
    little-endian, low byte re), with the gulp's own axes;
(c) ``c_words_rows``: those words with their leading axes collapsed
    to two dimensions, a row the fewest trailing axes that fill a
    lane of 128 (``gpuspec``: the rows the spectrometer's kernel
    reads; PR 34's first tree);
(d) ``d_words_flat``: the words as one axis, what
    ``devrep.to_device_rep`` ships since PR 34.

For each: seconds from the call to ``block_until_ready`` (the best
and the median of ``REPS``), the process's CPU seconds over the same
stretch (``getrusage``: microseconds), those seconds by thread family
(``bifrost_tpu.telemetry.threadcpu``'s reading of ``/proc/self/task``:
the scheduler's clock, nanoseconds, or ticks of 10 ms where the kernel
keeps no ``schedstat``; the mean over all ``REPS``), the layout the
runtime gave the device array, and whether the bytes read back are the
gulp's.  One JSON line on standard output, the line so far on standard
error after every form.  Shape names on the command line run those
alone.

``beside`` on the command line adds, for each form, what the served
cells are bound by: a thread that copies the gulp into a second host
buffer as fast as it can (the benchmark's source writing its ring)
while the main thread copies it into a staging buffer and ships it,
one transfer in flight behind the one being staged (the H2D block),
for ``BESIDE_S`` seconds: milliseconds a copy of the source alone and
beside each form, and milliseconds a gulp of the shipper.

``ring`` on the command line runs another probe in the forms' place
(PERF.md section 6, PR 36): the ``gpuspec`` gulp of words shipped by
the package's own engine (``bifrost_tpu.xfer.TransferEngine``) from a
read span of a host ``Ring`` in ``system`` space, four gulps deep, as
``CopyBlock`` ships it, (a) ``staged``: copied into a staging slot
first, the engine's path for memory the caller recycles, (b)
``direct``: from the span's own memory, the span held open until the
runtime has let go of it.  For each, one transfer at a time: seconds
from the call to ``block_until_ready`` and the process's CPU seconds
over them; for ``direct`` also when the runtime let go of the host
memory, counted from the call (``lease_s``: looked for every 0.2 ms
with ``collect_garbage``, the device array kept, and again with the
device array deleted at once as a donating reader deletes it), and
whether that is seen without such a call.  With ``beside``, both forms
next to a thread that copies a gulp as fast as it can (the benchmark's
source), the shipper one transfer behind the one it issues, for
``BESIDE_S`` seconds: milliseconds a gulp of the shipper, milliseconds
a copy of the source (alone, and beside each form), CPU seconds a
shipped gulp, and what the holds read.

    chiprun -- python3 tools/h2d_probe.py
    chiprun -- python3 tools/h2d_probe.py ring beside
"""

import json
import os
import resource
import statistics
import sys
import threading
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from bifrost_tpu.telemetry.threadcpu import family, read   # noqa: E402

GULPS = {'gpuspec': (16384, 2, 4096),
         'xcorr': (512, 1024, 256, 2),
         'gpuspec-hsr': (1, 64, 2, 1048576)}
LANE = 128
REPS = 12
BESIDE_S = 6.0


def rows(shape):
    """(rows, row length): a row is the fewest trailing axes of
    ``shape`` that fill a lane."""
    tail = 1
    while tail < len(shape) and int(np.prod(shape[-tail:])) < LANE:
        tail += 1
    n = int(np.prod(shape[-tail:]))
    return int(np.prod(shape)) // n, n


FORMS = {
    'a_pairs': lambda g: g.view(np.int8).reshape(g.shape + (2,)),
    'b_words': lambda g: g,
    'c_words_rows': lambda g: g.reshape(rows(g.shape)),
    'd_words_flat': lambda g: g.reshape(-1),
}


def aligned(shape, dtype):
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.zeros(nbytes + 128, np.uint8)
    off = (-raw.ctypes.data) % 128
    return raw[off:off + nbytes].view(dtype).reshape(shape)


def _cpu_s():
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


def _layout(arr):
    try:
        return str(arr.format.layout)
    except Exception:
        try:
            return str(arr.format)
        except Exception as e:
            return 'unknown (%s)' % type(e).__name__


def put_once(host):
    """One transfer: wall and CPU seconds to ready, CPU by thread."""
    before, cpu0 = read()['threads'], _cpu_s()
    t0 = time.perf_counter()
    arr = jax.device_put(host)
    t_put = time.perf_counter() - t0
    arr.block_until_ready()
    wall = time.perf_counter() - t0
    cpu = _cpu_s() - cpu0
    by = {}
    for tid, (name, named, s1, _q) in read()['threads'].items():
        s0 = before.get(tid, (name, named, 0.0, None))[2]
        if s1 - s0 > 0:
            fam = family(name, named)
            by[fam] = by.get(fam, 0.0) + s1 - s0
    return arr, {'wall_s': wall, 'put_returns_s': t_put, 'cpu_s': cpu,
                 'cpu_s_by_thread': by}


def beside(gulp, host):
    """The source's copy and the shipper's gulp, side by side."""
    ring = np.zeros_like(gulp)
    stage = np.zeros_like(host)
    stop = threading.Event()
    copies = []

    def source():
        while not stop.is_set():
            t0 = time.perf_counter()
            np.copyto(ring, gulp)
            copies.append(time.perf_counter() - t0)

    def ship(seconds):
        ships, held = [], None
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            t0 = time.perf_counter()
            np.copyto(stage, host)
            arr = jax.device_put(stage)
            if held is not None:
                held.block_until_ready()
            held = arr
            ships.append(time.perf_counter() - t0)
        held.block_until_ready()
        return ships

    th = threading.Thread(target=source, name='probe-source')
    cpu0 = _cpu_s()
    th.start()
    ships = ship(BESIDE_S)
    stop.set()
    th.join()
    cpu = _cpu_s() - cpu0
    return {'source_ms_a_copy_median': 1e3 * statistics.median(copies),
            'source_ms_a_copy_mean': 1e3 * statistics.fmean(copies),
            'ship_ms_a_gulp_median': 1e3 * statistics.median(ships),
            'cpu_s_a_shipped_gulp': cpu / len(ships),
            'copies': len(copies), 'ships': len(ships)}


def source_alone(gulp):
    ring = np.zeros_like(gulp)
    took = []
    for _ in range(40):
        t0 = time.perf_counter()
        np.copyto(ring, gulp)
        took.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(took)


def ring_probe(with_beside):
    """The ``gpuspec`` gulp from a host ring's span, staged and
    direct, through the package's engine."""
    from bifrost_tpu import xfer
    from bifrost_tpu.ring import Ring
    from bifrost_tpu.telemetry import counters, histograms
    from bifrost_tpu.words import host_view

    shape = GULPS['gpuspec']
    nframe = shape[0]
    rng = np.random.default_rng(36)
    gulp = aligned(shape, np.int16)
    gulp.view(np.int8)[...] = rng.integers(
        -64, 64, gulp.view(np.int8).shape, dtype=np.int8)
    flat = gulp.reshape(-1)
    hdr = {'name': 'probe', 'time_tag': 0, 'gulp_nframe': nframe,
           '_tensor': {'shape': [-1] + list(shape[1:]), 'dtype': 'ci8',
                       'labels': ['time', 'pol', 'fine_time'],
                       'scales': [[0, 1]] * 3, 'units': [None] * 3}}
    depth = 4
    ring = Ring(space='system')
    writer = ring.begin_writing()
    writer.__enter__()
    wseq = writer.begin_sequence(hdr, nframe, depth * nframe)
    wseq.__enter__()
    for _ in range(depth):
        with wseq.reserve(nframe) as sp:
            host_view(sp.data.as_numpy())[...] = flat
            sp.commit(nframe)
    rseq = ring.open_earliest_sequence(guarantee=True)
    eng = xfer.TransferEngine()
    out = {'ring': type(ring).__name__, 'depth': depth,
           'total_span': int(ring.total_span), 'nbytes': int(gulp.nbytes),
           'zero_copy_backend': bool(eng._is_zero_copy())}

    def words_of(span):
        return host_view(span.data.as_numpy())

    def ship(k, direct):
        """(device array, seconds until to_device returned)."""
        with rseq.acquire((k % depth) * nframe, nframe) as span:
            w = words_of(span)
            if k < depth:
                out.setdefault('span_address_mod_4096', []).append(
                    int(w.ctypes.data % 4096))
            t0 = time.perf_counter()
            arr = eng.to_device(w, span=span if direct else None)
            return arr, time.perf_counter() - t0

    def lease(k, delete):
        """Seconds from the call until the runtime let go of the host
        memory, and until the array was ready."""
        with rseq.acquire((k % depth) * nframe, nframe) as span:
            hold = xfer._Hold(rseq.acquire((k % depth) * nframe, nframe),
                              words_of(span))
            t0 = time.perf_counter()
            arr = jax.device_put(hold.lend())
            t_put = time.perf_counter() - t0
            if delete:
                arr.delete()
            t_gone = None
            while t_gone is None and time.perf_counter() - t0 < 5.0:
                xfer._collect_runtime_garbage()
                if hold.consumed():
                    t_gone = time.perf_counter() - t0
                else:
                    time.sleep(2e-4)
            t_ready = None
            if not delete:
                arr.block_until_ready()
                t_ready = time.perf_counter() - t0
            hold.release()
            return {'put_returns_s': t_put, 'lease_s': t_gone,
                    'ready_s': t_ready}

    for name, delete in (('lease_array_kept', False),
                         ('lease_array_deleted', True)):
        got = [lease(k, delete) for k in range(REPS)]
        out[name] = {
            key: (statistics.median(g[key] for g in got)
                  if all(g[key] is not None for g in got) else None)
            for key in ('put_returns_s', 'lease_s', 'ready_s')}
        out[name]['lease_s_all'] = [g['lease_s'] for g in got]
    # is the runtime's letting go seen with no call into jaxlib?
    with rseq.acquire(0, nframe) as span:
        hold = xfer._Hold(rseq.acquire(0, nframe), words_of(span))
        arr = jax.device_put(hold.lend())
        arr.block_until_ready()
        time.sleep(0.2)
        seen_alone = hold.consumed()
        xfer._collect_runtime_garbage()
        out['lease_seen_without_a_call'] = bool(seen_alone)
        out['lease_seen_after_collect_garbage'] = bool(hold.consumed())
        hold.release()
        del arr
    print(json.dumps(out), file=sys.stderr, flush=True)

    # a runtime that keeps the host array for as long as the device
    # array lives never gives a held span back: the engine's direct
    # path would wait for ever, so it is not entered
    forms = [('staged', False)]
    if out['lease_array_kept']['lease_s'] is not None:
        forms.append(('direct', True))
    else:
        out['direct'] = 'not run: the lease outlives the transfer'

    for form, direct in forms:
        counters.reset()
        takes = []
        for k in range(REPS + 1):
            cpu0 = _cpu_s()
            t0 = time.perf_counter()
            arr, t_call = ship(k, direct)
            arr.block_until_ready()
            wall = time.perf_counter() - t0
            cpu = _cpu_s() - cpu0
            eng.release_held(rseq)
            if k:                                   # the first warms
                takes.append((wall, cpu, t_call))
        exact = bool(np.array_equal(np.asarray(arr), flat))
        del arr
        out[form] = {
            'wall_s_best': min(t[0] for t in takes),
            'wall_s_median': statistics.median(t[0] for t in takes),
            'cpu_s_median': statistics.median(t[1] for t in takes),
            'to_device_returns_s_median': statistics.median(
                t[2] for t in takes),
            'gbps_best': gulp.nbytes / min(t[0] for t in takes) / 1e9,
            'exact': exact,
            'counters': {k: v for k, v in counters.snapshot().items()
                         if k.startswith('xfer.h2d')}}
        print(json.dumps(out), file=sys.stderr, flush=True)

    if with_beside:
        out['source_alone_ms_a_copy'] = source_alone(gulp)
        for form, direct in forms:
            counters.reset()
            hist = histograms.get_or_create('xfer.h2d_hold_s', unit='s')
            wait = histograms.get_or_create('xfer.h2d_hold_wait_s',
                                            unit='s')
            h0, w0 = (hist.count, hist.total), (wait.count, wait.total)
            target = np.zeros_like(gulp)
            stop = threading.Event()
            copies = []

            def source():
                while not stop.is_set():
                    t0 = time.perf_counter()
                    np.copyto(target, gulp)
                    copies.append(time.perf_counter() - t0)

            th = threading.Thread(target=source, name='probe-source')
            cpu0 = _cpu_s()
            th.start()
            ships, behind, k = [], None, 0
            end = time.perf_counter() + BESIDE_S
            while time.perf_counter() < end:
                t0 = time.perf_counter()
                arr, _t = ship(k, direct)
                if behind is not None:
                    behind.block_until_ready()
                    # as every block does once a gulp: the pool takes
                    # its slot back while the array still lives (one
                    # that died unseen costs the slot, and 0.3 s of
                    # first touches for its replacement)
                    eng.drain()
                behind = arr
                ships.append(time.perf_counter() - t0)
                k += 1
            behind.block_until_ready()
            eng.release_held(rseq)
            stop.set()
            th.join()
            cpu = _cpu_s() - cpu0
            del behind, arr
            out[form]['beside'] = {
                'source_ms_a_copy_median':
                    1e3 * statistics.median(copies),
                'source_ms_a_copy_mean': 1e3 * statistics.fmean(copies),
                'ship_ms_a_gulp_median': 1e3 * statistics.median(ships),
                'ship_ms_a_gulp_mean': 1e3 * statistics.fmean(ships),
                'cpu_s_a_shipped_gulp': cpu / len(ships),
                'copies': len(copies), 'ships': len(ships),
                'holds': hist.count - h0[0],
                'hold_ms_mean': 1e3 * (hist.total - h0[1]) /
                    max(hist.count - h0[0], 1),
                'hold_wait_ms_a_gulp': 1e3 * (wait.total - w0[1]) /
                    len(ships),
                'counters': {k: v for k, v in counters.snapshot().items()
                             if k.startswith('xfer.h2d')}}
            print(json.dumps(out), file=sys.stderr, flush=True)
    rseq.close()
    wseq.__exit__(None, None, None)
    writer.__exit__(None, None, None)
    return out


def main():
    dev = jax.devices()[0]
    if 'ring' in sys.argv[1:]:
        import faulthandler
        faulthandler.dump_traceback_later(700, exit=True)
        out = {'device': {'platform': dev.platform,
                          'kind': dev.device_kind}, 'reps': REPS,
               'ring_probe': ring_probe('beside' in sys.argv[1:])}
        os.makedirs('chiprun_out', exist_ok=True)
        with open(os.path.join('chiprun_out', 'h2d_probe_ring.json'),
                  'w') as f:
            json.dump(out, f)
        print(json.dumps(out))
        return 0
    only = [a for a in sys.argv[1:] if a in GULPS]
    with_beside = 'beside' in sys.argv[1:]
    out = {'device': {'platform': dev.platform, 'kind': dev.device_kind},
           'reps': REPS, 'gulps': {}}
    rng = np.random.default_rng(34)
    for name, shape in GULPS.items():
        if only and name not in only:
            continue
        gulp = aligned(shape, np.int16)
        gulp.view(np.int8)[...] = rng.integers(
            -64, 64, gulp.view(np.int8).shape, dtype=np.int8)
        got = out['gulps'][name] = {
            'shape': list(shape), 'nbytes': int(gulp.nbytes), 'forms': {}}
        if with_beside:
            got['source_alone_ms_a_copy'] = source_alone(gulp)
        for form, view in FORMS.items():
            host = view(gulp)
            arr, _first = put_once(host)            # pages, pools: warm
            takes = []
            for _ in range(REPS):
                del arr
                arr, took = put_once(host)
                takes.append(took)
            back = np.asarray(arr)
            exact = bool(np.array_equal(
                back.reshape(-1).view(np.int16), gulp.reshape(-1)))
            by = {}
            for t in takes:
                for k, v in t['cpu_s_by_thread'].items():
                    by[k] = by.get(k, 0.0) + v / REPS
            got['forms'][form] = {
                'shape': list(host.shape), 'dtype': str(host.dtype),
                'layout': _layout(arr),
                'wall_s_best': min(t['wall_s'] for t in takes),
                'wall_s_median': statistics.median(
                    t['wall_s'] for t in takes),
                'put_returns_s_median': statistics.median(
                    t['put_returns_s'] for t in takes),
                'cpu_s_median': statistics.median(
                    t['cpu_s'] for t in takes),
                'cpu_s_mean': statistics.fmean(
                    t['cpu_s'] for t in takes),
                'cpu_s_mean_by_thread': by,
                'gbps_best': gulp.nbytes / min(
                    t['wall_s'] for t in takes) / 1e9,
                'exact': exact}
            del arr, back
            if with_beside:
                got['forms'][form]['beside'] = beside(gulp, host)
            print(json.dumps(out), file=sys.stderr, flush=True)
        del gulp
    os.makedirs('chiprun_out', exist_ok=True)
    with open(os.path.join('chiprun_out', 'h2d_probe.json'), 'w') as f:
        json.dump(out, f)
    print(json.dumps(out))
    return 0


if __name__ == '__main__':
    sys.exit(main())
