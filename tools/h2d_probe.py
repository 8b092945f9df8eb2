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
(``tools/thread_cpu.py``'s reading of ``/proc/self/task``, in ticks of
10 ms: the mean over all ``REPS``), the layout the runtime gave the
device array, and whether the bytes read back are the gulp's.  One
JSON line on standard output, the line so far on standard error after
every form.  Shape names on the command line run those alone.

``beside`` on the command line adds, for each form, what the served
cells are bound by: a thread that copies the gulp into a second host
buffer as fast as it can (the benchmark's source writing its ring)
while the main thread copies it into a staging buffer and ships it,
one transfer in flight behind the one being staged (the H2D block),
for ``BESIDE_S`` seconds: milliseconds a copy of the source alone and
beside each form, and milliseconds a gulp of the shipper.

    chiprun -- python3 tools/h2d_probe.py
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

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from thread_cpu import family, threads        # noqa: E402

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
    before, cpu0 = threads(), _cpu_s()
    t0 = time.perf_counter()
    arr = jax.device_put(host)
    t_put = time.perf_counter() - t0
    arr.block_until_ready()
    wall = time.perf_counter() - t0
    cpu = _cpu_s() - cpu0
    by = {}
    for tid, (name, s1, _f) in threads().items():
        s0 = before.get(tid, (name, 0.0, 0))[1]
        if s1 - s0 > 0:
            by[family(name)] = by.get(family(name), 0.0) + s1 - s0
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


def main():
    dev = jax.devices()[0]
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
