#!/usr/bin/env python3
"""What the runtime's device-to-host readback does, with no pipeline.

Five readings on float32 products of the gpuspec cell's own shape,
``(16384, 4, 1024)`` (268 MB), each made by a jitted program and
``block_until_ready`` before any clock starts.  One JSON line on
standard output; imports ``jax`` and ``numpy`` only.

1. ``np.asarray(x)`` cold: seconds and GB/s.
2. ``x.copy_to_host_async()``, a second's sleep, then ``np.asarray(x)``:
   milliseconds mean the hint works and the bytes move in the
   background; the same as 1 means they move inside the call.  And
   the same with the hint issued while the device is still computing
   the product, which is when a pipeline's copy block issues it.
3. 1, 2 and 4 threads, each taking a distinct ready product at the same
   moment, with and without the hint issued first: wall time of all
   over wall time of one is the runtime's D2H concurrency.
4. Eight products hinted back to back, then taken in order: the
   runtime's sustained D2H rate with nothing else on the host.
5. 1 again for the same bytes as ``(16384, 4096)`` and as one flat
   axis: whether a second-minor dimension of 4 costs a padded or
   strided transfer.

And two beside them, for what a completion worker can buy: one
thread copies a taken product into a second host buffer (the ring
fill) while another takes the next product; and a closed loop of
products, four hinted ahead, taken and then filled on one thread or on
two, alone and while a third thread stages and ships one 268 MB int8
gulp to the device for every product taken (the cell's H2D bytes, as
one flat axis: in the cell's own shape, ``(16384, 2, 4096, 2)``, the
runtime's host-side copy of two gulps in flight passed the machine's
40 GiB in two calls of four; PERF.md section 6, PR 27).  The same
loop once more with each product split on the device into 16 pieces
of 16 MiB, each hinted and taken by itself: what the fresh pages of
a 268 MB landing buffer cost, in seconds and in CPU seconds a product
(``xfer._D2H_PIECE_BYTES``).

The complex case (PR 29; ``complex`` on the command line runs it
alone, ``float32`` the readings above alone, nothing both): one
2.147 GB complex64 product of the xcorr cell's own shape,
``(1, 1024, 256, 2, 256, 2)``, made from random bits so that every NaN
payload, denormal, infinity and -0.0 is in it, crosses into a touched
host buffer as the engine does it: cut on the device along its channel
axis into pieces of 16 MiB, eight to a program, each group hinted as
it is cut, one group ahead of the one being taken.  What differs is
the form in which a piece leaves the device:

(a) ``a_complex_rows``: complex64 rows ``(step, rest)``, as xfer.py
    cut them until PR 29; the host copies each into its place.
(b) float32 rows with re and im interleaved element by element on the
    device, seen as complex64 on the host (``h.view``) and copied into
    place.  ``b_pairs`` folds just enough trailing axes into a row to
    fill a lane; ``b_pairs_words`` is the same with the planes stacked
    as the uint32 words they are (xfer.py's cut since PR 29: libtpu's
    compiler joins two float arrays with a ``maximum``, which is
    arithmetic); ``b_pairs_step_rows`` is ``(step, 2 * rest)``.
(c) two float32 planes taken apart, interleaved by the host as it
    writes them into place (two strided stores): ``c_planes_rows`` as
    rows ``(step, rest)``, ``c_planes_lane_rows`` as lane-filling rows,
    ``c_planes_as_is`` in the product's own shape, with no program on
    the device but the slices.
(d) ``d_from_planes_words`` (PR 31): (b)'s ``b_pairs_words`` cut from
    a product that is held as two float32 planes and never was
    complex64 on the device, as a correlator's reaches xfer.py since
    PR 31: what is left of (b)'s cut once no program has a complex
    argument to split.

(b) ``b_pairs_words`` and (d) cross twice more with the product CUT
UP AT ONCE (``products_cut_up_at_once``, PR 32): all sixteen cut
programs dispatched before the first group is taken, the readback
hinted one group ahead as before; what xfer.py does with a LARGE
product of real words, and why it does not with a complex64 one
(there the sixteen whole-product splits then stand in front of the
first piece).  Form names after ``complex`` run those forms alone.

For each: ``cut_s_a_product`` (the sixteen cut programs of a product
with nothing else on the device and no transfer), and for each of
two products in turn the seconds inside ``block_until_ready``,
``np.asarray`` and the copy into place, the wall time, and
``asarray_gbps`` / ``wall_gbps``; ``exact`` says the host buffer holds
the product's bits, word for word.

The flat case (PR 38; ``flat`` on the command line, with ``beside``
once more next to a thread that copies a 268 MB buffer as fast as it
can, as the benchmark's source does; cell names after it run those
alone): a 16 MiB piece of each served cell's product in the form
``xfer._cut`` gave it until PR 38 (``gpuspec`` ``f32[1024,4,1024]``,
``gpuspec-hsr`` ``f32[1,4,1048576]``, ``beamform-tab`` ``u8[256,55296]``,
``xcorr`` the pair rows ``u32[4096,1024]``) and the same bytes as one
axis of uint32 words, ``u32[N]``.  Sixty-four ready pieces a reading,
taken one after the other by ``np.asarray`` on a thread that is not
the main one, each hinted before: all sixty-four at once, one at a
time, or sixteen ahead of the take (``FLAT_MODES``): wall ms a piece,
process CPU-s a GB,
and CPU-s a GB by thread family (``bifrost_tpu.telemetry.threadcpu``),
``futex-default-S`` among them: the runtime's pool that lays a tiled
device array out in the host's order.  Also written to
``chiprun_out/d2h_probe_flat.json``.

    chiprun -- python3 tools/d2h_probe.py
    chiprun -- python3 tools/d2h_probe.py flat beside
"""

import json
import os
import resource
import statistics
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SHAPE = (16384, 4, 1024)
NBYTES = 4 * int(np.prod(SHAPE))


def _maker(shape):
    n = int(np.prod(shape))
    return jax.jit(lambda s: (jnp.arange(n, dtype=jnp.float32)
                              * s).reshape(shape))


_made = [0]


def products(make, count, wait=True):
    """``count`` distinct ready products (a jax array caches its host
    value, so every reading takes fresh ones)."""
    out = []
    for _ in range(count):
        _made[0] += 1
        out.append(make(jnp.float32(_made[0])))
    if wait:
        jax.block_until_ready(out)
    return out


def timed(fn, *args):
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0


def take_together(xs):
    """Wall seconds for len(xs) threads to each ``np.asarray`` one
    product, released at the same moment."""
    gate = threading.Barrier(len(xs) + 1)
    done = []

    def take(x):
        gate.wait()
        np.asarray(x)
        done.append(time.perf_counter())

    threads = [threading.Thread(target=take, args=(x,)) for x in xs]
    for t in threads:
        t.start()
    gate.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    return max(done) - t0


PIECES = 16
_in_pieces = jax.jit(lambda x: tuple(jnp.split(x, PIECES, axis=0)))


def _cpu_s():
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


def loop_period(make, split, h2d, seconds=5.0, depth=4, pieces=False):
    """Seconds, and CPU seconds of the whole process, a product in a
    closed loop: ``depth`` products hinted ahead, the oldest taken
    (``np.asarray``) and then copied into a ring slot, on the taking
    thread or (``split``) on a second one; with ``h2d`` (a shape), a
    third thread stages and ships one int8 gulp of that shape for
    every product taken; with ``pieces`` every product is split on the
    device into ``PIECES`` along its leading axis and crosses piece by
    piece."""
    import queue
    ring = [np.zeros(SHAPE, np.float32) for _ in range(2)]
    rows = SHAPE[0] // PIECES

    def fill(slot, host):
        if pieces:
            for i, piece in enumerate(host):
                np.copyto(slot[i * rows:(i + 1) * rows], piece)
        else:
            np.copyto(slot, host)

    stop = threading.Event()
    fills = queue.Queue(maxsize=1)
    ships = threading.Semaphore(0)

    def filler():
        while True:
            item = fills.get()
            if item is None:
                return
            fill(ring[item[0] % 2], item[1])

    def shipper():
        src = np.ones(h2d, np.int8)
        stage = np.empty_like(src)
        held = []
        while True:
            ships.acquire()
            if stop.is_set():
                return
            np.copyto(stage, src)                  # the source's copy
            np.copyto(src, stage)                  # the staging copy
            held.append(jax.device_put(stage))
            if len(held) > 1:
                held.pop(0).block_until_ready()

    threads = [threading.Thread(target=filler)] if split else []
    if h2d:
        threads.append(threading.Thread(target=shipper))
    for t in threads:
        t.start()
    flight = []
    taken = 0
    t0 = t_first = time.perf_counter()
    cpu_first = _cpu_s()
    while True:
        x = products(make, 1, wait=False)[0]
        x = _in_pieces(x) if pieces else (x,)
        for piece in x:
            piece.copy_to_host_async()
        flight.append(x)
        if len(flight) <= depth:
            continue
        host = [np.asarray(piece) for piece in flight.pop(0)]
        if not pieces:
            host = host[0]
        if split:
            fills.put((taken, host))
        else:
            fill(ring[taken % 2], host)
        ships.release()
        taken += 1
        now = time.perf_counter()
        if taken == 4:
            t_first, cpu_first = now, _cpu_s()      # warmed up
        if now - t0 > seconds:
            break
    stop.set()
    fills.put(None)
    ships.release()
    for t in threads:
        t.join()
    cpu = _cpu_s() - cpu_first
    return [(now - t_first) / max(taken - 4, 1), cpu / max(taken - 4, 1)]


def gbps(seconds, nbytes=NBYTES):
    return nbytes / seconds / 1e9


CSHAPE = (1, 1024, 256, 2, 256, 2)          # xcorr's product, complex64
CSTEP, CGROUP, LANE = 8, 8, 128             # 16 MiB pieces, eight a program


def _lane_rows(shape):
    """The fewest trailing axes of ``shape`` that fill a lane."""
    tail = 1
    while tail < len(shape) and int(np.prod(shape[-tail:])) < LANE:
        tail += 1
    return tuple(shape[-tail:])


def _pairs(p, tail, words=False):
    re, im = p if isinstance(p, tuple) else (p.real, p.imag)
    planes = [q.reshape((-1,) + tail) for q in (re, im)]
    if words:
        planes = [jax.lax.bitcast_convert_type(q, jnp.uint32)
                  for q in planes]
    return jnp.stack(planes, -1).reshape(-1, 2 * int(np.prod(tail)))


def _interleave(host, place):
    """(re, im) host planes into a complex64 view: two strided
    stores."""
    pair = place.view(np.float32).reshape(-1, 2)
    pair[:, 0] = host[0].reshape(-1)
    pair[:, 1] = host[1].reshape(-1)


def _as_complex(host, place):
    place[...] = host[0].view(np.complex64).reshape(place.shape)


#: name -> (device arrays of one piece, how they land in their place)
COMPLEX_FORMS = {
    'a_complex_rows': (
        lambda p: [p.reshape(CSTEP, -1)],
        lambda host, place: np.copyto(place,
                                      host[0].reshape(place.shape))),
    'b_pairs': (
        lambda p: [_pairs(p, _lane_rows(p.shape))], _as_complex),
    'b_pairs_words': (
        lambda p: [_pairs(p, _lane_rows(p.shape), words=True)],
        _as_complex),
    'b_pairs_step_rows': (
        lambda p: [_pairs(p, (int(np.prod(p.shape)) // CSTEP,))],
        _as_complex),
    'c_planes_rows': (
        lambda p: [p.real.reshape(CSTEP, -1), p.imag.reshape(CSTEP, -1)],
        _interleave),
    'c_planes_lane_rows': (
        lambda p: [q.reshape(-1, int(np.prod(_lane_rows(p.shape))))
                   for q in (p.real, p.imag)], _interleave),
    'c_planes_as_is': (lambda p: [p.real, p.imag], _interleave),
    # its piece is the pair of plane slices (``_FROM_PLANES``)
    'd_from_planes_words': (
        lambda p: [_pairs(p, _lane_rows(p[0].shape), words=True)],
        _as_complex),
}
_FROM_PLANES = {'d_from_planes_words'}
_planes_of = jax.jit(lambda z: (z.real, z.imag))


def _complex_cut(form):
    """``x`` is the complex64 product, or the pair of its planes."""
    def cut(x, start):
        out = []
        for j in range(CGROUP):
            out += form(jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(
                    a, start + j * CSTEP, CSTEP, 1), x))
        return tuple(out)
    return jax.jit(cut)


def _cross_complex(x, cut, land, out, at_once=False):
    """One product into ``out`` as the engine does it, one group ahead
    (``at_once``: every group cut before the first is taken, the
    readback one group ahead); seconds by part."""
    took = {'ready_s': 0.0, 'asarray_s': 0.0, 'fill_s': 0.0}
    starts = list(range(0, CSHAPE[1], CSTEP * CGROUP))
    t0 = time.perf_counter()
    cut_up = {s: cut(x, s) for s in starts} if at_once else {}
    took['dispatch_s'] = time.perf_counter() - t0

    def hinted(start):
        group = cut_up.pop(start) if at_once else cut(x, start)
        for piece in group:
            piece.copy_to_host_async()
        return group

    ahead = hinted(starts[0])
    for k, start in enumerate(starts):
        group = ahead
        ahead = hinted(starts[k + 1]) if k + 1 < len(starts) else None
        t1 = time.perf_counter()
        jax.block_until_ready(group)
        t2 = time.perf_counter()
        host = [np.asarray(piece) for piece in group]
        t3 = time.perf_counter()
        each = len(host) // CGROUP
        for j in range(CGROUP):
            row = start + j * CSTEP
            land(host[j * each:(j + 1) * each], out[0, row:row + CSTEP])
        t4 = time.perf_counter()
        took['ready_s'] += t2 - t1
        took['asarray_s'] += t3 - t2
        took['fill_s'] += t4 - t3
    took['wall_s'] = time.perf_counter() - t0
    took['asarray_gbps'] = gbps(took['asarray_s'], out.nbytes)
    took['wall_gbps'] = gbps(took['wall_s'], out.nbytes)
    return took


_AT_ONCE = {'b_pairs_words', 'd_from_planes_words'}


def complex_case(only=()):
    """(a) to (d) of the module docstring (``only``: those forms
    alone); the line so far goes to standard error after every
    form."""
    out = {'shape': list(CSHAPE),
           'nbytes': 8 * int(np.prod(CSHAPE)), 'forms': {}}
    bits = jax.jit(lambda k: jax.random.bits(k, CSHAPE, jnp.uint32))
    fuse = jax.jit(lambda re, im: jax.lax.complex(
        jax.lax.bitcast_convert_type(re, jnp.float32),
        jax.lax.bitcast_convert_type(im, jnp.float32)))
    prods, wants = [], []
    for k in range(2):
        key = jax.random.PRNGKey(29 + k)
        re, im = bits(key), bits(jax.random.fold_in(key, 1))
        prods.append(fuse(re, im))
        want = np.empty(CSHAPE + (2,), np.uint32)
        want[..., 0] = np.asarray(re)
        want[..., 1] = np.asarray(im)
        wants.append(want)
        del re, im
    jax.block_until_ready(prods)
    host = np.zeros(CSHAPE, np.complex64)                # touched
    starts = range(0, CSHAPE[1], CSTEP * CGROUP)
    for name, (form, land) in COMPLEX_FORMS.items():
        if only and name not in only:
            continue
        cut = _complex_cut(form)
        # from planes: a third product's worth of HBM, one at a time
        given = _planes_of if name in _FROM_PLANES else (lambda z: z)
        x = jax.block_until_ready(given(prods[0]))
        jax.block_until_ready(cut(x, 0))                 # compile
        cuts = []
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready([cut(x, s) for s in starts])
            cuts.append(time.perf_counter() - t0)
        got = {'cut_s_a_product': cuts, 'products': []}
        for prod, want in zip(prods, wants):
            host[...] = 0
            del x
            x = jax.block_until_ready(given(prod))
            took = _cross_complex(x, cut, land, host)
            have = host.view(np.uint32).reshape(want.shape)
            took['exact'] = bool(np.array_equal(have, want))
            if not took['exact']:
                took['words_wrong'] = int((have != want).sum())
            got['products'].append(took)
            if name in _AT_ONCE:
                host[...] = 0
                took = _cross_complex(x, cut, land, host, at_once=True)
                took['exact'] = bool(np.array_equal(
                    host.view(np.uint32).reshape(want.shape), want))
                got.setdefault('products_cut_up_at_once', []).append(took)
        got['peak_hbm_gb_so_far'] = (
            jax.devices()[0].memory_stats() or {}).get(
            'peak_bytes_in_use', 0) / 1e9
        del x
        out['forms'][name] = got
        print(json.dumps({'complex': out}), file=sys.stderr, flush=True)
    return out


#: a 16 MiB piece of each served cell's product as ``xfer._cut`` left
#: it until PR 38 (name -> shape, dtype); ``xcorr`` is the (re, im)
#: pair rows ``xfer._pairs`` made of a (1, 8, 256, 2, 256, 2) slice
FLAT_PIECES = {'gpuspec': ((1024, 4, 1024), np.float32),
               'gpuspec-hsr': ((1, 4, 1048576), np.float32),
               'beamform-tab': ((256, 55296), np.uint8),
               'xcorr': ((4096, 1024), np.uint32)}
FLAT_COUNT, FLAT_REPS, FLAT_BESIDE = 64, 5, (16384, 4, 4096)


def _piece_maker(shape, dtype, flat):
    """A program that makes one piece from a seed: the same bytes in
    ``shape`` or, with ``flat``, as one axis of uint32 words."""
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize // 4

    def make(s):
        words = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761) + s
        if flat:
            return words
        return jax.lax.bitcast_convert_type(words, dtype).reshape(shape)
    return jax.jit(make)


def _layout(arr):
    try:
        return str(arr.format.layout)
    except Exception:
        try:
            return str(arr.format)
        except Exception as e:
            return 'unknown (%s)' % type(e).__name__


def _family_cpu(before, after):
    """CPU seconds by thread family between two ``threadcpu`` readings."""
    from bifrost_tpu.telemetry.threadcpu import family
    by = {}
    for tid, (name, named, s1, _q) in after['threads'].items():
        s0 = before['threads'].get(tid, (name, named, 0.0, None))[2]
        if s1 - s0 > 0:
            fam = family(name, named)
            by[fam] = by.get(fam, 0.0) + s1 - s0
    return by


def _take_flat(make, seed0, ahead):
    """``FLAT_COUNT`` ready pieces taken one after the other by
    ``np.asarray`` on a thread that is not the main one, each hinted
    ``ahead`` pieces before its take (``FLAT_COUNT``: all at once
    first): wall seconds from the first hint to the last take, process
    CPU seconds and CPU seconds by family over the same stretch."""
    from bifrost_tpu.telemetry.threadcpu import read
    xs = [make(jnp.uint32(seed0 + k)) for k in range(FLAT_COUNT)]
    jax.block_until_ready(xs)
    took = []

    def taker():
        for k, x in enumerate(xs):
            if k + ahead - 1 < len(xs) and k:
                xs[k + ahead - 1].copy_to_host_async()
            t0 = time.perf_counter()
            np.asarray(x)
            took.append(time.perf_counter() - t0)

    before, cpu0 = read(), _cpu_s()
    t0 = time.perf_counter()
    for x in xs[:ahead]:
        x.copy_to_host_async()
    th = threading.Thread(target=taker, name='probe-take')
    th.start()
    th.join()
    wall = time.perf_counter() - t0
    cpu, after = _cpu_s() - cpu0, read()
    return {'wall_s': wall, 'asarray_ms_median': 1e3 * sorted(took)[
                len(took) // 2],
            'cpu_s': cpu, 'cpu_s_by_family': _family_cpu(before, after)}


#: reading -> (pieces hinted ahead of the take, beside a source)
FLAT_MODES = {'alone': (FLAT_COUNT, False), 'beside': (FLAT_COUNT, True),
              'one_at_a_time': (1, False), 'sixteen_ahead': (16, False),
              'sixteen_ahead_beside': (16, True)}


def flat_case(only=(), with_beside=False):
    """A piece of each cell's form as cut until PR 38 and the same
    bytes as ``u32[N]``, ``FLAT_COUNT`` of each a reading: wall ms a
    piece, process CPU-s a GB, the ``futex-default-S`` family's CPU-s
    a GB (``threadcpu``: ticks of 10 ms on the benchmark's machine, so
    a GB a reading); with ``beside``, once more while a thread copies a
    268 MB buffer as fast as it can (the benchmark's source)."""
    out = {'count': FLAT_COUNT, 'reps': FLAT_REPS, 'pieces': {}}
    stop = threading.Event()
    src = np.ones(FLAT_BESIDE, np.float32)
    dst = np.zeros_like(src)
    copies = []

    def source():
        while not stop.is_set():
            t0 = time.perf_counter()
            np.copyto(dst, src)
            copies.append(time.perf_counter() - t0)

    modes = [m for m, (_a, side) in FLAT_MODES.items()
             if with_beside or not side]
    seed = 1
    for name, (shape, dtype) in FLAT_PIECES.items():
        if only and name not in only:
            continue
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        got = out['pieces'][name] = {'shape': list(shape),
                                     'dtype': np.dtype(dtype).name,
                                     'nbytes': nbytes}
        for form, flat in (('as_cut', False), ('flat', True)):
            make = _piece_maker(shape, dtype, flat)
            x = jax.block_until_ready(make(jnp.uint32(0)))
            want = np.asarray(x).reshape(-1).view(np.uint8)
            got[form] = {'device_shape': list(x.shape),
                         'device_dtype': str(x.dtype),
                         'layout': _layout(x)}
            del x
            for mode in modes:
                ahead, side = FLAT_MODES[mode]
                th = None
                if side:
                    stop.clear()
                    th = threading.Thread(target=source, name='probe-source')
                    th.start()
                    time.sleep(0.2)
                reps = []
                for _ in range(FLAT_REPS):
                    reps.append(_take_flat(make, seed, ahead))
                    seed += FLAT_COUNT
                if th is not None:
                    stop.set()
                    th.join()
                gb = FLAT_COUNT * nbytes / 1e9
                fams = {}
                for r in reps:
                    for k, v in r['cpu_s_by_family'].items():
                        fams[k] = fams.get(k, 0.0) + v / FLAT_REPS / gb
                got[form][mode] = {
                    'wall_ms_a_piece_median': 1e3 * statistics.median(
                        r['wall_s'] for r in reps) / FLAT_COUNT,
                    'asarray_ms_median': statistics.median(
                        r['asarray_ms_median'] for r in reps),
                    'cpu_s_a_gb_median': statistics.median(
                        r['cpu_s'] for r in reps) / gb,
                    'cpu_s_a_gb_by_family_mean': fams,
                    'futex_default_s_cpu_s_a_gb': fams.get(
                        'futex-default-S', 0.0)}
            # exact: the first piece again, as one axis or as cut
            y = make(jnp.uint32(0))
            got[form]['exact'] = bool(np.array_equal(
                np.asarray(y).reshape(-1).view(np.uint8), want))
            del y, want
            print(json.dumps({'flat': out}), file=sys.stderr, flush=True)
    if copies:
        out['source_ms_a_copy_median'] = 1e3 * statistics.median(copies)
    return out


def main():
    dev = jax.devices()[0]
    cases = sys.argv[1:] or ['float32', 'complex']
    out = {'device': {'platform': dev.platform,
                      'kind': dev.device_kind}}
    if 'flat' in cases:
        out['flat'] = flat_case([c for c in cases if c in FLAT_PIECES],
                                'beside' in cases)
        os.makedirs('chiprun_out', exist_ok=True)
        with open(os.path.join('chiprun_out', 'd2h_probe_flat.json'),
                  'w') as f:
            json.dump(out, f)
    if 'complex' in cases:
        out['complex'] = complex_case(
            [c for c in cases if c in COMPLEX_FORMS])
    if 'float32' in cases:
        float32_case(out)
    print(json.dumps(out))
    return 0


def float32_case(out):
    make = _maker(SHAPE)
    products(make, 1)                                    # compile
    out.update({'shape': list(SHAPE), 'nbytes': NBYTES})

    # 1: cold
    cold = [timed(np.asarray, x) for x in products(make, 3)]
    out['1_cold_s'] = cold
    out['1_cold_gbps'] = gbps(min(cold))

    # 1 again with a thread of pure Python beside it: the longest time
    # that thread went without the interpreter lock says whether the
    # take holds it while the bytes move
    gaps = []
    for x in products(make, 2):
        stop = threading.Event()
        worst = [0.0]

        def spin():
            last = time.perf_counter()
            while not stop.is_set():
                now = time.perf_counter()
                worst[0] = max(worst[0], now - last)
                last = now

        th = threading.Thread(target=spin)
        th.start()
        time.sleep(0.05)
        worst[0] = 0.0
        took = timed(np.asarray, x)
        stop.set()
        th.join()
        gaps.append([took, worst[0]])
    out['1_cold_s_and_longest_python_stall_s'] = gaps

    # 2: hinted, a second later
    hinted = []
    for x in products(make, 3):
        x.copy_to_host_async()
        time.sleep(1.0)
        hinted.append(timed(np.asarray, x))
    out['2_hinted_s'] = hinted

    # 2 again, hinted before the product is ready: a program of some
    # tens of milliseconds, the hint right behind its dispatch
    slow = jax.jit(lambda x: jax.lax.fori_loop(
        0, 100, lambda _i, y: y * 1.0001 + 1.0, x))
    base = products(make, 1)[0]
    jax.block_until_ready(slow(base))                    # compile
    unready = []
    for _ in range(3):
        x = slow(base)
        was_ready = x.is_ready()
        x.copy_to_host_async()
        time.sleep(1.0)
        unready.append([was_ready, timed(np.asarray, x)])
    out['2_hinted_unready_s'] = unready
    del base

    # 3: concurrent takes
    conc = {}
    for hint in (False, True):
        for n in (1, 2, 4):
            walls = []
            for _ in range(2):
                xs = products(make, n)
                if hint:
                    for x in xs:
                        x.copy_to_host_async()
                walls.append(take_together(xs))
            conc['%s_%d' % ('hint' if hint else 'cold', n)] = min(walls)
    out['3_wall_s'] = conc
    out['3_ratio_2_over_1'] = {k: conc[k + '_2'] / conc[k + '_1']
                               for k in ('cold', 'hint')}
    out['3_ratio_4_over_1'] = {k: conc[k + '_4'] / conc[k + '_1']
                               for k in ('cold', 'hint')}

    # 4: eight hinted back to back, taken in order
    xs = products(make, 8)
    t0 = time.perf_counter()
    for x in xs:
        x.copy_to_host_async()
    each = [timed(np.asarray, x) for x in xs]
    wall = time.perf_counter() - t0
    out['4_each_s'] = each
    out['4_sustained_gbps'] = gbps(wall, 8 * NBYTES)
    del xs

    # 5: the same bytes in other shapes
    shapes = {}
    for shape in ((16384, 4096), (int(np.prod(SHAPE)),)):
        mk = _maker(shape)
        products(mk, 1)
        shapes['x'.join(map(str, shape))] = min(
            timed(np.asarray, x) for x in products(mk, 3))
    out['5_cold_s_by_shape'] = shapes

    # beside them: the ring fill of one product under the take of the
    # next (what a completion worker overlaps)
    ring = np.empty(SHAPE, np.float32)
    ring[...] = 0                                        # pages in
    a, b = products(make, 2)
    host_a = np.asarray(a)
    fill_alone = timed(np.copyto, ring, host_a)
    t0 = time.perf_counter()
    th = threading.Thread(target=np.copyto, args=(ring, host_a))
    th.start()
    take_b = timed(np.asarray, b)
    th.join()
    out['fill_alone_s'] = fill_alone
    out['take_under_fill_s'] = take_b
    out['fill_and_take_wall_s'] = time.perf_counter() - t0

    out['loop_period_s_and_cpu_s'] = {}
    out['loop_peak_rss_gb'] = {}
    jax.block_until_ready(_in_pieces(products(make, 1)[0]))     # compile
    for name, h2d, pieces in (('alone', None, False),
                              ('h2d_flat', (NBYTES,), False),
                              ('alone_in_pieces', None, True)):
        for split in (False, True):
            key = '%s_%s' % ('two_threads' if split else 'one_thread',
                             name)
            out['loop_period_s_and_cpu_s'][key] = loop_period(
                make, split, h2d, pieces=pieces)
            out['loop_peak_rss_gb'][key] = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9
            # the line so far: a kill for memory must not lose it
            print(json.dumps(out), file=sys.stderr, flush=True)


if __name__ == '__main__':
    sys.exit(main())
