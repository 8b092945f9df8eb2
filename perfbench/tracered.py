"""From the profiler's trace to numbers: the one reduction every PR's
per-layer metrics go through.

``load(path)`` reads an ``.xplane.pb`` with nothing but JAX and returns
the plain form the rest of this module works on (and the self-check's
recorded fixture is stored in):

    {plane name: {line name: [[event name, start_ns, duration_ns], ...]}}

The run traces with the host tracer OFF: on the v5e the runtime's
host-side layout conversion emits a 'Transpose' event per tile, fifteen
million in a five-second window of 268 MB gulps (a 437 MB trace, 65 s
to stop, the host path six times slower: my chip run, PR 25).  So the
trace holds device planes only, and host time is tied to trace time by
``bench_anchor``: a one-element program the bench launches and waits
for, stamping the host clock when the wait returns.  ``clock(trace,
anchors)`` gives the map; ``reduce(trace, window, spans)`` clips
everything to the window and returns busy time, the operations by
time, the programs launched and how much of the idle gaps the bench's
own host spans cover.  The bench's own device programs (``OWN``: the
anchor, and ``bench_take``, with which a device sink samples a
product) and every operation inside them count as neither programs
nor busy time: they are the yardstick's, not the system's.
"""

import re

import numpy as np

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
ANCHOR = 'bench_anchor'
OWN = (ANCHOR, 'bench_take')        # the bench's own device programs


def short_name(name):
    """The trace names a device operation by its whole HLO line
    (``%fusion.3 = f32[...] fusion(...)``): keep what is left of the
    equals sign."""
    return name.split(' = ', 1)[0].lstrip('%')[:64]


def wanted(plane, line):
    """The lines ``reduce`` reads."""
    return bool(DEVICE_PLANE.match(plane)) and \
        line in (OPS_LINE, MODULES_LINE)


def load(path, keep=None):
    """The plain form of one ``.xplane.pb``.  ``keep(plane, line)``
    may drop lines nobody reads (host threads with millions of
    events)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            if keep is not None and not keep(plane.name, line.name):
                continue
            evs = [[short_name(e.name), float(e.start_ns),
                    float(e.duration_ns)] for e in line.events]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        if lines:
            out[plane.name] = lines
    return out


def _intervals(events, lo, hi):
    """(n, 2) array of [start, end) clipped to [lo, hi], sorted."""
    rows = [(s, s + d) for _, s, d in events if s + d > lo and s < hi]
    if not rows:
        return np.zeros((0, 2))
    arr = np.array(sorted(rows), dtype=np.float64)
    return np.clip(arr, lo, hi)


def union(iv):
    """Merge overlapping intervals of a sorted (n, 2) array."""
    if len(iv) == 0:
        return iv
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return np.array(out)


def covered(iv):
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def _coverage_fn(iv):
    """F(t): length of the (disjoint, sorted) intervals below t."""
    if len(iv) == 0:
        return lambda t: np.zeros_like(np.asarray(t, dtype=np.float64))
    starts, ends = iv[:, 0], iv[:, 1]
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def f(t):
        t = np.asarray(t, dtype=np.float64)
        i = np.searchsorted(starts, t, side='right')
        j = np.maximum(i - 1, 0)
        part = np.maximum(np.minimum(t, ends[j]) - starts[j], 0.0)
        return np.where(i > 0, cum[j] + part, 0.0)
    return f


def gaps(busy, lo, hi):
    """The complement of the disjoint busy intervals within [lo, hi]."""
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def attribute(gap_iv, spans):
    """Seconds of the idle gaps that each host span covers; ``spans``
    is {name: disjoint intervals}.  ``unattributed`` is the part no
    span covers.  Spans of different threads may overlap, and the
    overlap counts for both."""
    idle = covered(gap_iv)
    if idle <= 0:
        return {}
    out = {}
    for name, iv in spans.items():
        f = _coverage_fn(iv)
        sec = float(np.sum(f(gap_iv[:, 1]) - f(gap_iv[:, 0])))
        if sec > 0:
            out[name] = sec * 1e-9
    every = [iv for iv in spans.values() if len(iv)]
    f = _coverage_fn(union(np.array(sorted(map(tuple, np.concatenate(every)))))
                     if every else np.zeros((0, 2)))
    rest = idle - float(np.sum(f(gap_iv[:, 1]) - f(gap_iv[:, 0])))
    if rest > 0:
        out['unattributed'] = rest * 1e-9
    return out


def own(name):
    return any(o in name for o in OWN)


def without_own(lines):
    """(modules, ops) of one device plane with the bench's own
    programs, and the operations that start inside one, left out."""
    mods = lines.get(MODULES_LINE, [])
    ops = lines.get(OPS_LINE, [])
    mine = sorted((s, s + d) for name, s, d in mods if own(name))
    if mine:
        starts = np.array([m[0] for m in mine])
        ends = np.array([m[1] for m in mine])

        def inside(t):
            i = np.searchsorted(starts, t, side='right') - 1
            return i >= 0 and t < ends[i]
        ops = [e for e in ops if not inside(e[1])]
    return [m for m in mods if not own(m[0])], ops


def device_planes(trace):
    return sorted(p for p in trace if DEVICE_PLANE.match(p))


def clock(trace, host_stamps):
    """(to_ns, residual_s): the map from the host's clock (seconds) to
    trace time (ns), from the first anchor program's end and the host
    stamp taken when the wait for it returned; ``residual_s`` is how
    far the last anchor lies from where the map puts it.  None where
    the trace holds no anchor."""
    ends = []
    for plane in device_planes(trace):
        ends = sorted(s + d for name, s, d in
                      trace[plane].get(MODULES_LINE, []) if ANCHOR in name)
        if ends:
            break
    if not ends or not host_stamps:
        return None
    t0, ns0 = host_stamps[0], ends[0]

    def to_ns(t):
        return ns0 + (np.asarray(t, dtype=np.float64) - t0) * 1e9
    residual = 0.0
    if len(ends) > 1 and len(host_stamps) > 1:
        residual = float(to_ns(host_stamps[-1]) - ends[-1]) * 1e-9
    return to_ns, residual


def reduce(trace, window, spans):
    """The numbers, or None where the trace holds no device operation
    in the window.  ``window`` is (lo_ns, hi_ns); ``spans`` is {name:
    (n, 2) array of host spans in trace ns}.  Times in seconds,
    averaged over the devices used."""
    lo, hi = window
    planes = device_planes(trace)
    if not planes or hi <= lo:
        return None
    spans = {n: union(np.clip(np.asarray(sorted(map(tuple, iv)),
                                         dtype=np.float64), lo, hi))
             if len(iv) else np.zeros((0, 2)) for n, iv in spans.items()}
    busy_s, programs = [], []
    op_seconds, idle_by = {}, {}
    for plane in planes:
        mods, ops = without_own(trace[plane])
        busy = union(_intervals(ops, lo, hi))
        if len(busy) == 0:
            continue
        busy_s.append(covered(busy) * 1e-9)
        programs.append(sum(1 for _, s, _ in mods if lo <= s < hi))
        for name, s, d in ops:
            if s + d > lo and s < hi:
                op_seconds[name] = op_seconds.get(name, 0.0) + \
                    (min(s + d, hi) - max(s, lo)) * 1e-9
        for name, sec in attribute(gaps(busy, lo, hi), spans).items():
            idle_by[name] = idle_by.get(name, 0.0) + sec
    if not busy_s:
        return None
    n = len(busy_s)
    top = lambda d: [[k, v / n] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {'window_s': (hi - lo) * 1e-9,
            'busy_s': sum(busy_s) / n,
            'programs': sum(programs) / n,
            'device_ops': top(op_seconds),
            'idle_gaps': top(idle_by),
            'devices': n}
