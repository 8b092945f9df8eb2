"""Fixed-bucket log2 latency/size histograms — no dependencies, cheap
enough for per-gulp hot paths.

The flat counters in :mod:`bifrost_tpu.telemetry.counters` answer "how
many"; these answer "how long / how big", which is what tuning needs
(a mean hides the p99 that pages the operator).  Each histogram is 64
power-of-two buckets: bucket ``i`` holds values in
``[2**(i + EXP_MIN - 1), 2**(i + EXP_MIN))``, so one ``math.frexp``
finds the bucket and a 64-int walk yields any percentile — no
sampling, no reservoir, no numpy on the hot path.  Recording is one
short critical section per observation (a few arithmetic ops under the
histogram's own lock), so the histograms are always on; what they
cost on the served path is part of ``host_cpu_s_per_gsample``
(PERF.md).

Histogram names used by the framework (the registry is open — blocks
and operators may add their own):

- ``block.<block>.gulp_s``       per-gulp wall time through a block's
                                 main loop (acquire + reserve + process)
- ``block.<block>.ring_wait_s``  per-gulp time blocked on ring flow
                                 control (acquire + reserve)
- ``ring.<ring>.reserve_s``      writer-side span reservation time
- ``ring.<ring>.acquire_s``      reader-side span acquisition time
- ``block.<block>.sync_wait_s``   the dispatch-ahead wait for the device
- ``xfer.h2d_s`` / ``xfer.d2h_wait_s``  host-side transfer time, and its
                                 parts: ``xfer.h2d_stage_s`` (staging
                                 copy), ``xfer.h2d_put_s`` (device_put);
                                 ``xfer.d2h_ready_s`` (device and DMA
                                 remainder), ``xfer.d2h_asarray_s``,
                                 ``xfer.d2h_convert_s``
- ``xfer.d2h_fill_s``            the copy of a product into its host
                                 ring span (deferred fills)
- ``xfer.d2h_peer_wait_s``       waiting for the thread that claimed
                                 the same transfer (a completion
                                 thread as a rule) to land it
- ``xfer.h2d_nbytes`` / ``xfer.d2h_nbytes``  transfer sizes
- ``jit.compile_s``              compilations and persistent-cache
                                 loads (jax.monitoring backend-compile
                                 events)
- ``slo.<block>.commit_age_s``   capture -> block-commit data age
                                 (telemetry.slo; needs a trace-context
                                 origin in the sequence header)
- ``slo.<block>.exit_age_s`` / ``slo.exit_age_s``  capture ->
                                 pipeline-exit age per sink / merged
                                 (the capture-to-commit SLO p50/p99)

Every duration above but the two ``block.<block>`` loop times is
recorded by the span of the same site, from the same two stamps
(``spans.timed``; docs/observability.md has the table).

Percentiles are bucket UPPER bounds clamped to the observed min/max:
an estimate, monotone in ``p`` by construction (the exporter tests
rely on that), and never off by more than one power of two.
"""

from __future__ import annotations

import math
import threading

__all__ = ['Histogram', 'observe', 'get', 'get_or_create', 'snapshot',
           'reset', 'NBUCKET', 'EXP_MIN']

#: number of power-of-two buckets per histogram
NBUCKET = 64
#: exponent of the lowest bucket's upper bound: bucket 0 collects
#: everything below 2**EXP_MIN (~60 ns for seconds; tiny for bytes)
EXP_MIN = -24


def bucket_upper(i):
    """Upper bound of bucket ``i`` (exclusive)."""
    return 2.0 ** (EXP_MIN + i)


class Histogram(object):
    """One named log2 histogram (count / sum / min / max / buckets)."""

    __slots__ = ('name', 'unit', 'count', 'total', 'vmin', 'vmax',
                 'buckets', '_lock')

    def __init__(self, name, unit=''):
        self.name = name
        self.unit = unit
        self.count = 0
        self.total = 0.0
        self.vmin = float('inf')
        self.vmax = 0.0
        self.buckets = [0] * NBUCKET
        self._lock = threading.Lock()

    def record(self, value):
        """Add one observation (negative values clamp to 0)."""
        v = float(value)
        if v < 0.0 or v != v:          # negative / NaN: clamp
            v = 0.0
        if v > 0.0:
            i = math.frexp(v)[1] - EXP_MIN   # v in [2**(e-1), 2**e)
            if i < 0:
                i = 0
            elif i >= NBUCKET:
                i = NBUCKET - 1
        else:
            i = 0
        with self._lock:
            self.count += 1
            self.total += v
            if v < self.vmin:
                self.vmin = v
            if v > self.vmax:
                self.vmax = v
            self.buckets[i] += 1

    @staticmethod
    def _percentile_locked(buckets, count, vmin, vmax, p):
        if count <= 0:
            return 0.0
        target = p / 100.0 * count
        if target < 1.0:
            target = 1.0
        cum = 0
        for i, c in enumerate(buckets):
            cum += c
            if cum >= target:
                est = bucket_upper(i)
                # clamp to the observed range: tighter than the bucket
                # edge and still monotone in p (the clamps are
                # constants over a nondecreasing estimate)
                return min(max(est, vmin), vmax)
        return vmax

    def percentile(self, p):
        """Estimated p-th percentile (upper bucket bound, clamped to
        the observed min/max; monotone in ``p``)."""
        with self._lock:
            return self._percentile_locked(self.buckets, self.count,
                                           self.vmin, self.vmax, p)

    def snapshot(self):
        """Plain-dict snapshot: count/sum/min/max, p50/p90/p99, and the
        non-empty buckets keyed by their upper-bound exponent."""
        with self._lock:
            buckets = list(self.buckets)
            count = self.count
            total = self.total
            vmin = self.vmin if count else 0.0
            vmax = self.vmax
        pct = lambda p: self._percentile_locked(buckets, count,  # noqa: E731
                                                vmin, vmax, p)
        return {
            'count': count,
            'sum': total,
            'min': vmin,
            'max': vmax,
            'p50': pct(50),
            'p90': pct(90),
            'p99': pct(99),
            'buckets': {EXP_MIN + i: c for i, c in enumerate(buckets)
                        if c},
        }


# ---------------------------------------------------------------------------
# process-wide registry
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_registry = {}


def get_or_create(name, unit=''):
    """The histogram named ``name`` (created on first use).  Hot paths
    should cache the returned object and call ``record`` directly."""
    h = _registry.get(name)
    if h is None:
        with _lock:
            h = _registry.get(name)
            if h is None:
                h = Histogram(name, unit=unit)
                _registry[name] = h
    return h


def observe(name, value):
    """Record ``value`` into the histogram named ``name``."""
    get_or_create(name).record(value)


def get(name):
    """The named histogram, or None if nothing was ever recorded."""
    return _registry.get(name)


def snapshot():
    """{name: histogram snapshot} for every registered histogram."""
    with _lock:
        items = list(_registry.items())
    return {name: h.snapshot() for name, h in items}


def reset():
    """Drop every histogram (tests/benchmarks)."""
    with _lock:
        _registry.clear()


def clear(name):
    """Zero one histogram IN PLACE (hot-path caches holding the object
    keep recording into it) — the SLO age-reset path uses this so one
    skipped/shed sequence's stale ages don't poison p99 forever."""
    h = _registry.get(name)
    if h is None:
        return False
    with h._lock:
        h.count = 0
        h.total = 0.0
        h.vmin = float('inf')
        h.vmax = 0.0
        h.buckets = [0] * NBUCKET
    return True


def clear_matching(prefix):
    """Zero every registered histogram whose name starts with
    ``prefix`` (in place); returns how many were cleared."""
    with _lock:
        names = [n for n in _registry if n.startswith(prefix)]
    return sum(1 for n in names if clear(n))
