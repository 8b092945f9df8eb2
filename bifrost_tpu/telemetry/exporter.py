"""Unified metrics snapshot + export surface.

One call — :func:`snapshot` — merges the three live metric sources
(the flat :mod:`~bifrost_tpu.telemetry.counters`, the log2
:mod:`~bifrost_tpu.telemetry.histograms`, and point-in-time ring
occupancy) into a plain dict, and two exporters publish it:

- **ProcLog** — :class:`MetricsPublisher` (started by
  ``Pipeline.run``) periodically writes ``telemetry/metrics`` (flat
  counters + histogram percentiles) and per-ring ``rings_flow/<name>``
  entries (occupancy %, cumulative gulps, gulps/s, wait percentiles),
  which ``tools/pipeline2dot.py`` uses to label ring edges as a
  bottleneck map and ``tools/like_top.py`` complements with the
  per-block p50/p99 columns the blocks publish themselves.

- **Prometheus textfile** — ``BF_METRICS_FILE=/path/metrics.prom``
  makes the publisher (and the final flush at pipeline exit) write the
  snapshot in Prometheus text exposition format for a node-exporter
  textfile collector or any scraper that reads files.  Counters become
  ``bifrost_tpu_counter_total{name=...}``, histograms become real
  Prometheus histograms (cumulative ``_bucket{le=...}`` / ``_sum`` /
  ``_count``), ring occupancy becomes a gauge.

``BF_METRICS_INTERVAL`` sets the publish period (seconds, default 5).
The publisher's thread is also who samples the process's CPU by
thread (:mod:`~bifrost_tpu.telemetry.threadcpu`), once every
``CPU_SAMPLE_S``: the snapshot's ``threads`` section and the
``bf_thread_*`` series are the newest of those readings by family.
Everything here is read-only over the live metric state; a publisher
failure never propagates into the pipeline.
"""

from __future__ import annotations

import os
import threading
import time

from . import counters, histograms, spans, threadcpu

__all__ = ['snapshot', 'write_prometheus', 'prometheus_text',
           'MetricsPublisher', 'RateTracker']

DEFAULT_INTERVAL = 5.0
#: seconds between two readings of the process's threads
#: (``threadcpu.sample``) by a running publisher: 0.2 ms of CPU each
CPU_SAMPLE_S = 1.0


class RateTracker(object):
    """Derives per-second rates from the deltas between successive
    snapshots (docs/autotune.md; the closed-loop auto-tuner's signal
    source, and what the metrics publisher's ``gulps_per_s`` columns
    are computed from instead of ad-hoc last-value bookkeeping).

    Each caller that needs an independent cadence owns its own
    tracker (``snapshot(rates=my_tracker)``); ``snapshot(rates=True)``
    uses a shared module-level one, fine for a single consumer.  The
    first observation has no baseline and reports empty rates.
    Counter resets (``counters.reset()``) produce negative deltas,
    which are clamped to 0 rather than reported as nonsense."""

    def __init__(self):
        self._last = None            # (monotonic, counts, hist_state)

    def observe(self, counts, hists=None):
        """Per-second rates since the previous observe::

            {'dt': seconds_or_None,
             'counters':   {name: per_second},
             'histograms': {name: {'count_per_s': ..,
                                   'sum_per_s': ..}}}

        ``counts`` is a counters.snapshot() dict; ``hists`` an optional
        histograms.snapshot() dict (count/sum deltas — e.g. the
        send-stall seconds accrued per wall second)."""
        now = time.monotonic()
        out = {'dt': None, 'counters': {}, 'histograms': {}}
        hstate = {name: (h.get('count', 0), h.get('sum', 0.0))
                  for name, h in (hists or {}).items()}
        if self._last is not None:
            t0, prev, prev_h = self._last
            dt = now - t0
            if dt > 0:
                out['dt'] = dt
                for name, v in counts.items():
                    out['counters'][name] = \
                        max(v - prev.get(name, 0), 0) / dt
                for name, (cnt, tot) in hstate.items():
                    pc, ps = prev_h.get(name, (0, 0.0))
                    out['histograms'][name] = {
                        'count_per_s': max(cnt - pc, 0) / dt,
                        'sum_per_s': max(tot - ps, 0.0) / dt}
        self._last = (now, counts, hstate)
        return out


#: shared tracker behind ``snapshot(rates=True)``
_global_rates = RateTracker()


def _ring_occupancy(pipeline=None):
    """{ring_name: occupancy dict (+ 'fill' fraction)} — from the
    pipeline's rings when given, else from the process-wide live-ring
    registry (ring.live_rings)."""
    if pipeline is not None:
        from ..supervision import ring_occupancies
        occ = ring_occupancies(pipeline)
    else:
        from ..ring import live_rings
        occ = {}
        for r in live_rings():
            try:
                occ[r.name] = r.occupancy()
            except Exception:
                pass
    out = {}
    for name, d in occ.items():
        d = dict(d)
        size = d.get('size') or 0
        if size and 'head' in d and 'tail' in d:
            frac = (d['head'] - d['tail']) / float(size)
            d['fill'] = max(0.0, min(1.0, frac))
        out[name] = d
    return out


def _device_stats():
    """Per-device HBM/allocator stats from jax ``memory_stats()``
    (docs/parallel.md / docs/observability.md mesh telemetry):
    ``{device_index: {platform, bytes_in_use, bytes_limit,
    peak_bytes_in_use?}}``.  Empty when jax was never imported by this
    process (a snapshot must not drag the backend in) or when
    ``BF_DEVICE_METRICS=0``."""
    import sys
    if os.environ.get('BF_DEVICE_METRICS', '1') == '0':
        return {}
    if 'jax' not in sys.modules:
        return {}
    out = {}
    try:
        import jax
        for i, d in enumerate(jax.local_devices()):
            try:
                s = d.memory_stats() or {}
            except Exception:
                s = {}
            entry = {'platform': str(getattr(d, 'platform', '?'))}
            for src, dst in (('bytes_in_use', 'bytes_in_use'),
                             ('bytes_limit', 'bytes_limit'),
                             ('peak_bytes_in_use', 'peak_bytes_in_use'),
                             ('largest_alloc_size', 'largest_alloc')):
                if src in s:
                    entry[dst] = int(s[src])
            out[i] = entry
    except Exception:
        return {}
    return out


def _tenant_section():
    """The multi-tenant service tier's per-tenant rollups
    (bifrost_tpu.service.telemetry_section — docs/service.md), or {}
    when no service is live in this process.  Gated on the module
    already being imported, like the jax device stats: a snapshot
    must not drag the service layer in."""
    import sys
    if 'bifrost_tpu.service' not in sys.modules:
        return {}
    try:
        from .. import service
        return service.telemetry_section()
    except Exception:
        return {}


def _scheduler_section():
    """The elastic control plane's placement/migration counters
    (bifrost_tpu.scheduler.telemetry_section — docs/scheduler.md),
    or {} when no scheduler is live in this process.  Same
    lazy-import gate as the tenant section."""
    import sys
    if 'bifrost_tpu.scheduler' not in sys.modules:
        return {}
    try:
        from .. import scheduler
        return scheduler.telemetry_section()
    except Exception:
        return {}


#: mesh counter prefixes folded into the snapshot's 'mesh' summary
_MESH_KEYS = ('mesh.reshards', 'mesh.reshard_bytes',
              'mesh.sharded_commits', 'mesh.layout_mismatch',
              'mesh.plans_analyzed', 'mesh.plans_collective_free',
              'mesh.frame_local_fallback')


def _mesh_summary(counts):
    """The mesh-resident pipeline counters regrouped into one section
    (they remain in 'counters' too — this is the at-a-glance view, with
    ``mesh.collectives.<kind>`` folded into a sub-dict)."""
    out = {k.split('.', 1)[1]: counts[k] for k in _MESH_KEYS
           if k in counts}
    coll = {k.split('.', 2)[2]: v for k, v in counts.items()
            if k.startswith('mesh.collectives.')}
    if coll:
        out['collectives'] = coll
    return out


def _threads_section():
    """The newest reading of the process's CPU by thread family
    (``threadcpu``: a running publisher's last sample, else one taken
    now), cumulative seconds; {} without ``/proc``."""
    reading = threadcpu.newest(max_age_s=2 * CPU_SAMPLE_S) or \
        threadcpu.read()
    if reading is None:
        return {}
    return {'clock': reading['clock'],
            'process_cpu_s': reading['process_cpu_s'],
            'steal_s': reading['steal_s'],
            'throttled_s': reading['throttled_s'],
            'families': threadcpu.by_family(reading)}


def snapshot(pipeline=None, rates=False):
    """The unified metrics snapshot::

        {'counters':   {name: int},
         'histograms': {name: {count,sum,min,max,p50,p90,p99,buckets}},
         'rings':      {name: {tail,head,size,...,fill}},
         'devices':    {index: {platform,bytes_in_use,bytes_limit,...}},
         'mesh':       {reshards,sharded_commits,collectives,...},
         'tenants':    {tenant_id: {state,health,gulps,bytes,
                        quota_shed_*,ring_shed_*,slo,...}},
         'scheduler':  {placements,migrations,replacements,...},
         'threads':    {clock, process_cpu_s, steal_s, throttled_s,
                        families: {family: {cpu_s, runq_s, threads,
                                            named}}},
         'rates':      {dt, counters: {name: per_s},
                        histograms: {name: {count_per_s, sum_per_s}}}}

    ``pipeline`` narrows the ring section to one pipeline's rings;
    without it every live ring in the process is reported.  The
    'counters' section includes the live ``trace.dropped_spans`` total
    (per-thread span-buffer overflow — docs/observability.md); the SLO
    age histograms/violation counters (telemetry.slo) appear under
    their ``slo.*`` names in 'histograms'/'counters'.

    ``rates`` adds derived per-second rates from the counter and
    histogram deltas since this tracker's PREVIOUS snapshot: ``True``
    uses a shared module tracker (one consumer), or pass your own
    :class:`RateTracker` for an independent cadence (the closed-loop
    auto-tuner and the metrics publisher each own one).  The first
    snapshot has no baseline and reports empty rate dicts.
    """
    counts = counters.snapshot()
    dropped = spans.dropped_spans()
    if dropped:
        counts['trace.dropped_spans'] = \
            counts.get('trace.dropped_spans', 0) + dropped
    hists = histograms.snapshot()
    # host identity (docs/fabric.md): which host/launcher this
    # process IS — N fabric processes aggregating snapshots (or
    # Prometheus textfiles on a shared filesystem) stay attributable
    import os as _os
    import socket as _socket
    from ..proclog import get_identity
    ident = get_identity()
    identity = {'hostname': _socket.gethostname(), 'pid': _os.getpid()}
    if ident is not None:
        identity['fabric_host'] = ident[0]
        identity['fabric_role'] = ident[1]
    snap = {
        'counters': counts,
        'gauges': counters.gauges(),
        'histograms': hists,
        'rings': _ring_occupancy(pipeline),
        'devices': _device_stats(),
        'mesh': _mesh_summary(counts),
        'tenants': _tenant_section(),
        'scheduler': _scheduler_section(),
        'threads': _threads_section(),
        'identity': identity,
    }
    if rates:
        tracker = rates if isinstance(rates, RateTracker) \
            else _global_rates
        snap['rates'] = tracker.observe(counts, hists)
    return snap


# ---------------------------------------------------------------------------
# Prometheus textfile export
# ---------------------------------------------------------------------------

def _esc(value):
    return str(value).replace('\\', r'\\').replace('"', r'\"') \
                     .replace('\n', r'\n')


def prometheus_text(snap=None):
    """Render a snapshot in Prometheus text exposition format."""
    if snap is None:
        snap = snapshot()
    lines = ['# bifrost_tpu metrics (telemetry.exporter)']
    lines.append('# TYPE bifrost_tpu_counter_total counter')
    for name in sorted(snap.get('counters', {})):
        lines.append('bifrost_tpu_counter_total{name="%s"} %d'
                     % (_esc(name), snap['counters'][name]))
    if snap.get('gauges'):
        lines.append('# TYPE bifrost_tpu_gauge gauge')
    for name in sorted(snap.get('gauges', {})):
        lines.append('bifrost_tpu_gauge{name="%s"} %g'
                     % (_esc(name), snap['gauges'][name]))
    hists = snap.get('histograms', {})
    if hists:
        lines.append('# TYPE bifrost_tpu_hist histogram')
    for name in sorted(hists):
        h = hists[name]
        label = _esc(name)
        cum = 0
        for exp in sorted(h.get('buckets', {})):
            cum += h['buckets'][exp]
            lines.append('bifrost_tpu_hist_bucket{name="%s",le="%g"} %d'
                         % (label, 2.0 ** exp, cum))
        lines.append('bifrost_tpu_hist_bucket{name="%s",le="+Inf"} %d'
                     % (label, h['count']))
        lines.append('bifrost_tpu_hist_sum{name="%s"} %g'
                     % (label, h['sum']))
        lines.append('bifrost_tpu_hist_count{name="%s"} %d'
                     % (label, h['count']))
    rings = snap.get('rings', {})
    if rings:
        lines.append('# TYPE bifrost_tpu_ring_fill_ratio gauge')
        lines.append('# TYPE bifrost_tpu_ring_bytes gauge')
    for name in sorted(rings):
        d = rings[name]
        label = _esc(name)
        if 'fill' in d:
            lines.append('bifrost_tpu_ring_fill_ratio{ring="%s"} %g'
                         % (label, d['fill']))
        for key in ('tail', 'head', 'size'):
            if key in d:
                lines.append('bifrost_tpu_ring_bytes{ring="%s",'
                             'kind="%s"} %d' % (label, key, d[key]))
    devices = snap.get('devices', {})
    if devices:
        lines.append('# TYPE bifrost_tpu_device_bytes gauge')
    for idx in sorted(devices):
        d = devices[idx]
        for key, kind in (('bytes_in_use', 'in_use'),
                          ('bytes_limit', 'limit'),
                          ('peak_bytes_in_use', 'peak'),
                          ('largest_alloc', 'largest_alloc'),
                          ('watermark_bytes', 'watermark')):
            if key in d:
                lines.append('bifrost_tpu_device_bytes{device="%s",'
                             'kind="%s"} %d' % (_esc(idx), kind,
                                                d[key]))
    # tenant-labeled series (the multi-tenant service tier,
    # docs/service.md): one gauge family keyed {tenant,kind} plus a
    # one-hot health-state family, so per-tenant dashboards need no
    # name parsing
    tenants = snap.get('tenants', {})
    if tenants:
        lines.append('# TYPE bifrost_tpu_tenant gauge')
        lines.append('# TYPE bifrost_tpu_tenant_health gauge')
    for tid in sorted(tenants):
        d = tenants[tid]
        label = _esc(tid)
        for key in ('gulps', 'bytes', 'quota_shed_gulps',
                    'quota_shed_bytes', 'ring_shed_gulps',
                    'ring_shed_bytes', 'warm'):
            v = d.get(key)
            if isinstance(v, (int, float)):
                # ledger counters are exact integers — %d like every
                # other counter series (%g would quantize past ~6
                # significant digits and stair-step rate() queries)
                lines.append('bifrost_tpu_tenant{tenant="%s",'
                             'kind="%s"} %d' % (label, key, int(v)))
        slo = d.get('slo') or {}
        p99 = slo.get('exit_age_p99_s')
        if isinstance(p99, (int, float)):
            lines.append('bifrost_tpu_tenant{tenant="%s",'
                         'kind="exit_age_p99_s"} %g' % (label, p99))
        if isinstance(slo.get('violations'), (int, float)):
            lines.append('bifrost_tpu_tenant{tenant="%s",'
                         'kind="slo_violations"} %g'
                         % (label, slo['violations']))
        lines.append('bifrost_tpu_tenant_health{tenant="%s",'
                     'state="%s"} 1' % (label,
                                        _esc(d.get('health', '?'))))
    # CPU by thread family (telemetry.threadcpu): cumulative seconds
    # on a CPU and runnable but waiting for one, from the kernel's
    # scheduler clock; the machine's steal and the cgroup's throttle
    threads = snap.get('threads') or {}
    fams = threads.get('families', {})
    if fams:
        lines.append('# TYPE bf_thread_cpu_seconds_total counter')
        lines.append('# TYPE bf_thread_runq_seconds_total counter')
    for series, key in (('bf_thread_cpu_seconds_total', 'cpu_s'),
                        ('bf_thread_runq_seconds_total', 'runq_s')):
        for name in sorted(fams):
            if fams[name].get(key) is not None:
                lines.append('%s{family="%s"} %.6f'
                             % (series, _esc(name), fams[name][key]))
    for series, key in (('bf_cpu_steal_seconds_total', 'steal_s'),
                        ('bf_cpu_throttled_seconds_total', 'throttled_s')):
        if threads.get(key) is not None:
            lines.append('# TYPE %s counter' % series)
            lines.append('%s %.6f' % (series, threads[key]))
    return '\n'.join(lines) + '\n'


def write_prometheus(path, snap=None):
    """Atomically write the snapshot as a Prometheus textfile."""
    text = prometheus_text(snap)
    # pid AND thread ident: concurrent pipelines each run their own
    # publisher thread against the same BF_METRICS_FILE
    tmp = '%s.tmp%d.%d' % (path, os.getpid(),
                           threading.get_ident())
    with open(tmp, 'w') as f:
        f.write(text)
    os.replace(tmp, path)
    return path


# ---------------------------------------------------------------------------
# periodic publisher (ProcLog + Prometheus)
# ---------------------------------------------------------------------------

class MetricsPublisher(threading.Thread):
    """Daemon thread publishing the unified snapshot periodically:
    ``telemetry/metrics`` + ``rings_flow/<name>`` ProcLogs always, the
    ``BF_METRICS_FILE`` Prometheus textfile when configured.  A final
    publish runs on :meth:`stop` so short pipelines still leave a
    complete last snapshot behind."""

    def __init__(self, pipeline=None, interval=None):
        super(MetricsPublisher, self).__init__(
            name='bf-metrics', daemon=True)
        if interval is None:
            try:
                interval = float(os.environ.get('BF_METRICS_INTERVAL',
                                                '') or DEFAULT_INTERVAL)
            except ValueError:
                interval = DEFAULT_INTERVAL
        self.interval = max(float(interval), 0.1)
        self.pipeline = pipeline
        self._stop_event = threading.Event()
        self._proclogs = {}
        #: per-second rate derivation between publishes (shared
        #: RateTracker machinery — no more ad-hoc last-value dicts)
        self._rates = RateTracker()
        #: per-device HBM watermark: the highest bytes_in_use this
        #: publisher has SAMPLED (coarser than the allocator's own
        #: peak_bytes_in_use where available, but live on every
        #: backend and reset-free across allocator stat resets)
        self._hbm_watermark = {}
        #: fleet streaming (telemetry.fleet): when BF_FLEET_COLLECTOR
        #: is set, hold the process-shared FleetPublisher for this
        #: pipeline's lifetime — N tenant pipelines share one stream;
        #: the last stop() sends the final full snapshot
        from . import fleet as _fleet
        self._fleet = _fleet.acquire_publisher()

    def stop(self, wait=True):
        """Stop the loop; publishes one final snapshot first."""
        self._stop_event.set()
        if wait and self.is_alive():
            self.join(self.interval + 2.0)
        if self._fleet is not None:
            from . import fleet as _fleet
            _fleet.release_publisher(self._fleet)
            self._fleet = None

    def run(self):
        # the pipeline's one housekeeping thread also keeps the series
        # of CPU readings by thread: one as it starts, one every
        # CPU_SAMPLE_S, one as it stops
        threadcpu.sample()
        due = time.monotonic() + self.interval
        while not self._stop_event.wait(
                min(CPU_SAMPLE_S, max(due - time.monotonic(), 0.0))):
            if time.monotonic() >= due:
                self.publish()
                due = time.monotonic() + self.interval
            else:
                threadcpu.sample()
        self.publish()               # final snapshot at shutdown

    # -- publishing --------------------------------------------------------
    def _proclog(self, name):
        log = self._proclogs.get(name)
        if log is None:
            from ..proclog import ProcLog
            log = self._proclogs[name] = ProcLog(name)
        return log

    def publish(self):
        try:
            threadcpu.sample()
            snap = snapshot(self.pipeline, rates=self._rates)
            self._note_watermarks(snap)
            self._publish_proclog(snap)
            path = os.environ.get('BF_METRICS_FILE')
            if path:
                write_prometheus(path, snap)
        except Exception:
            pass                     # never take the pipeline down

    def _note_watermarks(self, snap):
        """Fold the publisher's sampled HBM watermark into the
        snapshot's device entries (and keep it across publishes)."""
        for idx, d in snap.get('devices', {}).items():
            in_use = d.get('bytes_in_use')
            if in_use is None:
                continue
            mark = max(self._hbm_watermark.get(idx, 0), in_use)
            self._hbm_watermark[idx] = mark
            d['watermark_bytes'] = mark

    def _publish_proclog(self, snap):
        flat = {}
        for name, value in sorted(snap['counters'].items()):
            flat['c.' + name] = value
        for name, h in sorted(snap['histograms'].items()):
            flat['h.%s.count' % name] = h['count']
            flat['h.%s.p50' % name] = '%g' % h['p50']
            flat['h.%s.p99' % name] = '%g' % h['p99']
        self._proclog('telemetry/metrics').update(flat, force=True)

        crates = snap.get('rates', {}).get('counters', {})
        hists = snap['histograms']
        for name, d in sorted(snap['rings'].items()):
            gulps = snap['counters'].get('ring.%s.gulps' % name, 0)
            rate = crates.get('ring.%s.gulps' % name, 0.0)
            entry = {
                'occupancy_pct': round(100.0 * d.get('fill', 0.0), 1),
                'gulps': gulps,
                'gulps_per_s': round(rate, 3),
                'poisoned': int(bool(d.get('poisoned'))),
            }
            for kind in ('reserve', 'acquire'):
                h = hists.get('ring.%s.%s_s' % (name, kind))
                if h and h['count']:
                    entry['%s_wait_p99_ms' % kind] = \
                        round(h['p99'] * 1e3, 3)
            self._proclog('rings_flow/%s' % name).update(entry,
                                                         force=True)
        # per-device HBM telemetry (mesh observability): one proclog
        # entry per local device with in-use/limit/peak/watermark
        for idx, d in sorted(snap.get('devices', {}).items()):
            entry = {k: v for k, v in d.items() if k != 'platform'}
            if not entry:
                continue
            entry['platform'] = d.get('platform', '?')
            self._proclog('devices/%s' % idx).update(entry, force=True)
