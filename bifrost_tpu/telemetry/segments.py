"""Telemetry synthesis for compiled pipeline segments
(docs/perf.md "Compiled pipeline segments").

When the segment compiler fuses a chain of device blocks into ONE XLA
program and elides their interior rings, every per-block telemetry
seam of the replaced blocks disappears with them: no on_data wrapper
to span, no ring commit to feed the SLO ages, no dispatch to count.
Observability must survive fusion, so the :class:`SegmentBlock`
records markers around its single dispatch and this module
re-synthesizes the per-block view from them:

- ``block.<member>.gulps`` counters keep advancing (so gulps-per-
  second rollups and like_top's G/D column stay truthful) — but
  ``block.*.dispatches`` stays HONEST: it counts real Python
  dispatches, i.e. segments, not member blocks (the whole point of
  fusion is that members dispatch zero times);
- per-member compute spans on the Chrome-trace timeline: the
  segment's dispatch window sliced evenly across members, tagged
  ``synthesized: 1`` + ``segment: <name>`` so a trace reader can tell
  estimated spans from measured ones (the in-program per-stage split
  is not host-observable — one XLA program has one wall window);
- per-member SLO commit ages (``slo.<member>.commit_age_s``): the
  members commit nothing themselves anymore (the tail-ring commit
  belongs to the segment), so each member observes the segment's
  capture-to-commit age — exact for the chain tail, an upper bound of
  at most one dispatch for the others;
- member perf-ProcLog rows (``publish_member_perf``) so monitor tools
  that discover blocks through ProcLogs never show a fused block as
  dead.

Aggregate fusion health rides two counters the regression sentinel
watches (tools/telemetry_diff.py): ``segment.dispatches`` /
``segment.gulps`` (real dispatch traffic through compiled segments)
and — at plan time — ``segment.compiled`` / ``segment.elided_rings``.
"""

from __future__ import annotations

from . import counters, slo, spans

__all__ = ['note_dispatch', 'publish_member_perf']


def note_dispatch(segment, members, ndispatches, ngulps, t0_us,
                  dur_us, seq, gulp, trace=None, header=None,
                  frame_end=None):
    """Record one segment dispatch covering ``ngulps`` logical gulps
    (``ndispatches`` > 1 when the auto-tuner split the segment into
    sequential sub-programs) and synthesize the members' telemetry
    from it.  Called from ``SegmentBlock.on_data`` — must stay cheap:
    a handful of counter increments and one span per member, plus
    SLO work only when the header carries an origin."""
    counters.inc('segment.dispatches', ndispatches)
    counters.inc('segment.gulps', ngulps)
    for m in members:
        counters.inc('block.%s.gulps' % m, ngulps)
    if members:
        slot = dur_us / len(members)
        for i, m in enumerate(members):
            args = {'seq': seq, 'gulp': gulp, 'segment': segment,
                    'synthesized': 1}
            if trace:
                args['trace'] = trace
            spans.record('%s.on_data' % m, 'compute',
                         t0_us + i * slot, slot, args)
    if header is not None:
        try:
            age = slo.capture_age_s(header, frame_end)
        except Exception:
            age = None
        if age is not None:
            for m in members:
                slo.observe_commit(m, age, ngulps)


def publish_member_perf(proclog, segment, process_s,
                        gulps_per_dispatch):
    """One synthesized perf-ProcLog row for a segment member: the
    member's share of the segment's dispatch wall time, the segment's
    amortization ratio (like_top's G/D column), and the
    ``in_segment`` membership marker.  Rate-limited by the member's
    own ProcLog interval; never raises into the hot path."""
    try:
        if not proclog.ready():
            return
        proclog.update({'acquire_time': 0.0,
                        'reserve_time': 0.0,
                        'process_time': process_s,
                        'gulps_per_dispatch':
                            round(float(gulps_per_dispatch), 3),
                        'in_segment': segment})
    except Exception:
        pass
