"""Capture-to-commit latency SLOs (docs/observability.md
"Distributed tracing & SLOs").

PR 3's histograms answer "how long does a BLOCK take per gulp"; an
ingest tier serving live traffic needs the orthogonal question — "how
OLD is the data by the time it lands?".  This module tracks that age
end to end: the stream-origin block stamps a wall-clock origin
timestamp into the sequence header (``header_standard.
ensure_trace_context``), and every ring commit downstream — including
commits on ANOTHER HOST, because the bridge ships headers verbatim —
records ``now - capture_time`` into a log2 histogram:

- ``slo.<block>.commit_age_s``   capture -> block-commit age, per
                                 committing block (ring owner), one
                                 observation per logical gulp
- ``slo.<block>.exit_age_s``     capture -> pipeline-exit age observed
                                 by sink blocks (no output ring: the
                                 data is leaving the pipeline)
- ``slo.exit_age_s``             all sinks merged — THE
                                 pipeline-exit p50/p99

``capture_time`` is the sequence's origin timestamp extrapolated by
frame time when the header carries a numeric ``tsamp`` (seconds per
frame): frame ``f`` was captured at ``origin + f * tsamp``, so a long
healthy stream reports steady transit latency instead of an age that
grows with stream position.  Without ``tsamp`` the age is measured
against the sequence origin (exact for the short sequences benches and
tests run; an upper bound elsewhere).

**Budget**: ``BF_SLO_MS=<ms>`` arms a latency budget.  Any observation
above it increments ``slo.violations`` plus a per-block
``slo.<name>.violations`` counter — surfaced by
``telemetry.snapshot()``, the Prometheus textfile, and the supervisors
reading either.  Ages always record (the histograms are the
observability); the budget only adds the violation counting.

Cost: one ``time.time()`` plus one histogram record per commit.
Everything is a no-op for sequences without a trace context
(``BF_TRACE_CONTEXT=0`` or pre-context peers).
"""

from __future__ import annotations

import os
import time

from . import counters, histograms
from ..header_standard import trace_context

__all__ = ['budget_s', 'reset_budget', 'capture_age_s',
           'observe_commit', 'observe_exit', 'observe_shed',
           'observe_fabric_exit', 'reset_block_ages',
           'EXIT_HISTOGRAM', 'SHED_HISTOGRAM',
           'FABRIC_EXIT_HISTOGRAM']

#: the merged pipeline-exit age histogram (all sink blocks)
EXIT_HISTOGRAM = 'slo.exit_age_s'
#: cross-host capture-to-sink age (docs/fabric.md): recorded by sink
#: blocks whose stream crossed >= 1 bridge hop, against the ORIGIN
#: host's trace-context ``origin_ns`` corrected by the cumulative
#: handshake-measured wall-clock skew (``_trace.skew_ns``, stamped by
#: each bridge sender) — THE fabric end-to-end SLO number
FABRIC_EXIT_HISTOGRAM = 'slo.fabric_exit_age_s'
#: age of data at the moment a drop_* overload policy shed it — how
#: stale the stream had become when the pipeline chose loss over
#: latency (docs/robustness.md "Overload & degradation")
SHED_HISTOGRAM = 'slo.shed_age_s'

_budget = None          # cached 1-tuple (budget seconds or None)


def budget_s():
    """The ``BF_SLO_MS`` latency budget in seconds, or None when no
    budget is armed.  Cached; :func:`reset_budget` re-reads (tests /
    long-lived operator processes)."""
    global _budget
    if _budget is None:
        raw = os.environ.get('BF_SLO_MS', '').strip()
        val = None
        if raw:
            try:
                val = float(raw) * 1e-3
            except ValueError:
                val = None
        _budget = (val,)
    return _budget[0]


def reset_budget():
    """Drop the cached budget so the next observation re-reads
    ``BF_SLO_MS`` (reached via ``bifrost_tpu.trace.reset()``)."""
    global _budget
    _budget = None


def capture_age_s(header, frame_end=None, now=None):
    """Age of the data being committed: ``now - capture_time``, or
    None when the header carries no trace-context origin.

    ``frame_end`` (the committed span's last frame index within the
    sequence) enables frame-time extrapolation when the header has a
    numeric ``tsamp`` > 0; otherwise the sequence origin is used."""
    ctx = trace_context(header)
    if ctx is None:
        return None
    try:
        origin = float(ctx['origin_ns']) * 1e-9
    except (KeyError, TypeError, ValueError):
        return None
    # cross-host correction (docs/fabric.md): each bridge hop
    # accumulated its handshake-measured wall-clock offset into
    # skew_ns, so origin + skew is the capture instant expressed on
    # THIS host's clock — without it a skewed host would report the
    # clock difference as transit latency (or a negative age)
    skew = ctx.get('skew_ns')
    if isinstance(skew, (int, float)):
        origin += float(skew) * 1e-9
    if frame_end is not None:
        tsamp = header.get('tsamp')
        if isinstance(tsamp, (int, float)) and 0 < tsamp < 1e6:
            origin += frame_end * float(tsamp)
    if now is None:
        now = time.time()
    age = now - origin
    return age if age > 0.0 else 0.0


def _observe(hist_name, counter_name, age_s):
    histograms.observe(hist_name, age_s)
    b = budget_s()
    if b is not None and age_s > b:
        counters.inc('slo.violations')
        counters.inc(counter_name)


def observe_commit(name, age_s, ngulps=1):
    """Record a capture->commit age for the block (or ring) ``name``
    — called from ``Ring._note_commit`` (BOTH cores) once per commit;
    ``ngulps`` > 1 (macro spans) still records ONE observation (the
    span commits as one unit; its age is the age of its newest
    frame)."""
    _observe('slo.%s.commit_age_s' % name,
             'slo.%s.violations' % name, age_s)


def observe_exit(name, age_s):
    """Record a capture->pipeline-exit age (sink blocks): both the
    per-sink histogram and the merged ``slo.exit_age_s``."""
    histograms.observe(EXIT_HISTOGRAM, age_s)
    _observe('slo.%s.exit_age_s' % name,
             'slo.%s.violations' % name, age_s)


def observe_fabric_exit(name, age_s):
    """Record a CROSS-HOST capture->sink age (docs/fabric.md): called
    next to :func:`observe_exit` by sink blocks whose input stream's
    trace context shows >= 1 bridge hop.  Records the merged
    ``slo.fabric_exit_age_s`` plus a per-sink histogram; ages above
    the ``BF_SLO_MS`` budget count on the shared violation counters
    like any other SLO observation."""
    histograms.observe(FABRIC_EXIT_HISTOGRAM, age_s)
    _observe('slo.%s.fabric_exit_age_s' % name,
             'slo.%s.violations' % name, age_s)


def observe_shed(age_s):
    """Record the age of data a drop_* overload policy shed
    (``Ring._note_shed``, both ring cores): the merged
    ``slo.shed_age_s`` histogram is how an operator sees WHAT was
    lost under overload — old backlog (healthy drop_oldest behavior)
    vs fresh data (the pipeline is badly underprovisioned).  Never
    counts on the violation counters: shedding is the budget-KEEPING
    mechanism."""
    histograms.observe(SHED_HISTOGRAM, age_s)


def reset_block_ages(name):
    """Zero ``slo.<name>.commit_age_s`` / ``slo.<name>.exit_age_s``
    in place.  Called when a block sheds or skips a whole sequence
    (``on_failure='skip_sequence'``): the abandoned sequence's stale
    origin would otherwise sit in the p99 forever, paging operators
    about latency the recovery already resolved.  Violation COUNTERS
    are cumulative history and are deliberately not reset."""
    histograms.clear('slo.%s.commit_age_s' % name)
    histograms.clear('slo.%s.exit_age_s' % name)
