"""Re-reading the tracing configuration without a restart.

The reference wraps NVTX ranges around block operations so nsight shows
per-op spans (reference: src/trace.hpp:48-179, --enable-trace).  Here
that is :mod:`bifrost_tpu.telemetry.spans`, the one span recorder,
always on; this module keeps the documented entry point that tests and
long-lived operator processes use to apply a changed environment.
"""

from __future__ import annotations

__all__ = ['reset']


def reset():
    """Re-read the gulp-span configuration (``BF_TRACE_FILE`` /
    ``BF_SPAN_BUFFER`` — :mod:`bifrost_tpu.telemetry.spans`) and the
    ``BF_SLO_MS`` latency budget (:mod:`bifrost_tpu.telemetry.slo`).
    ``Pipeline.run`` re-reads both on every run anyway."""
    from .telemetry import spans, slo
    spans.reconfigure()
    slo.reset_budget()
