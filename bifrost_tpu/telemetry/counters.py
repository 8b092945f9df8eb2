"""In-process performance counters for the transfer engine and the
pipeline gulp loop.

Unlike the usage telemetry in :mod:`bifrost_tpu.telemetry` (opt-in,
persisted), these are always-on, process-local integers with no
persistence and no I/O: the hot paths (per-gulp transfer issue, sync
waits, donation hits) increment them under a lock, and benchmarks /
tests read a snapshot to verify overlap claims (e.g. "hard syncs per
gulp dropped from 1 to <= 1/sync_depth").

Counter names used by the framework:

- ``xfer.h2d_issued`` / ``xfer.h2d_bytes``  host->device transfers
- ``xfer.h2d_word_bytes``                  bytes of those that crossed
                                           as int16 words, one a ci8
                                           sample, in the host's order
                                           (devrep.ComplexWords)
- ``xfer.h2d_staged``                      H2D via a reused staging slot
- ``xfer.h2d_unstaged``                    H2D that fell back to a fresh
                                           defensive copy
- ``xfer.d2h_issued`` / ``xfer.d2h_bytes``  device->host transfers
- ``xfer.d2h_async``                       D2H issued non-blocking
                                           (copy_to_host_async + queue)
- ``xfer.sync_waits``                      hard host blocks inside a
                                           transfer (result not ready)
- ``xfer.fills_by_worker`` /
  ``xfer.fills_by_caller``                 deferred ring fills completed
                                           by one of the engine's
                                           completion threads / by the
                                           thread that needed the bytes
                                           (a reader, a writer, the
                                           depth bound, a synchronous
                                           fill); their sum is the fills
                                           completed
- ``pipeline.sync_waits``                  dispatch-ahead drain waits in
                                           Block._sync_gulp
- ``pipeline.gulps``                       gulps processed through
                                           Block._sync_gulp
- ``donation.hits`` / ``donation.misses``   gulp inputs donated to XLA /
                                           eligible but not exclusive

Robustness counters (supervision layer — docs/robustness.md; surfaced
by :func:`bifrost_tpu.telemetry.flush`):

- ``block_failures``                       exceptions that escaped a
                                           block's main loop (any policy)
- ``block_restarts``                       restart-policy re-entries
- ``ring_poisoned``                        rings marked dead by
                                           Ring.poison (failure
                                           propagation / shutdown wakeup)
- ``watchdog_stalls``                      whole-pipeline stalls the
                                           watchdog detected
- ``xfer.errors`` / ``xfer.fill_errors``    failed D2H transfers /
                                           deferred ring fills
- ``io.socket_retries``                    transient socket errors
                                           (EINTR/ECONNREFUSED) retried
                                           with backoff

Ring-bridge counters (io/bridge.py wire v2 — docs/networking.md):

- ``bridge.tx.frames`` / ``bridge.tx.bytes`` /
  ``bridge.tx.spans``                      frames/payload bytes/span
                                           frames sent by RingSender
- ``bridge.tx.reconnects``                 sender-side transport
                                           redials (unacked frames
                                           retransmitted)
- ``bridge.tx.restripes``                  planned stripe-count
                                           retunes (the auto-tuner's
                                           BF_BRIDGE_STREAMS knob):
                                           drained redials at a span
                                           boundary, never counted
                                           against the reconnect
                                           budget
- ``bridge.rx.frames`` / ``bridge.rx.bytes`` /
  ``bridge.rx.spans``                      frames/bytes/spans committed
                                           by RingReceiver
- ``bridge.rx.dups``                       retransmitted frames dropped
                                           by sequence number after a
                                           reconnect
- ``bridge.rx.crc_errors``                 span CRC32 mismatches
                                           (BF_BRIDGE_CRC=1); each one
                                           raises BridgeProtocolError

(Send-stall / recv-wait distributions live on the
``bridge.<name>.send_stall_s`` / ``bridge.<name>.recv_wait_s``
histograms; per-endpoint byte totals also feed the like_bmon bridge
rows via ``<name>_bridge_transmit|capture/stats`` proclogs.)

Observability counters (docs/observability.md; complemented by
:mod:`bifrost_tpu.telemetry.histograms` for distributions):

- ``ring.<name>.capacity_bytes``           GAUGE: bytes ring ``<name>``
                                           may hold at its present
                                           geometry (span depth plus,
                                           on the host, the ghost
                                           region), set at every
                                           resize
- ``ring.held_bytes.system`` /
  ``ring.held_bytes.tpu``                  GAUGE: the sum of those over
                                           the live rings of a space
- ``xfer.d2h_piece_bytes``                 bytes of ``xfer.d2h_bytes``
                                           that crossed in pieces
                                           (docs/transfer.md)
- ``xfer.d2h_pair_bytes``                  bytes of those that crossed
                                           as real (re, im) pairs: the
                                           complex products' (0, and
                                           there, where none is)
- ``xfer.d2h_plane_bytes``                 bytes of those whose pieces
                                           were cut from the two real
                                           planes the product was
                                           computed in
                                           (devrep.ComplexPlanes), not
                                           from a complex64 array
- ``xfer.d2h_cutup_bytes``                 bytes of products in pieces
                                           whose cuts were all issued
                                           before the first piece was
                                           taken (LARGE, real-worded:
                                           docs/transfer.md)
- ``correlate.integrations``               integrations a CorrelateBlock
                                           emitted
- ``correlate.acc_in_place``               gulps it integrated into
                                           float32 planes in place
- ``accumulate.gulps`` /
  ``accumulate.acc_in_place`` /
  ``accumulate.integrations``              gulps an AccumulateBlock
                                           integrated / those it added
                                           into the donated accumulator
                                           where it lay (all but the
                                           first of an integration, on
                                           the device) / integrations
                                           it handed to its ring
- ``spectrometer.gulps`` /
  ``spectrometer.long_gulps`` /
  ``spectrometer.word_gulps``              gulps a FusedBlock took
                                           through a chain with a
                                           transform in it / those
                                           whose transform ran as
                                           three levels of DFT matrices
                                           (ops.fft.long_fft) / those
                                           whose program started from
                                           the gulp's int16 words
                                           (0, and there, where none
                                           did)
- ``beamform.gulps`` /
  ``beamform.fused_gulps`` /
  ``beamform.word_gulps`` /
  ``beamform.int8_ops``                    gulps a FusedBlock took
                                           through a chain with a
                                           beamformer in it / of them,
                                           through the one kernel, no
                                           beam voltage in HBM
                                           (stages.match_beamformer) /
                                           of them, started from the
                                           gulp's int16 words / 8 x
                                           beams x samples of their
                                           frames, from the shapes
                                           (all at 0, and there, where
                                           nothing of the kind ran)
- ``ring.<name>.gulps``                    LOGICAL gulps committed
                                           through ring ``<name>``
                                           (both cores; a macro-gulp
                                           span credits its K gulps) —
                                           the exporter derives per-ring
                                           gulps/s from its deltas

Macro-gulp execution counters (bifrost_tpu.macro — docs/perf.md):

- ``block.<name>.dispatches``              on_data dispatches issued by
                                           block ``<name>``
- ``block.<name>.gulps``                   logical gulps those
                                           dispatches covered —
                                           dispatches/gulps is the
                                           amortization ratio (1 at
                                           K=1, ~1/K batched)
- ``macro.fallback.<reason>``              macro-gulp requests that
                                           fell back to K=1 (reason:
                                           block / topology /
                                           unguaranteed / overlap /
                                           dynamic_gulp / nonlinear;
                                           multi_reader_retired counts
                                           sequences that batch on a
                                           multi-reader ring the PRE-6
                                           runtime would have forced
                                           to K=1)
- ``xfer.h2d_batched``                     host gulps shipped through
                                           the EXPLICIT batch entry
                                           point (xfer.to_device_batch,
                                           K separate gulps per call).
                                           A CopyBlock moving a macro
                                           ring span ships through
                                           to_device (the span is one
                                           contiguous view) and counts
                                           on h2d_issued only — watch
                                           block.<name>.dispatches to
                                           confirm macro H2D engaged

Compiled-segment counters (bifrost_tpu.segments — docs/perf.md
"Compiled pipeline segments"):

- ``segment.compiled``                     chains fused into one
                                           compiled segment at plan
                                           time
- ``segment.elided_rings``                 interior rings elided by
                                           those segments (no span
                                           ever flows through them)
- ``segment.dispatches`` /
  ``segment.gulps``                        real dispatches issued by
                                           segment programs and the
                                           logical gulps they covered
                                           (> 1 dispatch per gulp-set
                                           only when the auto-tuner
                                           split a segment).  Member
                                           blocks keep synthesized
                                           ``block.<name>.gulps`` but
                                           NO dispatches counter —
                                           ``block.*.dispatches``
                                           counts segments, not
                                           blocks (the regression
                                           sentinel watches both
                                           segment.* counters)

Mesh-resident pipeline counters (docs/parallel.md):

- ``mesh.reshards`` / ``mesh.reshard_bytes``  gulps a block had to
                                           relayout before its mesh
                                           plan (shard_gulp
                                           device_put).  Steady state
                                           in a mesh-resident chain is
                                           ZERO beyond prewarm — a
                                           per-gulp rate means a span
                                           is committed in the wrong
                                           layout
- ``mesh.sharded_commits``                 device-ring span commits
                                           whose chunk spans > 1
                                           device
- ``mesh.layout_mismatch``                 sequences whose producer
                                           advertised a ``_sharding``
                                           header descriptor this
                                           consumer's mesh scope would
                                           relayout (once per
                                           sequence; the per-gulp cost
                                           shows up on mesh.reshards)
- ``ring.<name>.sharded_gulps`` /
  ``ring.<name>.shard_bytes``              per-ring sharded commits
                                           and bytes landing on EACH
                                           device (the per-chip slice)
- ``xfer.h2d_sharded`` /
  ``xfer.h2d_shard_bytes``                 sharded H2D placements
                                           (per-shard staged
                                           device_put + assembly) and
                                           per-shard bytes;
                                           ``xfer.h2d_sharded_fallback``
                                           counts whole-array
                                           device_put fallbacks
                                           (BF_MESH_H2D=0 or an
                                           unstageable sharding)
- ``mesh.frame_local_fallback``            frame-local shard_map plan
                                           builds that FAILED and
                                           degraded to GSPMD (the
                                           divisible-geometry
                                           early-out is not counted —
                                           only unexpected build
                                           errors)
- ``mesh.plans_analyzed`` /
  ``mesh.plans_collective_free`` /
  ``mesh.collectives.<kind>``              BF_MESH_HLO_STATS=1 plan
                                           analysis: compiled mesh
                                           plans inspected, how many
                                           contained no collectives,
                                           and the per-kind counts
                                           (all_gather / all_reduce /
                                           reduce_scatter / all_to_all
                                           / collective_permute)

Distributed-observability counters (docs/observability.md
"Distributed tracing & SLOs"):

- ``slo.violations``                       capture-to-commit/-exit age
                                           observations above the
                                           ``BF_SLO_MS`` budget (see
                                           telemetry.slo); per-block
                                           breakdown on
                                           ``slo.<block>.violations``
- ``trace.dropped_spans``                  spans evicted by per-thread
                                           span-buffer overflow
                                           (BF_SPAN_BUFFER saturation)
                                           — synthesized into
                                           ``telemetry.snapshot()``
                                           from the live buffers
- ``jit.compiles``                         compilations and loads from
                                           the persistent cache
                                           (jax.monitoring
                                           backend-compile events,
                                           telemetry.spans.watch_jax);
                                           one inside a steady window
                                           is a stall to explain
- ``jaxprof.captures``                     one-shot BF_JAX_PROFILE
                                           gulp captures taken
                                           (telemetry.profiling)

Multi-tenant service counters (bifrost_tpu.service — docs/service.md):

- ``service.submitted`` /
  ``service.admission.rejected``           tenant jobs admitted /
                                           refused at submit time
                                           (capacity, duplicate id,
                                           BF-E21x spec errors)
- ``service.<id>.admitted_gulps`` /
  ``service.<id>.admitted_bytes``          traffic the tenant's quota
                                           gate admitted (the
                                           per-tenant throughput
                                           ledger)
- ``service.<id>.quota_shed_gulps`` /
  ``service.<id>.quota_shed_bytes``        gulps a 'shed'-policy quota
                                           refused (counted loss at
                                           the ingest boundary)
- ``service.warm.hits`` /
  ``service.warm.rejected_stale``          warm starts granted /
                                           refused for a stale plan-
                                           signature mismatch
- ``service.affinity.applied`` /
  ``service.affinity.skipped``             per-block core assignments
                                           the partitioner applied /
                                           could not (empty pool)
- ``fused.plan_builds`` /
  ``fused.plan_depot_hits``                FusedBlock plan traces+
                                           compiles vs warm-start
                                           depot replays (a warm job's
                                           build delta is ZERO)
- ``autotune.profile_adoptions``           knob profiles pinned onto a
                                           new pipeline by
                                           autotune.adopt_profile
                                           (service warm starts)

Fleet observability counters (telemetry.fleet — docs/observability.md
"Fleet plane"):

- ``fleet.pub.msgs`` / ``fleet.pub.bytes``  snapshot messages / wire
                                           bytes a FleetPublisher sent
- ``fleet.pub.busy_us``                    publisher THREAD-CPU time
                                           spent building+sending
- ``fleet.pub.errors``                     publish/send/request
                                           failures (never raised)
- ``fleet.pub.events``                     out-of-band events pushed
                                           (health escalations, tenant
                                           transitions via note_event)
- ``fleet.pub.full_requests`` /
  ``fleet.pub.flight_replies``             collector ``need_full`` /
                                           ``flight_request`` messages
                                           answered
- ``fleet.msgs_rx`` / ``fleet.fulls_rx`` /
  ``fleet.deltas_rx`` / ``fleet.events_rx`` messages the collector
                                           ingested, by type
- ``fleet.decode_errors``                  corrupt/unparseable frames
                                           dropped at ingest
- ``fleet.need_full_tx``                   resync requests sent
                                           (unknown session, delta seq
                                           gap, collector restart)
- ``fleet.hosts_adopted``                  publisher sessions adopted
                                           into the rollup
- ``fleet.hosts_live``                     LEVEL: hosts currently
                                           fresh (inc'd by the signed
                                           per-tick change)
- ``fleet.hosts_stale_ticks``              ticks a host sat stale but
                                           not yet dead
- ``fleet.hosts_dead``                     hosts promoted to DEAD
                                           (membership verdict or
                                           final+stale), once each
- ``fleet.tick_errors``                    collector tick exceptions
- ``alerts.fired`` / ``alerts.resolved``   FIRING / RESOLVED
                                           transitions out of the
                                           AlertEngine state machines
- ``alerts.suppressed``                    repeat-bad ticks deduped
                                           while already firing
- ``alerts.sink_errors``                   alert-log/webhook delivery
                                           failures (never raised)
- ``incident.bundles``                     black-box bundles archived
                                           by the IncidentRecorder
- ``incident.suppressed``                  triggers absorbed by the
                                           per-reason cooldown
- ``incident.errors``                      bundle write failures
"""

from __future__ import annotations

import threading
from collections import defaultdict

__all__ = ['inc', 'get', 'snapshot', 'reset', 'set_gauge', 'gauges']

_lock = threading.Lock()
_counts = defaultdict(int)
_gauges = {}


def inc(name, n=1):
    """Add ``n`` to counter ``name`` (thread-safe)."""
    with _lock:
        _counts[name] += n


def get(name):
    """Current value of counter ``name`` (0 if never incremented)."""
    with _lock:
        return _counts.get(name, 0)


def snapshot():
    """Copy of all counters as a plain dict."""
    with _lock:
        return dict(_counts)


def reset():
    """Zero all counters and forget all gauges (tests/benchmarks)."""
    with _lock:
        _counts.clear()
        _gauges.clear()


def set_gauge(name, value):
    """Set gauge ``name``: a level (bytes held, a capacity), which the
    next ``set_gauge`` replaces and nothing adds to."""
    with _lock:
        _gauges[name] = value


def gauges():
    """Copy of all gauges as a plain dict."""
    with _lock:
        return dict(_gauges)
