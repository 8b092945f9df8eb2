#!/bin/bash
# Round-long TPU capture watcher (VERDICT r4 item 1).
#
# Probes the chip on a timer; at the first healthy probe it runs
# the full bench session and exits 0 so the caller can commit the
# artifacts immediately.  A probe that initializes but fails the matmul
# gate does NOT trigger a capture (tools/tpu_probe.py rc gate).
#
# The capture label comes from BF_BENCH_ROUND (default: rYYYYMMDD UTC of
# the capture), so artifacts are stamped with when they were measured
# instead of a hardcoded round number that silently goes stale.
#
# Artifacts on success (ROUND = $BF_BENCH_ROUND):
#   BENCH_${ROUND}.json       - the driver-format one-line JSON from bench.py
#   BENCH_SUITE_${ROUND}.json - per-config detail written by run_suite_into
#   BENCH_OBS_${ROUND}.json   - observability overhead gate (config 8 with
#                               spans on vs off; tools/obs_overhead.py)
#   BENCH_E2E_${ROUND}.json   - end-to-end observability gate (config 12:
#                               full-stack overhead on the config-8 chain +
#                               two-pipeline loopback SLO/trace-merge run;
#                               tools/e2e_gate.py)
#   BENCH_BATCH_${ROUND}.json - macro-gulp batch gate (config 9 on CPU:
#                               K=16 >= K=1 min-of-N, alternating arm
#                               order; tools/batch_gate.py)
#   BENCH_SEGMENT_${ROUND}.json - compiled-segment gate (config 16 on
#                               CPU: BF_SEGMENTS=auto fuses the unfused
#                               device chain into one program, byte-
#                               identical, zero member dispatches, both
#                               interior rings elided, no regression vs
#                               the hand-fused K=16 arm;
#                               tools/segment_gate.py)
#   BENCH_BEAM_${ROUND}.json  - quantized beamformer gate (config 13 on
#                               CPU: quantized winner beats the f32
#                               baseline arm, within accuracy class,
#                               deterministic; tools/beam_gate.py)
#   MULTICHIP_${ROUND}.json   - mesh pipeline gate (config 11 on an
#                               8-device host mesh: sharded arm matches
#                               single-device, zero-reshard plans;
#                               tools/mesh_gate.py)
#   VERIFY_GATE_${ROUND}.json - static verify gate (every pipeline-shaped
#                               bench topology + every example linted by
#                               the pipeline verifier; tools/verify_gate.py,
#                               strict: any BF-E fails the round up front)
#   CHAOS_SOAK_${ROUND}.json  - chaos/soak gate (config 15 on CPU: a
#                               bridged two-process pipeline under a
#                               scripted overload+kill+fault schedule —
#                               no deadlock, no silent loss, health
#                               SHEDDING->OK, p99 under BF_SLO_MS;
#                               tools/chaos_gate.py)
#   SERVICE_${ROUND}.json     - multi-tenant service gate (config 18 on
#                               CPU: 3 concurrent tenant jobs — replay
#                               + file ingest + synthetic capture —
#                               with paced quotas enforced within 10%,
#                               a BF_FAULTS-killed tenant contained
#                               (survivors DONE/OK, zero cross-tenant
#                               shed/poison), and a warm job start
#                               >= 2x faster than cold with 0
#                               recompiles; tools/service_gate.py)
#   FABRIC_CHAOS_${ROUND}.json - fabric chaos gate (config 17 on CPU:
#                               a 4-process loopback fabric survives a
#                               SIGKILL'd capture host — rejoin replays
#                               only unacked frames, dead origin gapped
#                               not stalled, produced == delivered +
#                               shed byte-exact; tools/fabric_gate.py)
#   SCHED_CHAOS_${ROUND}.json - elastic control-plane gate (config 20
#                               on CPU: a SIGKILL'd host's tenants
#                               re-place automatically onto survivors
#                               as warm zero-recompile starts resuming
#                               from the durable ledger frontier;
#                               displacement sheds by policy and the
#                               cross-tenant arbiter restores the SLO
#                               violator; tools/sched_gate.py)
#   bench_watch.log           - probe/attempt history (gitignored)
cd "$(dirname "$0")/.." || exit 1
ROUND="${BF_BENCH_ROUND:-r$(date -u +%Y%m%d)}"
export BF_BENCH_ROUND="$ROUND"
OUT="BENCH_${ROUND}.json"
LOG=bench_watch.log
echo "$(date -u +%FT%TZ) watcher start pid=$$ round=$ROUND" >> "$LOG"

# Tier-1 gate: run the CPU suite under a hard timeout with the stall
# watchdog armed.  A HUNG run (a regression back to the silent
# pipeline-hang failure mode — timeout rc 124/137) fails the watcher
# fast with a non-zero exit instead of wedging it for the whole round;
# ordinary test failures are logged but do not block the bench capture
# (the driver's own tier-1 gate judges those).  BF_SKIP_T1_GATE=1 opts
# out.
if [ "${BF_SKIP_T1_GATE:-0}" != "1" ]; then
  T1_TIMEOUT="${BF_T1_TIMEOUT:-870}"
  echo "$(date -u +%FT%TZ) tier-1 gate (timeout ${T1_TIMEOUT}s)" >> "$LOG"
  timeout -k 10 "$T1_TIMEOUT" env JAX_PLATFORMS=cpu \
    BF_WATCHDOG_SECS="${BF_WATCHDOG_SECS:-120}" BF_WATCHDOG_ESCALATE=1 \
    python -m pytest tests/ -q -m 'not slow' \
      --continue-on-collection-errors -p no:cacheprovider \
      > "t1_gate_${ROUND}.log" 2>&1
  t1rc=$?
  echo "$(date -u +%FT%TZ) tier-1 gate rc=$t1rc" >> "$LOG"
  if [ "$t1rc" -eq 124 ] || [ "$t1rc" -eq 137 ]; then
    echo "$(date -u +%FT%TZ) tier-1 HUNG past the watchdog timeout - failing fast" >> "$LOG"
    exit "$t1rc"
  fi
fi
# Static verify gate: lint every pipeline-shaped bench topology and
# every example with the pipeline verifier (tools/verify_gate.py ->
# tools/bf_lint.py).  Purely static — runs before the TPU probe loop
# so a misconfigured topology fails the round in seconds, not after a
# full capture.  BF_SKIP_VERIFY_GATE=1 opts out.
if [ "${BF_SKIP_VERIFY_GATE:-0}" != "1" ]; then
  echo "$(date -u +%FT%TZ) static verify gate (bench topologies + examples)" >> "$LOG"
  python tools/verify_gate.py --strict --out "VERIFY_GATE_${ROUND}.json" >> "$LOG" 2>&1
  vrc=$?
  echo "$(date -u +%FT%TZ) verify gate rc=$vrc" >> "$LOG"
  if [ "$vrc" -ne 0 ]; then
    echo "$(date -u +%FT%TZ) static verify gate FAILED" >> "$LOG"
    exit "$vrc"
  fi
fi
for i in $(seq 1 400); do
  out=$(BF_PROBE_DEADLINE=120 timeout 180 python tools/tpu_probe.py 2>/dev/null)
  rc=$?
  echo "$(date -u +%FT%TZ) probe[$i] rc=$rc $out" >> "$LOG"
  if [ "$rc" -eq 0 ]; then
    echo "$(date -u +%FT%TZ) healthy - starting full bench" >> "$LOG"
    timeout 5400 python bench.py > "$OUT.tmp" 2> "bench_${ROUND}.stderr"
    brc=$?
    echo "$(date -u +%FT%TZ) bench rc=$brc" >> "$LOG"
    if [ "$brc" -eq 0 ] && grep -q '"vs_baseline"' "$OUT.tmp" \
        && ! grep -q '"error": "jax backend' "$OUT.tmp"; then
      mv "$OUT.tmp" "$OUT"
      echo "$(date -u +%FT%TZ) capture OK -> $OUT" >> "$LOG"
      # Observability overhead gate: rerun bench_suite config 8 with
      # span recording on vs off and assert <5% per-gulp regression;
      # both runs land in BENCH_OBS_${ROUND}.json.  A failure exits
      # nonzero (the capture artifacts above are already in place).
      if [ "${BF_SKIP_OBS_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) observability overhead gate (config 8)" >> "$LOG"
        python tools/obs_overhead.py --out "BENCH_OBS_${ROUND}.json" >> "$LOG" 2>&1
        orc=$?
        echo "$(date -u +%FT%TZ) overhead gate rc=$orc" >> "$LOG"
        if [ "$orc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) observability overhead gate FAILED" >> "$LOG"
          exit "$orc"
        fi
      fi
      # End-to-end observability gate: config 12 on the CPU backend —
      # the FULL stack (trace context + spans + SLO tracking) must stay
      # under the 5% overhead bar on the config-8 chain, the two-
      # pipeline loopback run must produce one MERGED cross-host trace,
      # and the sink pipeline must report a capture-to-commit p99.
      # Writes BENCH_E2E_${ROUND}.json.  A failure exits nonzero.
      if [ "${BF_SKIP_E2E_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) e2e observability gate (config 12)" >> "$LOG"
        E2E_OUT="BENCH_E2E_${ROUND}.json"
        # keep the previous round's artifact for the regression sentinel
        E2E_PREV=""
        if [ -f "$E2E_OUT" ]; then
          E2E_PREV="${E2E_OUT}.prev"
          cp "$E2E_OUT" "$E2E_PREV"
        elif [ -f "BENCH_E2E_cpu.json" ]; then
          E2E_PREV="BENCH_E2E_cpu.json"
        fi
        python tools/e2e_gate.py --out "$E2E_OUT" >> "$LOG" 2>&1
        erc=$?
        echo "$(date -u +%FT%TZ) e2e gate rc=$erc" >> "$LOG"
        if [ "$erc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) e2e observability gate FAILED" >> "$LOG"
          exit "$erc"
        fi
        # Regression sentinel (ADVISORY): diff the fresh artifact
        # against the previous round's and log drifts beyond the
        # watchlist thresholds — the verdict is informational here
        # (tools/telemetry_diff.py --strict exists for CI that wants
        # a hard gate).
        if [ -n "$E2E_PREV" ]; then
          echo "$(date -u +%FT%TZ) telemetry drift sentinel vs $E2E_PREV (advisory)" >> "$LOG"
          python tools/telemetry_diff.py "$E2E_PREV" "$E2E_OUT" >> "$LOG" 2>&1 || true
          rm -f "${E2E_OUT}.prev"
        fi
      fi
      # Macro-gulp batch gate: config 9 on the CPU backend — K=16 must
      # not regress vs K=1 (min-of-N, alternating arm order) and the
      # dispatch amortization must actually engage.  A failure exits
      # nonzero (the capture artifacts above are already in place).
      if [ "${BF_SKIP_BATCH_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) macro-gulp batch gate (config 9, CPU)" >> "$LOG"
        python tools/batch_gate.py --out "BENCH_BATCH_${ROUND}.json" >> "$LOG" 2>&1
        grc=$?
        echo "$(date -u +%FT%TZ) batch gate rc=$grc" >> "$LOG"
        if [ "$grc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) macro-gulp batch gate FAILED" >> "$LOG"
          exit "$grc"
        fi
      fi
      # Compiled-segment gate: config 16 on the CPU backend — the
      # segment compiler must fuse the unfused device chain into ONE
      # program (byte-identical outputs, zero member-block dispatches,
      # both interior rings elided) and must not regress vs the
      # hand-fused macro K=16 arm.  A failure exits nonzero (the
      # capture artifacts above are already in place).
      if [ "${BF_SKIP_SEGMENT_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) compiled-segment gate (config 16, CPU)" >> "$LOG"
        python tools/segment_gate.py --out "BENCH_SEGMENT_${ROUND}.json" >> "$LOG" 2>&1
        sgc=$?
        echo "$(date -u +%FT%TZ) segment gate rc=$sgc" >> "$LOG"
        if [ "$sgc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) compiled-segment gate FAILED" >> "$LOG"
          exit "$sgc"
        fi
      fi
      # Auto-tune convergence gate: config 14 on the CPU backend — the
      # closed-loop controller must tune a de-tuned cold start (K=1,
      # sync=1) to within ~5% of the hand-tuned config-9 optimum with
      # byte-identical outputs, and the converged controller (no
      # retunes firing) must cost <2% on the hand-tuned arm.  The
      # converged knob values land in BENCH_TUNE_${ROUND}.json.  A
      # failure exits nonzero (the capture artifacts above are
      # already in place).
      if [ "${BF_SKIP_TUNE_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) auto-tune convergence gate (config 14, CPU)" >> "$LOG"
        python tools/autotune_gate.py --out "BENCH_TUNE_${ROUND}.json" >> "$LOG" 2>&1
        trc=$?
        echo "$(date -u +%FT%TZ) autotune gate rc=$trc" >> "$LOG"
        if [ "$trc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) auto-tune convergence gate FAILED" >> "$LOG"
          exit "$trc"
        fi
      fi
      # Quantized-beamformer gate: config 13 on the CPU backend — the
      # measured quantized winner must beat the f32 baseline arm on
      # the end-to-end chain (min-of-N, alternating arms), stay inside
      # the declared accuracy class, and be run-to-run deterministic.
      # A failure exits nonzero (the capture artifacts above are
      # already in place).
      if [ "${BF_SKIP_BEAM_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) quantized beamformer gate (config 13, CPU)" >> "$LOG"
        python tools/beam_gate.py --out "BENCH_BEAM_${ROUND}.json" >> "$LOG" 2>&1
        bmrc=$?
        echo "$(date -u +%FT%TZ) beam gate rc=$bmrc" >> "$LOG"
        if [ "$bmrc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) quantized beamformer gate FAILED" >> "$LOG"
          exit "$bmrc"
        fi
      fi
      # Ring-bridge wire gate: config 10 on the CPU backend — wire v2
      # (zero-copy, windowed) must not regress vs the naive v1 pump
      # and both arms must round-trip byte-identically.  A failure
      # exits nonzero (the capture artifacts above are already in
      # place).
      if [ "${BF_SKIP_BRIDGE_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) ring bridge wire gate (config 10, CPU)" >> "$LOG"
        python tools/bridge_gate.py --out "BENCH_BRIDGE_${ROUND}.json" >> "$LOG" 2>&1
        brg=$?
        echo "$(date -u +%FT%TZ) bridge gate rc=$brg" >> "$LOG"
        if [ "$brg" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) ring bridge wire gate FAILED" >> "$LOG"
          exit "$brg"
        fi
      fi
      # Chaos/soak gate: config 15 on CPU — a bridged two-process
      # pipeline under a scripted overload+kill+fault schedule must
      # never deadlock, account every lost byte in the shed ledgers
      # (no silent loss), traverse SHEDDING and recover to OK, and
      # keep the capture-to-exit p99 under BF_SLO_MS while shedding
      # (tools/chaos_gate.py; docs/robustness.md "Overload &
      # degradation").  Writes CHAOS_SOAK_${ROUND}.json.
      if [ "${BF_SKIP_CHAOS_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) chaos/soak gate (config 15, CPU)" >> "$LOG"
        python tools/chaos_gate.py --out "CHAOS_SOAK_${ROUND}.json" >> "$LOG" 2>&1
        crc_gate=$?
        echo "$(date -u +%FT%TZ) chaos gate rc=$crc_gate" >> "$LOG"
        if [ "$crc_gate" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) chaos/soak gate FAILED" >> "$LOG"
          exit "$crc_gate"
        fi
      fi
      # Fabric chaos gate: config 17 on CPU — a 4-process loopback
      # fabric (2 capture hosts fan-in to a reduce host, reduce
      # fans out to a leg through a chaos proxy) must survive a
      # SIGKILL'd capture host: survivors shed counted and recover
      # (SHEDDING -> OK), the relaunched host rejoins and replays
      # ONLY unacked frames (session adoption + resume probe), the
      # dead origin is marked gapped not stalled on, and produced ==
      # delivered + shed holds byte-exact across all surviving
      # ledgers (tools/fabric_gate.py; docs/fabric.md).  Writes
      # FABRIC_CHAOS_${ROUND}.json.
      if [ "${BF_SKIP_FABRIC_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) fabric chaos gate (config 17, CPU)" >> "$LOG"
        python tools/fabric_gate.py --out "FABRIC_CHAOS_${ROUND}.json" >> "$LOG" 2>&1
        frc_gate=$?
        echo "$(date -u +%FT%TZ) fabric gate rc=$frc_gate" >> "$LOG"
        if [ "$frc_gate" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) fabric chaos gate FAILED" >> "$LOG"
          exit "$frc_gate"
        fi
      fi
      # Multi-tenant service gate: config 18 on CPU — the JobManager
      # must run 3 concurrent tenant jobs with byte-correct outputs,
      # contain a BF_FAULTS-killed tenant (survivors DONE with health
      # OK, zero cross-tenant shed/poison), enforce the paced
      # per-tenant quotas within 10% of spec, and warm-start a
      # resubmitted topology >= 2x faster than cold with ZERO
      # recompiles (tools/service_gate.py; docs/service.md).  Writes
      # SERVICE_${ROUND}.json.
      if [ "${BF_SKIP_SERVICE_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) multi-tenant service gate (config 18, CPU)" >> "$LOG"
        python tools/service_gate.py --out "SERVICE_${ROUND}.json" >> "$LOG" 2>&1
        src_gate=$?
        echo "$(date -u +%FT%TZ) service gate rc=$src_gate" >> "$LOG"
        if [ "$src_gate" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) multi-tenant service gate FAILED" >> "$LOG"
          exit "$src_gate"
        fi
      fi
      # Elastic control-plane gate: config 20 on CPU — the scheduler
      # must pre-gate the cross-host placement (BF-E22x), detect a
      # SIGKILLed host, automatically re-place its tenant as a WARM
      # zero-recompile start resuming from the durable AckLedger
      # frontier (byte-exact, bounded counted loss), displace the
      # lowest-priority tenant on the oversubscribed survivor (shed
      # by policy, no deadlock), and restore an SLO violator through
      # the cross-tenant arbiter (tools/sched_gate.py;
      # docs/scheduler.md).  Writes SCHED_CHAOS_${ROUND}.json.
      if [ "${BF_SKIP_SCHED_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) elastic control-plane gate (config 20, CPU)" >> "$LOG"
        python tools/sched_gate.py --out "SCHED_CHAOS_${ROUND}.json" >> "$LOG" 2>&1
        sch_gate=$?
        echo "$(date -u +%FT%TZ) sched gate rc=$sch_gate" >> "$LOG"
        if [ "$sch_gate" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) elastic control-plane gate FAILED" >> "$LOG"
          exit "$sch_gate"
        fi
      fi
      # Fleet observability gate: config 21 on CPU — the streaming
      # telemetry plane must adopt both publishers, mark a SIGKILLed
      # host stale then DEAD (a never-seen host stays UNKNOWN), fire
      # and resolve the tenant-absence alert around the automatic
      # re-placement, archive a black-box bundle trace_merge consumes
      # directly, label the merged Prometheus export per host/tenant,
      # and keep streaming-publish overhead under 2% — also proven on
      # the config-8 chain by the obs_overhead fleet arm below
      # (tools/fleet_gate.py; docs/observability.md "Fleet plane").
      # Writes FLEET_OBS_${ROUND}.json + OBS_FLEET_${ROUND}.json.
      if [ "${BF_SKIP_FLEET_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) fleet observability gate (config 21, CPU)" >> "$LOG"
        python tools/fleet_gate.py --out "FLEET_OBS_${ROUND}.json" >> "$LOG" 2>&1
        flt_gate=$?
        echo "$(date -u +%FT%TZ) fleet gate rc=$flt_gate" >> "$LOG"
        if [ "$flt_gate" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) fleet observability gate FAILED" >> "$LOG"
          exit "$flt_gate"
        fi
        echo "$(date -u +%FT%TZ) fleet publish overhead arm (config-8 chain, CPU)" >> "$LOG"
        python tools/obs_overhead.py --stack fleet --reps 3 \
          --out "OBS_FLEET_${ROUND}.json" >> "$LOG" 2>&1
        flt_ovh=$?
        echo "$(date -u +%FT%TZ) fleet overhead rc=$flt_ovh" >> "$LOG"
        if [ "$flt_ovh" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) fleet publish overhead arm FAILED" >> "$LOG"
          exit "$flt_ovh"
        fi
      fi
      # Mesh-resident pipeline gate: config 11 on an 8-device
      # host-platform mesh — the sharded arm must match the
      # single-device arm, sharded spans must actually flow, and the
      # compiled mesh plans must be collective-free (zero reshards).
      # Writes MULTICHIP_${ROUND}.json (the revived multichip artifact
      # series).  A failure exits nonzero (the capture artifacts above
      # are already in place).
      if [ "${BF_SKIP_MESH_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) mesh pipeline gate (config 11, 8-dev host mesh)" >> "$LOG"
        python tools/mesh_gate.py --out "MULTICHIP_${ROUND}.json" >> "$LOG" 2>&1
        mrc=$?
        echo "$(date -u +%FT%TZ) mesh gate rc=$mrc" >> "$LOG"
        if [ "$mrc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) mesh pipeline gate FAILED" >> "$LOG"
          exit "$mrc"
        fi
      fi
      # FX-correlator flagship gate: config 19 — quantized X-engine
      # winner must beat the complex64 baseline, every arm must be
      # byte-identical to the sequential oracle, and the fused
      # segment arm must dispatch its member blocks ZERO times.
      # Writes BENCH_FXCORR_${ROUND}.json plus the mesh-scaling row
      # MULTICHIP_${ROUND}_fxcorr.json.
      if [ "${BF_SKIP_FXCORR_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) fx correlator gate (config 19, 8-dev host mesh)" >> "$LOG"
        python tools/fxcorr_gate.py --out "BENCH_FXCORR_${ROUND}.json" \
          --mesh-out "MULTICHIP_${ROUND}_fxcorr.json" >> "$LOG" 2>&1
        xrc=$?
        echo "$(date -u +%FT%TZ) fxcorr gate rc=$xrc" >> "$LOG"
        if [ "$xrc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) fx correlator gate FAILED" >> "$LOG"
          exit "$xrc"
        fi
      fi
      # FDMT FRB-search flagship gate: config 22 — all three arms
      # (unfused / halo-carried segment / segment at macro K) must be
      # byte-identical and match the float64 numpy oracle, the
      # ``overlap`` fusion boundary must be provably lifted (zero
      # member dispatches, zero interior-ring span traffic under
      # BF_RINGCHECK=1), and capture-to-candidate p99 must sit under
      # BF_SLO_MS.  Writes BENCH_FDMT_${ROUND}.json.
      if [ "${BF_SKIP_FDMT_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) fdmt frb-search gate (config 22)" >> "$LOG"
        python tools/fdmt_gate.py --out "BENCH_FDMT_${ROUND}.json" >> "$LOG" 2>&1
        frc=$?
        echo "$(date -u +%FT%TZ) fdmt gate rc=$frc" >> "$LOG"
        if [ "$frc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) fdmt frb-search gate FAILED" >> "$LOG"
          exit "$frc"
        fi
      fi
      # Wire-rate capture flagship gate: config 23 — the sharded
      # zero-copy UDP engine must sustain its loopback rate ladder at
      # <1% loss with an exact loss ledger, ring contents byte-equal
      # to the blaster oracle, and a paired-median win over the
      # staged single-thread arm.  Writes BENCH_CAPTURE_${ROUND}.json.
      if [ "${BF_SKIP_CAPTURE_GATE:-0}" != "1" ]; then
        echo "$(date -u +%FT%TZ) wire-rate capture gate (config 23)" >> "$LOG"
        python tools/capture_gate.py --out "BENCH_CAPTURE_${ROUND}.json" >> "$LOG" 2>&1
        crc=$?
        echo "$(date -u +%FT%TZ) capture gate rc=$crc" >> "$LOG"
        if [ "$crc" -ne 0 ]; then
          echo "$(date -u +%FT%TZ) wire-rate capture gate FAILED" >> "$LOG"
          exit "$crc"
        fi
      fi
      exit 0
    fi
    # never leave a truncated artifact where round automation could
    # commit it as if it were real
    rm -f "$OUT.tmp"
    echo "$(date -u +%FT%TZ) bench attempt failed; continuing watch" >> "$LOG"
  fi
  sleep 240
done
echo "$(date -u +%FT%TZ) watcher exhausted retries" >> "$LOG"
exit 1
