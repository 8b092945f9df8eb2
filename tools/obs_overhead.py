#!/usr/bin/env python3
"""Observability overhead gate: span tracing must stay cheap.

Runs bench_suite config 8 (the async-transfer gulp loop — the hottest
host-side path in the framework) in fresh subprocesses, ``--reps``
interleaved repetitions per arm: the Chrome-trace export OFF (the
default) vs ON (``BF_TRACE_FILE`` set; span recording itself has no
switch and runs in both arms), then asserts the exporting arm's best
per-gulp time regressed by less than ``--threshold`` percent (default
5).  Two noise defenses, both necessary in practice: the arms compare
per-arm MINIMA (run-to-run spread on a busy host is 2x — far larger
than the real instrumentation cost, which microbenchmarks at ~1us per
span), and the arm ORDER alternates between repetitions (a fixed
base-first order phase-locks against slow machine-state drift —
CPU-frequency / allocator / page-cache cycles — and measured a
spurious 80% "overhead" that vanished under interleaving).  Every
sample plus the verdict is written to the ``--out`` JSON artifact so
bench rounds record the observability cost next to the throughput
numbers.

Exit codes: 0 pass, 3 overhead above threshold, 2 a bench arm failed
to produce a result.  ``tools/watch_and_bench.sh`` runs this after a
successful bench capture (``BF_SKIP_OBS_GATE=1`` opts out).
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: per-gulp metric the gate compares (bench_xfer_overlap output)
METRIC = 'async_ms_per_gulp'

#: per-gulp metric of the ringcheck arm (the timed config-8 chain —
#: the ring-protocol checker's seams live on the ring span path, which
#: bench_xfer_overlap's raw engine loop never touches)
CHAIN_METRIC = 'chain_ms_per_gulp'

_CHAIN_SNIPPET = (
    "import json, sys; sys.path.insert(0, %r); "
    "from bench_suite import _timed_config8_chain as t; "
    "from bifrost_tpu.telemetry import counters, fleet; "
    "pub = fleet.acquire_publisher(); "
    "n = %%d; dt = t(ngulp=n); "
    "fleet.release_publisher(pub) if pub else None; "
    "print(json.dumps({'chain_ms_per_gulp': dt / n * 1e3, "
    "'wall_s': dt, "
    "'tuner_cpu_us': counters.get('autotune.tick_busy_us'), "
    "'fleet_pub_cpu_us': counters.get('fleet.pub.busy_us')}))"
    % ROOT)


def run_chain(armed, timeout=1800, stack='ringcheck',
              collector_port=None):
    """One timed config-8 chain run through a REAL pipeline
    (bench_suite._timed_config8_chain) with the stack under test
    armed or not — the measurement arm for ``--stack ringcheck``,
    ``--stack autotune`` and ``--stack fleet``.  The autotune arm runs
    the closed-loop controller with every knob ceiling pinned at the
    chain's current configuration (no retune can fire): the pure
    converged-controller cost the <2% acceptance bound in
    docs/autotune.md refers to, measured in fresh subprocesses where
    nothing else perturbs the arms.  The fleet arm streams the
    subprocess's telemetry to ``collector_port`` (an in-process
    FleetCollector in THIS process) at a 4Hz publish interval — the
    streaming-publish bound of docs/observability.md "Fleet plane"."""
    env = dict(os.environ)
    for knob in ('BF_TRACE_FILE', 'BF_WATCHDOG_SECS',
                 'BF_WATCHDOG_ESCALATE', 'BF_METRICS_FILE',
                 'BF_SLO_MS', 'BF_JAX_PROFILE', 'BF_RINGCHECK',
                 'BF_AUTOTUNE', 'BF_AUTOTUNE_PROFILE',
                 'BF_AUTOTUNE_INTERVAL', 'BF_AUTOTUNE_COOLDOWN',
                 'BF_AUTOTUNE_MIN_GAIN', 'BF_AUTOTUNE_MAX_BATCH',
                 'BF_AUTOTUNE_MAX_DEPTH', 'BF_AUTOTUNE_MAX_WINDOW',
                 'BF_AUTOTUNE_MAX_RING_BYTES', 'BF_FLEET_COLLECTOR',
                 'BF_FLEET_INTERVAL', 'BF_FLEET_HOST',
                 'BF_FLEET_FULL_EVERY'):
        env.pop(knob, None)
    if armed and stack == 'ringcheck':
        env['BF_RINGCHECK'] = '1'
    elif armed and stack == 'fleet':
        env['BF_FLEET_COLLECTOR'] = '127.0.0.1:%d' % collector_port
        env['BF_FLEET_INTERVAL'] = '0.25'
        env['BF_FLEET_HOST'] = 'obsgate'
    elif armed:
        # ceilings pinned at the chain's own config (K=1,
        # sync_depth=4): every step() returns None, so each knob
        # converges without a retune and the controller idles at the
        # deployment-default tick — pure converged overhead
        env['BF_AUTOTUNE'] = '1'
        env['BF_AUTOTUNE_MAX_BATCH'] = '1'
        env['BF_AUTOTUNE_MAX_DEPTH'] = '4'
        env['BF_AUTOTUNE_MAX_RING_BYTES'] = '1'
        env['BF_AUTOTUNE_PROFILE'] = os.path.join(
            tempfile.mkdtemp(prefix='bf_tune_gate_'), 'unused.json')
    # the autotune arm measures a FIXED per-run cost (controller
    # start/stop + the final telemetry pass, ~tens of ms) on top of a
    # negligible steady-state cost (a tick microbenchmarks at
    # ~0.3ms against a 0.5s interval): a long chain amortizes the
    # fixed part the way a real long-lived deployment does AND
    # shrinks the chain's per-run scheduling jitter below the 2%
    # bound (+-1% at this length, vs +-4% at 48 gulps), so the gate
    # judges the steady state rather than the thread setup or the
    # host's mood
    ngulp = 1920 if stack in ('autotune', 'fleet') else 48
    out = subprocess.run([sys.executable, '-c',
                          _CHAIN_SNIPPET % ngulp],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=timeout)
    for line in out.stdout.splitlines():
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict) and CHAIN_METRIC in d:
            return d
    raise RuntimeError(
        'timed chain produced no %s result (rc=%d):\n%s\n%s'
        % (CHAIN_METRIC, out.returncode, out.stdout[-1000:],
           out.stderr[-1000:]))


def run_config8(trace_file=None, timeout=1800, full_stack=False):
    """One bench_suite --config 8 subprocess; returns its result dict.
    ``trace_file`` set -> span recording on (plus the export cost);
    ``full_stack`` additionally arms trace-context stamping and
    BF_SLO_MS budget tracking on the traced arm (and explicitly
    disables the context on the baseline arm, since stamping defaults
    on) — the ``--stack full`` mode."""
    env = dict(os.environ)
    # strip EVERY knob that toggles span recording or adds publisher
    # work, so the baseline arm is genuinely instrumentation-off (an
    # inherited BF_WATCHDOG_SECS would arm the flight recorder and
    # make the gate compare on-vs-on)
    for knob in ('BF_TRACE_FILE', 'BF_WATCHDOG_SECS',
                 'BF_WATCHDOG_ESCALATE', 'BF_METRICS_FILE',
                 'BF_SLO_MS', 'BF_TRACE_CONTEXT', 'BF_JAX_PROFILE'):
        env.pop(knob, None)
    if trace_file is not None:
        env['BF_TRACE_FILE'] = trace_file
        if full_stack:
            env['BF_TRACE_CONTEXT'] = '1'
            env['BF_SLO_MS'] = '10000'
    elif full_stack:
        env['BF_TRACE_CONTEXT'] = '0'
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'bench_suite.py'),
         '--config', '8'],
        capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=timeout)
    for line in out.stdout.splitlines():
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict) and METRIC in d:
            return d
    raise RuntimeError(
        'config 8 produced no %s result (rc=%d):\n%s\n%s'
        % (METRIC, out.returncode, out.stdout[-1000:],
           out.stderr[-1000:]))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--out', default='BENCH_OBS.json',
                    help='artifact path (all samples + verdict)')
    ap.add_argument('--threshold', type=float, default=None,
                    help='max allowed regression in percent (default '
                         '5; --stack ringcheck defaults to 50 — a '
                         'debug tool gets a generous, but still '
                         'measured and recorded, bound)')
    ap.add_argument('--reps', type=int, default=4,
                    help='interleaved repetitions per arm '
                         '(minima are compared; order alternates)')
    ap.add_argument('--timeout', type=float, default=1800.0,
                    help='per-run bench timeout in seconds')
    ap.add_argument('--stack', choices=('spans', 'full', 'ringcheck',
                                        'autotune', 'fleet'),
                    default='spans',
                    help="what the traced arm enables: 'spans' (the "
                         "classic PR-3 gate), 'full' (spans + "
                         "trace-context stamping + BF_SLO_MS "
                         "tracking; baseline arm runs "
                         "BF_TRACE_CONTEXT=0), 'ringcheck' (the "
                         "dynamic ring-protocol checker BF_RINGCHECK=1 "
                         "on the timed config-8 PIPELINE chain, whose "
                         "ring spans are where the checker's seams "
                         "live — docs/analysis.md), or 'autotune' "
                         "(the closed-loop controller with every "
                         "knob ceiling pinned on the same chain — "
                         "the converged-controller bound of "
                         "docs/autotune.md, default threshold 2), or "
                         "'fleet' (streaming telemetry publisher "
                         "pushing 4Hz snapshots to an in-process "
                         "collector on the same chain — the <2% "
                         "streaming-publish bound of "
                         "docs/observability.md).  The chain-level "
                         "full-stack bar lives in tools/e2e_gate.py; "
                         "'spans'/'full' bound the same knobs on the "
                         "config-8 transfer loop.")
    args = ap.parse_args()
    if args.threshold is None:
        args.threshold = {'ringcheck': 50.0,
                          'autotune': 2.0,
                          'fleet': 2.0}.get(args.stack, 5.0)

    trace_tmp = os.path.join(tempfile.mkdtemp(prefix='bf_obs_gate_'),
                             'trace.json')
    full = args.stack == 'full'
    chain = args.stack in ('ringcheck', 'autotune', 'fleet')
    metric = CHAIN_METRIC if chain else METRIC
    collector = None
    if args.stack == 'fleet':
        # the receiving end lives HERE: the armed subprocess streams
        # to this collector, so the gate also proves the datagrams
        # actually arrive (fleet.msgs_rx below) rather than timing a
        # publisher shouting into a closed port
        sys.path.insert(0, ROOT)
        from bifrost_tpu.telemetry import fleet as _fleet
        collector = _fleet.FleetCollector(rules=[], interval=0.25)
        collector.start()
    base_runs, traced_runs = [], []
    try:
        for rep in range(max(args.reps, 1)):
            order = [(base_runs, False), (traced_runs, True)]
            if rep % 2:
                order.reverse()
            for runs, armed in order:
                if chain:
                    runs.append(run_chain(
                        armed, timeout=args.timeout, stack=args.stack,
                        collector_port=collector.port
                        if collector else None))
                else:
                    runs.append(run_config8(
                        trace_tmp if armed else None,
                        timeout=args.timeout, full_stack=full))
    except (RuntimeError, subprocess.TimeoutExpired) as exc:
        print('obs_overhead: bench arm failed: %s' % exc,
              file=sys.stderr)
        return 2
    finally:
        msgs_rx = 0
        if collector is not None:
            from bifrost_tpu.telemetry import counters as _counters
            msgs_rx = _counters.get('fleet.msgs_rx')
            collector.stop()
    if args.stack == 'fleet' and not msgs_rx:
        print('obs_overhead: fleet arm streamed no telemetry to the '
              'collector (fleet.msgs_rx == 0)', file=sys.stderr)
        return 2

    b = min(float(r[metric]) for r in base_runs)
    t = min(float(r[metric]) for r in traced_runs)
    ab_pct = None
    if args.stack in ('autotune', 'fleet'):
        # the BINDING number is the stack's directly-metered busy
        # time (autotune.tick_busy_us / fleet.pub.busy_us — a
        # conservative upper bound including the background thread's
        # own GIL waits) as a fraction of the pipeline wall:
        # deterministic to well under the 2% bound.
        # An A/B wall-clock comparison cannot certify 2% on a shared
        # CI host — adjacent same-length runs here spread by +-10%
        # under contention — so the drift-robust paired median of the
        # arms is recorded as a cross-check, not the verdict
        ratios = sorted(float(t_[metric]) / float(b_[metric])
                        for b_, t_ in zip(base_runs, traced_runs))
        ab_pct = (ratios[len(ratios) // 2] - 1.0) * 100.0
        cpu_key = 'tuner_cpu_us' if args.stack == 'autotune' \
            else 'fleet_pub_cpu_us'
        cpu = max(float(r.get(cpu_key) or 0)
                  for r in traced_runs) / 1e6
        wall = min(float(r.get('wall_s') or 0)
                   for r in traced_runs)
        overhead_pct = cpu / wall * 100.0 if wall > 0 else 0.0
    else:
        overhead_pct = (t / b - 1.0) * 100.0 if b > 0 else 0.0
    ok = overhead_pct < args.threshold
    artifact = {
        'metric': metric,
        'stack': args.stack,
        'reps': len(base_runs),
        'spans_disabled_ms': [float(r[metric]) for r in base_runs],
        'spans_enabled_ms': [float(r[metric]) for r in traced_runs],
        'spans_disabled': base_runs[-1],
        'spans_enabled': traced_runs[-1],
        'min_disabled_ms': b,
        'min_enabled_ms': t,
        'overhead_pct': round(overhead_pct, 2),
        'ab_paired_median_pct': (round(ab_pct, 2)
                                 if ab_pct is not None else None),
        'threshold_pct': args.threshold,
        'pass': ok,
        'round': os.environ.get('BF_BENCH_ROUND', ''),
        'trace_events_written': os.path.exists(trace_tmp),
    }
    if args.stack == 'fleet':
        artifact['fleet_msgs_rx'] = msgs_rx
    with open(args.out, 'w') as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write('\n')
    extra = ('' if ab_pct is None
             else ' [metered CPU; A/B paired median %+.2f%%]'
             % ab_pct)
    print('obs_overhead: %s min-of-%d: %.3fms off / %.3fms on -> '
          '%+.2f%% (threshold %.1f%%)%s %s'
          % (metric, len(base_runs), b, t, overhead_pct,
             args.threshold, extra, 'PASS' if ok else 'FAIL'))
    return 0 if ok else 3


if __name__ == '__main__':
    sys.exit(main())
