#!/usr/bin/env python3
"""End-to-end observability gate: the FULL distributed-observability
stack (trace context + span recording/export + capture-to-commit SLO
tracking) must stay under the overhead bar, and the cross-host
machinery must actually work.

Runs bench_suite config 12 (bench_e2e_observability) in a fresh
subprocess pinned to the CPU backend and asserts:

- ``overhead_ok``   — the full-stack overhead on the config-8 fused
  chain is under ``--threshold`` percent (default 5).  The judged
  number is the MEDIAN OF PER-REP PAIRED RATIOS (each rep runs both
  arms back to back, so the ratio cancels the slow machine-state
  drift that dominates run-to-run spread on shared hosts); the
  classic min-of-N ratio and the baseline arm's spread are recorded
  in the artifact for context.
- ``merged_trace_ok`` — the two-pipeline loopback bridge run produced
  one merged Chrome trace (tools/trace_merge.py) where at least one
  (trace id, seq, gulp) identity appears on BOTH hosts' timelines.
- ``slo_tracked``   — the sink pipeline's ``telemetry.snapshot()``
  reported a capture-to-commit p99 (the ``slo.exit_age_s`` histogram
  is populated).

The full config result lands in the ``--out`` JSON artifact
(``BENCH_E2E_${ROUND}.json`` from the watcher).

Exit codes: 0 pass, 3 a gate condition failed, 2 the bench arm failed
to produce a result.  ``tools/watch_and_bench.sh`` runs this after the
observability gate (``BF_SKIP_E2E_GATE=1`` opts out).
"""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_config12(timeout=1800):
    """One bench_suite --config 12 subprocess on the CPU backend;
    returns its result dict."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    # a configured observability environment would contaminate the
    # arms (the config manages these knobs itself)
    for var in ('BF_TRACE_FILE', 'BF_TRACE_CONTEXT',
                'BF_SLO_MS', 'BF_METRICS_FILE', 'BF_WATCHDOG_SECS',
                'BF_JAX_PROFILE'):
        env.pop(var, None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'bench_suite.py'),
         '--config', '12'],
        capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=timeout)
    for line in out.stdout.splitlines():
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict) and 'overhead' in d:
            return d
    raise RuntimeError(
        'config 12 produced no overhead result (rc=%d):\n%s\n%s'
        % (out.returncode, out.stdout[-1000:], out.stderr[-1000:]))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--out', default='BENCH_E2E.json',
                    help='artifact path (full config-12 result + '
                         'verdict)')
    ap.add_argument('--threshold', type=float, default=5.0,
                    help='max allowed full-stack overhead in percent '
                         '(paired-median estimator)')
    ap.add_argument('--timeout', type=float, default=1800.0,
                    help='bench subprocess timeout in seconds')
    args = ap.parse_args()

    try:
        res = run_config12(timeout=args.timeout)
    except (RuntimeError, subprocess.TimeoutExpired) as exc:
        print('e2e_gate: bench arm failed: %s' % exc, file=sys.stderr)
        return 2

    ov = res['overhead']
    overhead_pct = float(ov.get('overhead_pct', 0.0))
    overhead_ok = overhead_pct < args.threshold
    merged_ok = bool(res.get('merged_trace_ok'))
    slo_ok = bool(res.get('slo_tracked'))
    ok = overhead_ok and merged_ok and slo_ok
    artifact = dict(res,
                    gate={'overhead_pct': round(overhead_pct, 2),
                          'min_ratio_pct': ov.get('min_ratio_pct'),
                          'off_arm_spread_pct':
                              ov.get('off_arm_spread_pct'),
                          'threshold_pct': args.threshold,
                          'overhead_ok': overhead_ok,
                          'merged_trace_ok': merged_ok,
                          'slo_tracked': slo_ok,
                          'pass': ok,
                          'round': os.environ.get('BF_BENCH_ROUND',
                                                  '')})
    with open(args.out, 'w') as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
        f.write('\n')
    two_host = res.get('two_host', {})
    print('e2e_gate: full-stack overhead %+.2f%% paired-median '
          '(min-ratio %+.2f%%, off-arm spread %.1f%%, threshold '
          '%.1f%%), merged_trace=%s (%d shared identities), '
          'slo p99=%.2fms %s'
          % (overhead_pct, float(ov.get('min_ratio_pct', 0.0)),
             float(ov.get('off_arm_spread_pct', 0.0)),
             args.threshold, merged_ok,
             int(two_host.get('shared_identities', 0)),
             float(res.get('value', 0.0)),
             'PASS' if ok else 'FAIL'))
    return 0 if ok else 3


if __name__ == '__main__':
    sys.exit(main())
