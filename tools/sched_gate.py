#!/usr/bin/env python3
"""Control-plane gate: the elastic scheduler must survive losing a
host mid-stream without losing bytes, recompiling, or deadlocking.

Runs bench_suite config 20 (bifrost_tpu.scheduler —
docs/scheduler.md: three tenants placed across a 3-host fabric, the
victim tenant in a REAL subprocess acking a durable AckLedger
frontier, SIGKILLed mid-stream) in a fresh subprocess pinned to the
CPU backend, and asserts:

- ``placement_pre_gated``       — the initial plan passed the joint
  ``verify_placement`` pre-gate (no BF-E22x) before launch;
- ``death_detected``            — the head's Membership declared the
  killed host dead;
- ``replacement_automatic``     — the death-watch re-placed the
  victim onto a survivor and it ran to DONE with no operator step;
- ``warm_zero_recompiles``      — the migration was a warm start:
  zero ``fused.plan_builds``, >= 1 plan-depot hit, job flagged warm;
- ``resume_bounded_loss``       — the resume skipped exactly the
  ledger frontier (0 < F < total), counted on
  ``scheduler.resume.skipped_frames``;
- ``byte_exact``                — produced == acked-before-death +
  delivered-after-resume, and the resumed payload equals the source
  tail byte-for-byte;
- ``displaced_sheds_not_deadlocks`` — the lowest-priority tenant on
  the oversubscribed survivor was displaced and SHED by policy
  (counted) while still finishing DONE;
- ``arbiter_restored_slo``      — the cross-tenant arbiter moved
  quota from the donor to the SLO violator and the violator's
  rollup returned under budget within the run;
- ``scheduler_telemetry``       — the ``scheduler`` snapshot section
  recorded the re-placement.

The full config result is written to the ``--out`` JSON artifact
(``SCHED_CHAOS_${ROUND}.json``) so bench rounds record the control
plane's health next to the throughput numbers.

Exit codes: 0 pass, 3 an invariant failed, 2 the drill failed to
run.  ``tools/watch_and_bench.sh`` runs this after the service gate
(``BF_SKIP_SCHED_GATE=1`` opts out).
"""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_config20(timeout=900):
    """One bench_suite --config 20 subprocess on the CPU backend;
    returns its result dict."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    # configured fault/quota/tuning knobs would skew the scripted
    # drill; ambient fabric identity/state would leak into the
    # drill's own spec; BF_SEGMENTS would swap the warm chain's
    # FusedBlocks for SegmentBlocks (no plan depot -> spurious
    # recompiles)
    for var in ('BF_FAULTS', 'BF_OVERLOAD_POLICY', 'BF_SLO_MS',
                'BF_AUTOTUNE', 'BF_SERVE_MAX_TENANTS',
                'BF_SERVE_WARM', 'BF_SERVE_QUOTA_BURST',
                'BF_GULP_BATCH', 'BF_SYNC_DEPTH', 'BF_SEGMENTS',
                'BF_FABRIC_STATE',
                'BF_FABRIC_IDENTITY', 'BF_FABRIC_HEARTBEAT_SECS',
                'BF_FABRIC_DEADLINE_SECS', 'BF_SCHED_REBALANCE_SECS',
                'BF_SCHED_DISPLACE_QUOTA_FRAC',
                'BF_SCHED_MAX_REPLACEMENTS', 'BF_SCHED_ARBITER_FRAC'):
        env.pop(var, None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'bench_suite.py'),
         '--config', '20'],
        capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=timeout)
    for line in out.stdout.splitlines():
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict) and 'invariants' in d:
            return d
    raise RuntimeError(
        'config 20 produced no invariants result (rc=%d):\n%s\n%s'
        % (out.returncode, out.stdout[-1200:], out.stderr[-1200:]))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--out', default='SCHED_CHAOS_cpu.json',
                    help='artifact path for the full config result')
    ap.add_argument('--timeout', type=int, default=900)
    args = ap.parse_args(argv)
    if os.environ.get('BF_SKIP_SCHED_GATE', '0') == '1':
        print('sched_gate: skipped (BF_SKIP_SCHED_GATE=1)')
        return 0
    try:
        res = run_config20(timeout=args.timeout)
    except Exception as exc:
        print('sched_gate: drill failed to run: %s: %s'
              % (type(exc).__name__, exc))
        return 2
    res['round'] = os.environ.get('BF_BENCH_ROUND', '')
    with open(args.out, 'w') as f:
        json.dump(res, f, indent=2, sort_keys=True)
        f.write('\n')
    inv = res.get('invariants', {})
    for name in sorted(inv):
        print('%-30s %s' % (name, 'ok' if inv[name] else 'FAIL'))
    print('ledger: %s' % json.dumps(res.get('ledger', {}),
                                    sort_keys=True))
    print('migration: %s' % json.dumps(res.get('migration', {}),
                                       sort_keys=True))
    ok = bool(inv) and all(inv.values())
    print('sched_gate: %s -> %s' % ('PASS' if ok else 'FAIL',
                                    args.out))
    return 0 if ok else 3


if __name__ == '__main__':
    sys.exit(main())
