#!/usr/bin/env python3
"""Service-tier gate: the multi-tenant JobManager must hold its
isolation, quota, and warm-start invariants.

Runs bench_suite config 18 (bifrost_tpu.service — docs/service.md: 3
concurrent tenant jobs — serialized-recording replay at loop=3, flat
binary file ingest, and a paced synthetic capture — with paced
token-bucket quotas and one tenant killed mid-run by ``BF_FAULTS``)
in a fresh subprocess pinned to the CPU backend, and asserts:

- ``tenants_concurrent``       — the three jobs genuinely overlapped;
- ``outputs_byte_correct``     — replay and file tenants delivered
  byte-exact streams (replay: 3 identical renumbered loops), the
  killed tenant a clean prefix;
- ``fault_tenant_failed`` / ``fault_contained`` — the BF_FAULTS
  tenant FAILED while both survivors finished DONE with health OK;
- ``zero_cross_tenant_shed`` / ``zero_cross_tenant_poison`` — the
  blast radius stopped at the failed tenant's own rings: survivors
  show zero shed and zero poisoned rings;
- ``quota_within_10pct``       — both paced per-tenant quotas were
  enforced within 10% of spec;
- ``warm_speedup_ge2`` / ``warm_zero_recompiles`` /
  ``warm_profile_adopted`` — a resubmitted identical topology
  started >= 2x faster than its cold run with ZERO
  ``fused.plan_builds`` (plan-depot replay) and an adopted knob
  profile, byte-identical output;
- ``tenants_telemetry``        — ``telemetry.snapshot()['tenants']``
  carried every tenant's rollup.

The full config result is written to the ``--out`` JSON artifact
(``SERVICE_${ROUND}.json``) so bench rounds record the service tier's
health next to the throughput numbers.

Exit codes: 0 pass, 3 an invariant failed, 2 the drill failed to run.
``tools/watch_and_bench.sh`` runs this after the fabric gate
(``BF_SKIP_SERVICE_GATE=1`` opts out).
"""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_config18(timeout=900):
    """One bench_suite --config 18 subprocess on the CPU backend;
    returns its result dict."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    # configured fault/quota/tuning knobs would skew the scripted drill
    # BF_SEGMENTS would replace the warm chain's FusedBlocks with
    # fresh SegmentBlocks (no plan depot -> spurious recompiles)
    for var in ('BF_FAULTS', 'BF_OVERLOAD_POLICY', 'BF_SLO_MS',
                'BF_AUTOTUNE', 'BF_SERVE_MAX_TENANTS',
                'BF_SERVE_WARM', 'BF_SERVE_QUOTA_BURST',
                'BF_GULP_BATCH', 'BF_SYNC_DEPTH', 'BF_SEGMENTS'):
        env.pop(var, None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'bench_suite.py'),
         '--config', '18'],
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
        'config 18 produced no invariants result (rc=%d):\n%s\n%s'
        % (out.returncode, out.stdout[-1200:], out.stderr[-1200:]))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--out', default='SERVICE_cpu.json',
                    help='artifact path for the full config result')
    ap.add_argument('--timeout', type=int, default=900)
    args = ap.parse_args(argv)
    if os.environ.get('BF_SKIP_SERVICE_GATE', '0') == '1':
        print('service_gate: skipped (BF_SKIP_SERVICE_GATE=1)')
        return 0
    try:
        res = run_config18(timeout=args.timeout)
    except Exception as exc:
        print('service_gate: drill failed to run: %s: %s'
              % (type(exc).__name__, exc))
        return 2
    res['round'] = os.environ.get('BF_BENCH_ROUND', '')
    with open(args.out, 'w') as f:
        json.dump(res, f, indent=2, sort_keys=True)
        f.write('\n')
    inv = res.get('invariants', {})
    for name in sorted(inv):
        print('%-26s %s' % (name, 'ok' if inv[name] else 'FAIL'))
    print('warm: %s' % json.dumps(res.get('warm', {}),
                                  sort_keys=True))
    print('quota err %%: %s' % json.dumps(
        res.get('quota_err_pct', {}), sort_keys=True))
    ok = bool(inv) and all(inv.values())
    print('service_gate: %s -> %s' % ('PASS' if ok else 'FAIL',
                                      args.out))
    return 0 if ok else 3


if __name__ == '__main__':
    sys.exit(main())
