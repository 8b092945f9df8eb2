#!/usr/bin/env python3
"""bf_lint: run the static pipeline verifier over a pipeline script
WITHOUT running the pipeline (docs/analysis.md).

    python tools/bf_lint.py examples/fdmt_search.py
    python tools/bf_lint.py --codes

The script runs in a subprocess with ``BF_LINT=1``,
which makes every ``Pipeline.run()`` validate the constructed
block/ring graph, report its diagnostics, and return WITHOUT launching
block threads — the script executes end to end as a pure topology
builder.  Post-run script logic that expects real output may fail;
that is tolerated as long as at least one pipeline was linted (the
diagnostics were already captured through ``BF_LINT_OUT``).  The
topologies the repo ships are held clean by
``tests/test_analysis.py::test_shipped_topologies_validate_clean``.

Exit codes (matching tools/telemetry_diff.py's convention): 0 =
advisory mode, or strict mode with no ``BF-E``; 3 = ``--strict`` and
at least one ``BF-E`` diagnostic; 2 = the target could not be linted
at all (script crashed before building a pipeline).
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def lint_script(path, args, timeout):
    """Run ``path`` under BF_LINT=1; returns (records, proc) where
    records is the list of per-pipeline diagnostic dicts collected via
    BF_LINT_OUT."""
    out = tempfile.NamedTemporaryFile(prefix='bf_lint_', suffix='.jsonl',
                                      delete=False)
    out.close()
    env = dict(os.environ)
    env['BF_LINT'] = '1'
    env['BF_LINT_OUT'] = out.name
    env.setdefault('JAX_PLATFORMS', 'cpu')
    proc = subprocess.run([sys.executable, path] + list(args),
                          capture_output=True, text=True, env=env,
                          cwd=ROOT, timeout=timeout)
    records = []
    try:
        with open(out.name) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        records.append(json.loads(line))
                    except ValueError:
                        pass
    finally:
        os.unlink(out.name)
    return records, proc


def summarize(records, label, show_info=False):
    ne = nw = ni = 0
    for rec in records:
        for d in rec['diagnostics']:
            sev = d['severity']
            ne += sev == 'error'
            nw += sev == 'warning'
            ni += sev == 'info'
            if sev == 'info' and not show_info:
                continue
            where = d.get('block') or ''
            if d.get('ring'):
                where += ('@' if where else '') + 'ring:%s' % d['ring']
            print('%s %-9s %-40s %s' % (d['code'], sev, where,
                                        d['message']))
    print('bf_lint: %s — %d pipeline(s), %d error(s), %d warning(s), '
          '%d info' % (label, len(records), ne, nw, ni))
    return ne


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('script', nargs='?',
                    help='pipeline script to lint (BF_LINT=1 mode)')
    ap.add_argument('script_args', nargs=argparse.REMAINDER,
                    help='arguments passed through to the script')
    ap.add_argument('--codes', action='store_true',
                    help='print the diagnostic-code catalog and exit')
    ap.add_argument('--strict', action='store_true',
                    help='exit 3 when any BF-E diagnostic is reported '
                         '(default: advisory, exit 0)')
    ap.add_argument('--show-info', action='store_true',
                    help='print BF-I info diagnostics too')
    ap.add_argument('--timeout', type=float, default=300.0,
                    help='script-mode subprocess timeout (seconds)')
    args = ap.parse_args()

    if args.codes:
        from bifrost_tpu.analysis.verify import CODES
        for code in sorted(CODES):
            print('%s  %s' % (code, CODES[code]))
        return 0
    if not args.script:
        print('bf_lint: a script path is required (see --help)',
              file=sys.stderr)
        return 2
    try:
        records, proc = lint_script(args.script, args.script_args,
                                    args.timeout)
    except subprocess.TimeoutExpired:
        print('bf_lint: %s timed out' % args.script, file=sys.stderr)
        return 2
    if not records:
        print('bf_lint: %s built no pipeline under BF_LINT=1 '
              '(rc=%d)\n%s' % (args.script, proc.returncode,
                               proc.stderr[-2000:]), file=sys.stderr)
        return 2
    nerr = summarize(records, args.script, args.show_info)
    return 3 if (args.strict and nerr) else 0


if __name__ == '__main__':
    sys.exit(main())
