#!/usr/bin/env python3
"""Repo-invariant lint: every ``BF_*`` environment variable read by
``bifrost_tpu/`` must be documented in ``docs/envvars.md``, and every
documented variable must actually be read somewhere in the repo
(package, tools or tests) — no phantom knobs, no undocumented
behavior.

    python tools/lint_envvars.py            # report; exit 0/3
    pytest tests/test_tools.py -k envvars   # the tier-1 wiring

Detection: a QUOTED string literal matching ``BF_[A-Z0-9_]+`` in
Python source is an env read (the package's accessors —
``os.environ``, ``_env_int``/``_env_float``, ``EnvVars.get``,
``_force_env`` — all take the name as a string literal; counter/fault
names never start with BF_).  Docs side: any backticked ``BF_*`` token
in docs/envvars.md.

Exit codes follow tools/telemetry_diff.py: 0 = clean, 3 = violations.
"""

import argparse
import glob
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: quoted BF_ literal in Python source (an env read by construction in
#: this codebase; docstring prose mentions are unquoted)
_PY_READ = re.compile(r"""['"](BF_[A-Z0-9_]+)['"]""")
#: documented token in docs/envvars.md (backticked, possibly with a
#: `=value` suffix or `BF_*` glob-style family references)
_DOC = re.compile(r"`(BF_[A-Z0-9_]+)")

#: variables legitimately not read as literals anywhere scannable
#: (none today; the hook exists for e.g. native-core-only knobs)
ALLOW_UNREAD = set()
#: variables read by the package but intentionally undocumented
#: (none today)
ALLOW_UNDOCUMENTED = set()


def _py_files(*relative_dirs):
    out = []
    for d in relative_dirs:
        out.extend(glob.glob(os.path.join(ROOT, d, '**', '*.py'),
                             recursive=True))
    return [p for p in out if '__pycache__' not in p]


def package_reads():
    """BF_* vars read inside bifrost_tpu/ (the documented-API side of
    the invariant)."""
    vars_ = {}
    for path in _py_files('bifrost_tpu'):
        with open(path, 'r') as f:
            for name in _PY_READ.findall(f.read()):
                vars_.setdefault(name, set()).add(
                    os.path.relpath(path, ROOT))
    return vars_


def repo_reads():
    """BF_* vars read anywhere scannable: the package, tools/ and
    tests/ (for the documented->read direction)."""
    vars_ = dict(package_reads())
    for path in _py_files('tools', 'tests'):
        with open(path, 'r') as f:
            for name in _PY_READ.findall(f.read()):
                vars_.setdefault(name, set()).add(
                    os.path.relpath(path, ROOT))
    return vars_


def documented():
    """BF_* vars documented in docs/envvars.md."""
    path = os.path.join(ROOT, 'docs', 'envvars.md')
    with open(path, 'r') as f:
        return set(_DOC.findall(f.read()))


def check():
    """Run both directions; returns a dict report (empty
    'undocumented' + 'phantom' lists = clean)."""
    pkg = package_reads()
    repo = repo_reads()
    docs = documented()
    undocumented = sorted(set(pkg) - docs - ALLOW_UNDOCUMENTED)
    phantom = sorted(docs - set(repo) - ALLOW_UNREAD)
    return {
        'undocumented': [{'var': v, 'read_in': sorted(pkg[v])}
                         for v in undocumented],
        'phantom': phantom,
        'package_vars': len(pkg),
        'documented_vars': len(docs),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('-v', '--verbose', action='store_true',
                    help='list every variable with its read sites')
    args = ap.parse_args()
    report = check()
    if args.verbose:
        for v, sites in sorted(repo_reads().items()):
            print('%-28s %s' % (v, ', '.join(sorted(sites))))
        print()
    for entry in report['undocumented']:
        print('UNDOCUMENTED %-24s read in %s but absent from '
              'docs/envvars.md'
              % (entry['var'], ', '.join(entry['read_in'])))
    for v in report['phantom']:
        print('PHANTOM      %-24s documented in docs/envvars.md but '
              'never read anywhere in the repo' % v)
    bad = bool(report['undocumented'] or report['phantom'])
    print('lint_envvars: %s — %d package var(s), %d documented, '
          '%d undocumented, %d phantom'
          % ('FAIL' if bad else 'OK', report['package_vars'],
             report['documented_vars'], len(report['undocumented']),
             len(report['phantom'])))
    return 3 if bad else 0


if __name__ == '__main__':
    sys.exit(main())
