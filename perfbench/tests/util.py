"""Drive one rehearsal-size run of the harness in this process (the
look for a chip skipped by ``--rehearse``) and return its result."""

import io
import json
from contextlib import redirect_stdout

import run


def rehearse(workload, seed=1, control=False, wrap_chain=None, seconds=0.5):
    argv = ['--workload', workload, '--seed', str(seed), '--seconds',
            str(seconds), '--trace', '0', '--rehearse']
    if control:
        argv.append('--control')
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(argv, wrap_chain=wrap_chain)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
