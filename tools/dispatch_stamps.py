#!/usr/bin/env python3
"""Who dispatched what, when: host-clock stamps round one run of a
served benchmark cell (PERF.md section 6, PR 31 and PR 32).

The device runs programs in the order they were dispatched, from every
thread, and a device trace shows when each ran, not when it was asked
for.  This script wraps ``perfbench/run.py``'s ``main`` of the
checkout it is started in (the current directory, so that one copy of
it serves a parent's tree too) and stamps, with
``time.perf_counter``:

- every D2H cut program as it is dispatched (``xfer._cut``: time, the
  first index of its group, the thread),
- every gulp of ``CorrelateBlock`` as its program is dispatched
  (``_integrate_in_place``: start, end, frames integrated before it),
- every wait of a completion thread for the device
  (``jax.block_until_ready`` on an ``xfer-d2h`` thread: start, length),
- every landing of a product (``HostFill.complete``: start, end).

For the last four products that landed whole it prints one line to
stderr: the landing's length, and the cuts, gulp dispatches and waits
as milliseconds from the landing's start.  Everything goes to
``$STAMPS_OUT`` (default ``chiprun_out/stamps.json``) as JSON.  The
arguments are ``perfbench/run.py``'s own; its result line is printed
as always, and is a measurement of the program with four wrappers
round it: read it beside an unwrapped run, not in place of one.

    chiprun -- python3 tools/dispatch_stamps.py --workload xcorr-replay \\
        --seed 7 --seconds 30 --trace 0
"""

import importlib
import importlib.util
import json
import os
import sys
import threading
import time

ROOT = os.getcwd()


def main(argv):
    sys.path.insert(0, os.path.join(ROOT, 'perfbench'))
    sys.path.insert(0, ROOT)
    import jax
    import bifrost_tpu.xfer as xfer
    correlate = importlib.import_module('bifrost_tpu.blocks.correlate')
    log = {'cut': [], 'gulp': [], 'ready': [], 'fill_done': []}

    cut = xfer._cut

    def stamped_cut(arr, start, *rest):
        log['cut'].append((time.perf_counter(), int(start),
                           threading.current_thread().name))
        return cut(arr, start, *rest)
    xfer._cut = stamped_cut

    integrate = correlate.CorrelateBlock._integrate_in_place

    def stamped_integrate(self, x, reim):
        t0 = time.perf_counter()
        integrate(self, x, reim)
        log['gulp'].append((t0, time.perf_counter(),
                            self.nframe_integrated))
    correlate.CorrelateBlock._integrate_in_place = stamped_integrate

    # xfer._cross imports the name at call time, so this is seen
    block_until_ready = jax.block_until_ready

    def stamped_wait(x):
        t0 = time.perf_counter()
        out = block_until_ready(x)
        if threading.current_thread().name.startswith('xfer-d2h'):
            log['ready'].append((t0, time.perf_counter() - t0))
        return out
    jax.block_until_ready = stamped_wait

    complete = xfer.HostFill.complete

    def stamped_complete(self, who, *then):
        t0 = time.perf_counter()
        complete(self, who, *then)
        log['fill_done'].append((t0, time.perf_counter()))
    xfer.HostFill.complete = stamped_complete

    spec = importlib.util.spec_from_file_location(
        'run', os.path.join(ROOT, 'perfbench', 'run.py'))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(argv)

    out_path = os.environ.get('STAMPS_OUT') or os.path.join(
        'chiprun_out', 'stamps.json')
    os.makedirs(os.path.dirname(out_path) or '.', exist_ok=True)
    with open(out_path, 'w') as f:
        json.dump(log, f)

    def ms(t, t0):
        return round(1e3 * (t - t0))
    for t0, t1 in log['fill_done'][-5:-1]:
        cuts = [(ms(t, t0), s) for t, s, _who in log['cut']
                if t0 - 0.6 <= t <= t1]
        gulps = [(ms(a, t0), n) for a, _b, n in log['gulp']
                 if t0 - 0.1 <= a <= t1]
        waits = [(ms(t, t0), round(1e3 * d)) for t, d in log['ready']
                 if t0 <= t <= t1]
        print('landing %d ms; cuts (ms from its start, first index) %s; '
              'gulps dispatched (ms, frames before) %s; '
              'waits for the device (at, for) %s'
              % (ms(t1, t0), cuts, gulps, waits), file=sys.stderr)
    return rc


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
