#!/usr/bin/env python3
"""Which thread spent the CPU: one run of a benchmark cell with the
process's threads read at the two edges of its window (PERF.md
section 6, PR 32).

``host_cpu_s_per_gsample`` is the whole process's user + system time
over the window; the program's spans say what its own threads did, not
what the runtime's did beside them.  This script wraps
``perfbench/run.py``'s ``main`` of the checkout it is started in (the
current directory, so that one copy of it serves a parent's tree too)
and, where the drive reads the process's CPU time at the window's
edges (``drive._cpu_seconds``), also reads ``/proc/self/task/*/stat``:
user and system ticks and minor faults of every thread, under its
Python name where it has one and the runtime's own otherwise.  To
stderr, and as JSON to ``$THREADS_OUT`` (default
``chiprun_out/threads.json``): CPU seconds and minor faults a thread
name over the window (numbered siblings summed, ``xfer-d2h-0``
apart), threads that ended inside the window as the remainder against
the process's own count.  The run's result line is printed as always.

    cd <checkout> && python3 tools/thread_cpu.py --workload xcorr-replay \\
        --seed 7 --seconds 30 --trace 0
"""

import importlib.util
import json
import os
import re
import sys
import threading

TICK = os.sysconf('SC_CLK_TCK')


def threads():
    """``{tid: (name, cpu seconds, minor faults)}`` of this process."""
    names = {t.native_id: t.name for t in threading.enumerate()}
    out = {}
    for tid in os.listdir('/proc/self/task'):
        try:
            with open('/proc/self/task/%s/stat' % tid) as f:
                stat = f.read()
        except OSError:
            continue               # ended between the two calls
        comm = stat[stat.index('(') + 1:stat.rindex(')')]
        rest = stat[stat.rindex(')') + 2:].split()
        # after the name: state is field 3, so field n is rest[n - 3]
        minflt, utime, stime = (int(rest[n - 3]) for n in (10, 14, 15))
        out[int(tid)] = (names.get(int(tid), comm),
                         (utime + stime) / TICK, minflt)
    return out


def family(name):
    """``Feed_0`` and ``xfer-d2h-0`` as they are; the runtime's
    numbered pools (``tf_pjrt_3`` ...) as one."""
    if re.match(r'^[A-Z]', name) or name.startswith(('xfer-', 'bench-')):
        return name
    return re.sub(r'[-_/:]?\d+$', '', name) or name


def main(argv):
    root = os.getcwd()
    sys.path.insert(0, os.path.join(root, 'perfbench'))
    sys.path.insert(0, root)
    import drive
    edges = []
    cpu_seconds = drive._cpu_seconds

    def stamped():
        edges.append((threads(), cpu_seconds()))
        return edges[-1][1]
    drive._cpu_seconds = stamped

    spec = importlib.util.spec_from_file_location(
        'run', os.path.join(root, 'perfbench', 'run.py'))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(argv)
    if len(edges) < 2:
        print('thread_cpu: the window never closed', file=sys.stderr)
        return rc or 1
    (t0, cpu0), (t1, cpu1) = edges[0], edges[-1]
    cpu, flt = {}, {}
    for tid, (name, s1, f1) in t1.items():
        _n, s0, f0 = t0.get(tid, (name, 0.0, 0))
        cpu[family(name)] = cpu.get(family(name), 0.0) + s1 - s0
        flt[family(name)] = flt.get(family(name), 0) + f1 - f0
    seen = sum(cpu.values())
    cpu['(threads that ended in the window)'] = cpu1 - cpu0 - seen
    out = {'process_cpu_s': cpu1 - cpu0, 'cpu_s': cpu, 'minor_faults': flt}
    out_path = os.environ.get('THREADS_OUT') or os.path.join(
        'chiprun_out', 'threads.json')
    os.makedirs(os.path.dirname(out_path) or '.', exist_ok=True)
    with open(out_path, 'w') as f:
        json.dump(out, f)
    print('thread_cpu: process %.2f CPU-s in the window; by thread '
          '(CPU-s, minor faults): %s'
          % (cpu1 - cpu0, ', '.join(
              '%s %.2f %d' % (n, s, flt.get(n, 0))
              for n, s in sorted(cpu.items(), key=lambda kv: -kv[1])
              if s >= 0.05)), file=sys.stderr)
    return rc


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
