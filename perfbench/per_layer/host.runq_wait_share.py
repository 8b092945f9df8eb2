"""Seconds the process's runnable threads stood in a run queue waiting
for a core, per second they ran, in % (all threads' ``runq_s`` over
their ``cpu_s`` in the window, from the scheduler's clock by
``progcpu.runq_share``): how short of cores the host is.  Nothing on
the ``ticks`` clock, which has no such count: the machine the
benchmark runs on (gVisor) keeps no ``schedstat``, so
``BENCHMARK.json`` lists this metric in no cell yet (PERF.md section
7); ``.replay`` and ``.resident`` would share this reader."""

import progcpu


def read(run):
    return progcpu.runq_share(run)
