"""CPU seconds per 10^9 samples of the harness's own threads in the
window (role ``bench`` of ``progcpu``: ``Feed_*``, ``Sink_*``,
``bench-*``, ``MainThread``; the program's ``telemetry/threadcpu.py``
series): the part of ``host_cpu_s_per_gsample`` that is the source's
copy into its ring and the sink's sampling, which no change to the
program moves.  Nothing where the program keeps no series, or the
four roles do not add up to the harness's own count."""

import progcpu


def read(run):
    return progcpu.per_gsample(run, 'bench')
