"""CPU seconds per 10^9 samples that the program's block and transfer
threads burnt INSIDE spans of the categories ``ring`` and ``wait``
(the spans' ``cpu_us`` self time, by ``progcpu.wait_cpu``): a thread
that waits should sleep, so this should read near nothing, and where
it does not the notes name the span.  Nothing where the spans carry
no CPU time (a parent from before they did) or a buffer evicted spans
of the window."""

import progcpu


def read(run):
    return progcpu.wait_cpu_per_gsample(run)
