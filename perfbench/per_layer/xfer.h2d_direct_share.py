"""Share of the bytes the program sent from the host to the device
that crossed from the ring span they lay in, with no copy on the host
(its counters ``xfer.h2d_direct_bytes`` over ``xfer.h2d_bytes``, the
whole run: warm-up gulps cross as the window's do).  The engine hands
``device_put`` the span's own memory and keeps the span open until the
runtime has let go of it; every other gulp is copied into a staging
buffer first, a pass over it on the host (PERF.md section 6, PR 36).
100 where every gulp lies in a host ring that can lend it, 0 where
none does.  Nothing where the program does not count them (a parent
from before it did), or sent nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'xfer.h2d_direct_bytes' not in counts or \
            not counts.get('xfer.h2d_bytes'):
        return None
    return 100.0 * counts['xfer.h2d_direct_bytes'] / counts['xfer.h2d_bytes']
