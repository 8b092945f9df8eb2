"""Share of the bytes the program brought from the device to the host
that crossed as real (re, im) pairs (its counters
``xfer.d2h_pair_bytes`` over ``xfer.d2h_bytes``, the whole run:
warm-up products cross as the window's do).  A complex product in
pieces leaves the device as rows of 32-bit words with re and im
interleaved, which the runtime moves as they are and the host sees as
complex with a view; as complex64 the runtime converts it on the host, one
transfer at a time (PERF.md section 6, PR 29).  100 where every
product is complex and large, 0 where none is complex.  Nothing where
the program does not count pairs, or moved nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'xfer.d2h_pair_bytes' not in counts or \
            not counts.get('xfer.d2h_bytes'):
        return None
    return 100.0 * counts['xfer.d2h_pair_bytes'] / counts['xfer.d2h_bytes']
