"""Share of the bytes the program sent from the device to the host
that crossed in pieces of ONE axis of uint32 words in the host's byte
order (its counters ``xfer.d2h_flat_bytes`` over ``xfer.d2h_bytes``,
the whole run: warm-up products cross as the window's do).  A piece of
the device's tiles is laid out in the host's order by the runtime's
own threads, about 0.4 CPU-s a GB; one axis lands as it lies (PERF.md
section 6, PR 38).  100 where every product crosses in pieces that can
be words, 0 where none does.  Nothing where the program does not count
them (a parent from before it did), or sent nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'xfer.d2h_flat_bytes' not in counts or \
            not counts.get('xfer.d2h_bytes'):
        return None
    return 100.0 * counts['xfer.d2h_flat_bytes'] / counts['xfer.d2h_bytes']
