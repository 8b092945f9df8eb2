"""Share of the bytes the program sent from the host to the device
that crossed as int16 words, one a complex ci8 sample, on one axis in
the host's own order (its counters ``xfer.h2d_word_bytes`` over
``xfer.h2d_bytes``, the whole run: warm-up gulps cross as the window's
do).  An int8 gulp with a trailing (re, im) axis is taken apart and
reordered by the runtime on the host, byte by byte, inside
``device_put``; the words land as the host holds them, with no pass
of the runtime's over them, and a reader's program folds them
(PERF.md section 6, PR 34).  100 where every gulp is ci8 bound for one device, 0 where
none is.  Nothing where the program does not count them (a parent
from before it did), or sent nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'xfer.h2d_word_bytes' not in counts or \
            not counts.get('xfer.h2d_bytes'):
        return None
    return 100.0 * counts['xfer.h2d_word_bytes'] / counts['xfer.h2d_bytes']
