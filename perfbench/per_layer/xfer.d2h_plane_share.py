"""Share of the bytes the program brought from the device to the host
whose pieces were cut from the two float32 planes a complex product
was computed in (its counters ``xfer.d2h_plane_bytes`` over
``xfer.d2h_bytes``, the whole run: warm-up products cross as the
window's do).  A product that reaches the transfer engine as complex64
is split into planes, all of it, by every program that cuts pieces
from it; one that reaches it as the planes themselves is sliced and
interleaved with no complex type in the program (PERF.md section 6,
PR 31).  100 where every product is a large complex one handed on as
planes, 0 where none is.  Nothing where the program does not count
them, or moved nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'xfer.d2h_plane_bytes' not in counts or \
            not counts.get('xfer.d2h_bytes'):
        return None
    return 100.0 * counts['xfer.d2h_plane_bytes'] / counts['xfer.d2h_bytes']
