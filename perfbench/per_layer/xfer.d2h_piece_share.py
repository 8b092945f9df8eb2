"""Share of the bytes the program brought from the device to the host
that crossed in pieces (its counters ``xfer.d2h_piece_bytes`` over
``xfer.d2h_bytes``, the whole run: warm-up products cross as the
window's do).  Pieces come from the allocator's heap and are found
there again; a product that crosses whole first-touches fresh pages
(PERF.md section 6, PR 27).  Nothing where the program does not count
pieces, or moved nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'xfer.d2h_piece_bytes' not in counts or \
            not counts.get('xfer.d2h_bytes'):
        return None
    return 100.0 * counts['xfer.d2h_piece_bytes'] / counts['xfer.d2h_bytes']
