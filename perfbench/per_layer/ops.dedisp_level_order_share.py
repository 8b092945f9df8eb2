"""Share of the gulps a chain with a coherent dedispersion in it took
whose two transforms passed the spectrum between them in the order the
three levels of DFT matrices make (ops.fft.long_fft with ``natural``
off both ways, the chirp held in that order: no digit reversal of the
spectrum, a relayout of four times its bytes a transform, on either
side), read from the path record (the program's counters
``dedisp.level_order_gulps`` over ``dedisp.gulps``, the whole run).
100 where every gulp did, 0 where none did.  Nothing where the
program does not count them (a parent from before it did), or
dedispersed nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'dedisp.level_order_gulps' not in counts or \
            not counts.get('dedisp.gulps'):
        return None
    return 100.0 * counts['dedisp.level_order_gulps'] / counts['dedisp.gulps']
