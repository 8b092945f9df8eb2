"""Share of the gulps a chain with a coherent dedispersion in it took
that went through the one program: the channels of a block through
the forward transform, the chirp, the inverse and the detection a
chunk at a time, so that neither the spectra nor the dedispersed
voltages reached HBM whole, then the fold (the program's counters
``dedisp.fused_gulps`` over ``dedisp.gulps``, the whole run: warm-up
gulps go the way the window's do).  100 where every gulp did, 0 where
none did.  Nothing where the program does not count them (a parent
from before it did), or dedispersed nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'dedisp.fused_gulps' not in counts or \
            not counts.get('dedisp.gulps'):
        return None
    return 100.0 * counts['dedisp.fused_gulps'] / counts['dedisp.gulps']
