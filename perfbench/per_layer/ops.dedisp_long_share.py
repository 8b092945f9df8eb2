"""Share of the gulps a chain with a coherent dedispersion in it took
whose transforms, forward and inverse, took the three-level path of
DFT matrix products (ops.fft.long_fft, chosen from the length alone
past 16384 points), where XLA's own transform of an inverse of 65536
points read three and a half times as long (the program's counters
``dedisp.long_gulps`` over ``dedisp.gulps``, the whole run).  100
where every gulp did, 0 where none did.  Nothing where the program
does not count them (a parent from before it did), or dedispersed
nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'dedisp.long_gulps' not in counts or \
            not counts.get('dedisp.gulps'):
        return None
    return 100.0 * counts['dedisp.long_gulps'] / counts['dedisp.gulps']
