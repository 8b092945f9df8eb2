"""Share of the gulps a chain that dedisperses and folds took whose
plan cut the rotations from the Stokes series with the kernel that
reads the series once, where the chunk loop wrote it (ops.fold's Pallas
cut: no relayout or padded copy of the series, no array of its
rotations), read from the plan's record (the program's counters
``fold.kernel_gulps`` over ``fold.gulps``, the whole run).  100 where
every gulp did, 0 where none did.  Nothing where the program does not
count them (a parent from before it did), or folded nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'fold.kernel_gulps' not in counts or \
            not counts.get('fold.gulps'):
        return None
    return 100.0 * counts['fold.kernel_gulps'] / counts['fold.gulps']
