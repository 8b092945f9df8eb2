"""Share of the gulps a chain with a transform in it took whose
program started from the gulp's int16 words, one a complex ci8 sample,
as the device ring holds them (its counters ``spectrometer.word_gulps``
over ``spectrometer.gulps``, the whole run: warm-up gulps go the way
the window's do).  From words one pass of the device folds the
spectrometer's kernel its rows; from int8 (re, im) pairs four layout
passes stand in front of it, a fifth of the chain (PERF.md section 6,
PR 34).  100 where every gulp came as words, 0 where none
did.  Nothing where the program does not count them (a parent from
before it did), or transformed nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'spectrometer.word_gulps' not in counts or \
            not counts.get('spectrometer.gulps'):
        return None
    return 100.0 * counts['spectrometer.word_gulps'] \
        / counts['spectrometer.gulps']
