"""Share of the gulps a chain with a beamformer in it took whose
program started from the gulp's int16 words, one a complex ci8 sample,
as the device ring holds them (the program's counters
``beamform.word_gulps`` over ``beamform.gulps``, the whole run).  A
frame's words lie in (freq, station, pol) order, the MXU operand's
own, so the kernel reads them as they landed; from the int8 (re, im)
pairs one program more a gulp makes them first.  100 where every gulp
came as words, 0 where none did.  Nothing where the program does not
count them (a parent from before it did), or beamformed nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'beamform.word_gulps' not in counts or \
            not counts.get('beamform.gulps'):
        return None
    return 100.0 * counts['beamform.word_gulps'] / counts['beamform.gulps']
