"""Share of the gulps a chain with a transform in it took whose
transform ran in the program's long path, three levels of DFT
matrices (its counters ``spectrometer.long_gulps`` over
``spectrometer.gulps``, the whole run: warm-up gulps go the way the
window's do).  The path is chosen from the transform's length alone,
so this is 100 where every transform is past two levels' reach and 0
where none is.  Nothing where the program does not count them (a
parent from before it did), or transformed nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or not counts.get('spectrometer.gulps'):
        return None
    return 100.0 * counts.get('spectrometer.long_gulps', 0) \
        / counts['spectrometer.gulps']
