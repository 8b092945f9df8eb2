"""Share of the gulps a chain with a beamformer in it took that went
through the one fused kernel: beamformed, detected, integrated and
requantised a channel and a tile of time a program, no beam voltage
in HBM (the program's counters ``beamform.fused_gulps`` over
``beamform.gulps``, the whole run: warm-up gulps go the way the
window's do).  At the deployment's shape the unfused chain's beam
voltages would be 14.5 GB a gulp.  100 where every gulp did, 0 where
none did.  Nothing where the program does not count them (a parent
from before it did), or beamformed nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'beamform.fused_gulps' not in counts or \
            not counts.get('beamform.gulps'):
        return None
    return 100.0 * counts['beamform.fused_gulps'] / counts['beamform.gulps']
