"""Peak resident set of the process, ``ru_maxrss`` after the window:
the pool, the host rings, the staging slots, the pieces, the kept
samples and the runtime's own.  (Linux counts it in KiB.)"""

import resource


def read(run):
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9
