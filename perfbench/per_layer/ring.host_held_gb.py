"""Bytes the program's host rings may hold at the depth it gave them:
its gauge ``ring.held_bytes.system`` (the sum of ``ring.<name>.
capacity_bytes`` over the live rings in host memory, set at every
resize; span depth and ghost region), read after the window.  Every
ring's own capacity, device rings' too, goes to standard error.
Nothing where the program keeps no such gauge."""

import progcounters


def read(run):
    return progcounters.held_gb(run, 'system', note_rings=True)
