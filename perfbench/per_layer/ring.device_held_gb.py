"""Bytes the program's device rings may hold at the depth it gave
them: its gauge ``ring.held_bytes.tpu`` (as many spans of arrays as
fit each ring's size), read after the window.  Nothing where the
program keeps no such gauge."""

import progcounters


def read(run):
    return progcounters.held_gb(run, 'tpu')
