"""CPU seconds per 10^9 samples of the program's other threads in the
window (role ``chain`` of ``progcpu``: the blocks that are not copies,
``bf-*`` housekeeping, any other thread with a Python name): what
dispatching the chain, the rings' hand-over and the supervision cost
the host.  Nothing where the program keeps no series, or the four
roles do not add up."""

import progcpu


def read(run):
    return progcpu.per_gsample(run, 'chain')
