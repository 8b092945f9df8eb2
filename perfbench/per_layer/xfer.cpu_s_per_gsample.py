"""CPU seconds per 10^9 samples of the program's own transfer threads
in the window (role ``transfer`` of ``progcpu``: the blocks named
``CopyBlock_*`` and the engine's completion threads ``xfer-*``): ring
fills, pieces taken from the runtime's buffers, the calls into
``device_put``.  What those calls make the runtime's own threads spend
is ``xfer.runtime_cpu_s_per_gsample``.  Nothing where the program
keeps no series, or the four roles do not add up."""

import progcpu


def read(run):
    return progcpu.per_gsample(run, 'transfer')
