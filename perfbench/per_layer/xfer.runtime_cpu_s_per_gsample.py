"""CPU seconds per 10^9 samples of every thread without a Python name
in the window (role ``runtime`` of ``progcpu``: jaxlib's and libtpu's
``pjrt-tpu-tasks``, ``futex-default-S``, ``EventFDAsyncWor``, ``tf_*``
...): what the program's calls make the runtime spend on the host,
layout conversion and the copies of H2D and D2H among it; each family
goes to the run's notes by name.  Nothing where the program keeps no
series, or the four roles do not add up."""

import progcpu


def read(run):
    return progcpu.per_gsample(run, 'runtime')
