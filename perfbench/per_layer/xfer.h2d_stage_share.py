"""Share of the window the program spent copying host gulps into its
staging buffers before H2D (``np.copyto`` into an aligned slot, or
into a fresh buffer where the pool was exhausted; the plane split of a
complex gulp): the sum of its ``xfer.h2d_stage_s`` histogram, fed by
the ``h2d.stage`` spans.  With ``xfer.h2d_put_share`` it splits
``xfer.h2d_call_share``."""

import progspans


def read(run):
    return progspans.hist_share(run, 'xfer.h2d_stage_s')
