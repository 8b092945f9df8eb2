"""Share of the window the H2D block spent waiting for the transfer
before the newest to let go of its ring span (``xfer.h2d_hold_wait_s``,
the ``h2d.hold_wait`` spans; PERF.md section 6, PR 36): near nothing
while the source is slower than the link, and what grows first once
two transfers in flight are not enough."""

import progspans


def read(run):
    return progspans.hist_share(run, 'xfer.h2d_hold_wait_s')
