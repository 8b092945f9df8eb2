"""Share of the window the program spent copying products into the
host ring and mirroring the ghost region (``HostFill.wait`` after the
transfer's result: ``xfer.d2h_fill_s``, the ``d2h.fill`` spans).  It is
paid by whichever thread needs the bytes first; which one that was is
in the notes of ``dispatch.bottleneck_work_share`` (the thread whose
self times list ``d2h.fill``)."""

import progspans


def read(run):
    return progspans.hist_share(run, 'xfer.d2h_fill_s')
