"""Share of the window the program spent inside ``jax.device_put``
until it returned (``xfer.h2d_put_s``, the ``h2d.put`` spans): what of
the runtime's host-side work for H2D, its layout conversion among it,
is done on the caller's thread.  The rest of it runs on the runtime's
own threads and shows only as CPU time."""

import progspans


def read(run):
    return progspans.hist_share(run, 'xfer.h2d_put_s')
