"""Share of the window the program spent waiting for a product to be
ready on the host side of a D2H (``jax.block_until_ready`` before the
copy out: the device's and the DMA's remainder; ``xfer.d2h_ready_s``,
the ``d2h.ready`` spans).  With ``xfer.d2h_asarray_share`` (and the
conversion, where there is one) it splits ``xfer.d2h_wait_share``."""

import progspans


def read(run):
    return progspans.hist_share(run, 'xfer.d2h_ready_s')
