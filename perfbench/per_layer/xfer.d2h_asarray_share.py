"""Share of the window the program spent in ``np.asarray`` of products
that were ready (``xfer.d2h_asarray_s``, the ``d2h.asarray`` spans):
the copy out of the runtime into a numpy array."""

import progspans


def read(run):
    return progspans.hist_share(run, 'xfer.d2h_asarray_s')
