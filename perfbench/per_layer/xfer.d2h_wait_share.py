"""Share of the window the program's D2H block spent waiting for
device-to-host results and converting them on the host: the sum of its
``xfer.d2h_wait_s`` histogram (host clock, inside the program) over the
window.  Mostly ``np.asarray`` and layout conversion, not the DMA's own
time: the v5e's trace shows that only on host threads, which the run
does not trace."""


def read(run):
    spent = run.hist_seconds('xfer.d2h_wait_s')
    return 100.0 * spent / run.win.seconds if spent else None
