"""Share of the window the program's H2D block spent inside its
host-to-device calls: the sum of its ``xfer.h2d_s`` histogram (host
clock, inside the program) over the window.  Not the DMA's own time:
the v5e's trace shows that only on host threads, which the run does
not trace."""


def read(run):
    spent = run.hist_seconds('xfer.h2d_s')
    return 100.0 * spent / run.win.seconds if spent else None
