"""Share of the window that the most worked thread of the program spent
working: inside one of the program's spans and not waiting (for a ring,
the device or a transfer).  Over the threads of the program's own
blocks and of whoever reads their output rings (the bench's sink
thread, where ring fills land).  At 100 % a host thread bounds the
rate; far below it, as where the device does, no host thread does.
Every thread's work / wait / uncovered and the chosen thread's five
largest self times go to standard error."""

import progspans


def read(run):
    best = progspans.bottleneck(run)
    if best is None:
        return None
    return 100.0 * best[1]['work'] / run.win.seconds or None
