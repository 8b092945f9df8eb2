"""Share of the window's ring fills (``d2h.fill`` spans: the copy of a
product into its host ring span) that ran on one of the transfer
engine's own completion threads, named ``xfer-d2h-<n>``, and not on a
block thread or a ring's reader that needed the bytes before a
completion thread had claimed them.  By count of the spans that began
inside the window.  Nothing where no fill ran on such a thread: a
program from before the engine had threads of its own reads as nothing
here."""

import progspans


def read(run):
    got = progspans.events_of(run)
    if got is None:
        return None
    events, origin, _drops = got
    fills = [thread for thread, ev in events if ev[0] == 'd2h.fill'
             and run.win.t_open <= origin + ev[2] * 1e-6 < run.win.t_close]
    mine = sum(1 for thread in fills if thread.startswith('xfer-d2h-'))
    return 100.0 * mine / len(fills) if mine else None
