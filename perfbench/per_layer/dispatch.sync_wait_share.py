"""Share of the window the program's blocks spent in the dispatch-ahead
wait for the device (``Block._sync_gulp``: ``block.<name>.sync_wait_s``
histograms summed over the program's own blocks, fed by the
``<block>.sync_wait`` spans).  Where a block is bound by the device it
waits here, inside its own call, and not in a ring."""


def read(run):
    spent = [run.hist_seconds('block.%s.sync_wait_s' % name)
             for name, _irings, _orings in run.win.blocks]
    spent = sum(s for s in spent if s)
    return 100.0 * spent / run.win.seconds if spent else None
