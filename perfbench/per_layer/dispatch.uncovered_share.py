"""Share of the window that the most worked thread of the program (as
``dispatch.bottleneck_work_share`` chooses it) spent inside no span at
all: what the program's own timing still cannot see of the thread that
matters most."""

import progspans


def read(run):
    best = progspans.bottleneck(run)
    if best is None:
        return None
    return 100.0 * best[1]['uncovered'] / run.win.seconds or None
