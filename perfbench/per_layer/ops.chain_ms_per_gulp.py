"""Device time of the chain's operations per gulp delivered (trace:
union of the operations' intervals over the window)."""


def read(run):
    t = run.trace()
    if t is None:
        return None
    return 1e3 * t['busy_s'] / run.gulps()
