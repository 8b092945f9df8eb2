"""1 - union of the device's operation intervals over the window."""


def read(run):
    t = run.trace()
    if t is None:
        return None
    return 100.0 * (1.0 - t['busy_s'] / t['window_s'])
