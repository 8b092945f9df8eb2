"""User + system CPU time of the whole process (all threads, the feed
included) over the window, per 10^9 samples delivered in it."""


def read(run):
    return run.cpu_seconds() / (run.samples() / 1e9)
