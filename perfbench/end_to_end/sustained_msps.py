"""Every input sample whose product reached the sink inside the window,
over all of the window's seconds, in millions a second."""


def read(run):
    return run.samples() / run.win.seconds / 1e6
