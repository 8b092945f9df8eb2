"""Device programs launched in the window (trace) per gulp delivered."""


def read(run):
    t = run.trace()
    if t is None or not t['programs']:
        return None
    return t['programs'] / run.gulps()
