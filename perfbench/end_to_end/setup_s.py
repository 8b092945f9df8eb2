"""First line of run.py to the opening of the window."""


def read(run):
    return run.setup_seconds()
