"""The same quantity as ``sustained_msps``, under the name the
device-fed cell is bounded by (PERF.md section 2 says why two names)."""


def read(run):
    return run.samples() / run.win.seconds / 1e6
