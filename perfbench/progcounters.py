"""The program's own counters and gauges, read after a run.

``bifrost_tpu/telemetry/counters.py`` keeps process-wide counters
(``inc``) and gauges (``set_gauge``: a level, such as what a ring may
hold).  Where the program has no such module, or no gauges (a parent
commit from before it kept them), everything here returns None and
raises nothing.
"""


def _read(what):
    try:
        from bifrost_tpu.telemetry import counters as c
        return getattr(c, what)()
    except (ImportError, AttributeError):
        return None


def counters():
    return _read('snapshot')


def gauges():
    return _read('gauges')


def held_gb(run, space, note_rings=False):
    """``ring.held_bytes.<space>`` in GB; with ``note_rings`` every
    ring's own capacity, of either space, goes to the run's notes."""
    g = gauges()
    total = (g or {}).get('ring.held_bytes.%s' % space)
    if not total:
        return None
    for name in sorted(g) if note_rings else ():
        if name.endswith('.capacity_bytes'):
            run.note('%s: %.3f GB' % (name, g[name] / 1e9))
    return total / 1e9
