"""Seconds the process spent compiling, or loading compiled programs
from the persistent cache, before the window opened: the sum of the
program's ``jit.compile_s`` histogram (one observation per
backend-compile event of ``jax.monitoring``, which brackets compile or
cache load) at the window's opening.  Compilations inside the window,
of which there should be none, are named loudly on standard error."""

import progspans


def read(run):
    before, after = run.win.hists
    if not after or 'jit.compile_s' not in after:
        return None
    at_open = (before or {}).get('jit.compile_s', {'sum': 0.0, 'count': 0})
    inside = after['jit.compile_s']['count'] - at_open['count']
    if inside:
        events, origin, _drops = progspans.events_of(run) or ([], 0.0, {})
        lo, hi = run.win.t_open - origin, run.win.t_close - origin
        names = sorted(set(
            str((ev[4] or {}).get('fun', '?')) for _t, ev in events
            if ev[0] == 'jit.compile' and lo <= ev[2] * 1e-6 < hi))
        run.note('COMPILED INSIDE THE WINDOW: %d program(s): %s'
                 % (inside, ', '.join(names) or 'not in the span buffers'))
    else:
        run.note('compile: none inside the window; %d before it, %.2f s'
                 % (at_open['count'], at_open['sum']))
    return at_open['sum'] or None
