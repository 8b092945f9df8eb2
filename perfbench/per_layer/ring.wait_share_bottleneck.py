"""Share of the window that the least-blocked block of the program
spent blocked in ring calls (``ring.<r>.acquire_s`` of its input rings
plus ``ring.<r>.reserve_s`` of its output rings, histogram sums over
the window).  In a closed loop every block but the slowest waits on
its neighbours by construction; what the slowest one still waits in
the rings is time the hand-over itself costs the rate."""


def read(run):
    blocked = run.blocked_seconds()
    if not blocked:
        return None
    for name, sec in sorted(blocked.items(), key=lambda kv: kv[1]):
        run.note('ring: %s blocked %.2f %% of the window'
                 % (name, 100.0 * sec / run.win.seconds))
    return 100.0 * min(blocked.values()) / run.win.seconds or None
