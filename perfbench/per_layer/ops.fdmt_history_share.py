"""Share of the gulps an FDMT block dedispersed whose history, the
max_delay filterbank samples before the gulp, came from frames the
block held on the device, not from its input ring's overlap, which
would hold each product back two gulps and have the blocks upstream
compute those frames again for it (the program's counters
``fdmt.device_history_gulps`` over ``fdmt.gulps``, the whole run:
warm-up gulps go the way the window's do).  100 where every gulp's
did, 0 where none did.  Nothing where the program does not count
them (a parent from before it did), or dedispersed nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'fdmt.device_history_gulps' not in counts or \
            not counts.get('fdmt.gulps'):
        return None
    return 100.0 * counts['fdmt.device_history_gulps'] / counts['fdmt.gulps']
