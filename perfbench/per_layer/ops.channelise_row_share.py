"""Share of the gulps of a chain that opens with a short transform (of
a ci8 gulp's last axis, of a length that divides 128) that ran the row
channeliser, which takes the gulp's words as the rows of 128 they lie
in and makes every transform of a row one block-diagonal product, no
relayout of the gulp (the program's counters ``channelise.row_gulps``
over ``channelise.gulps``, the whole run: warm-up gulps go the way the
window's do).  100 where every such gulp did, 0 where none did.
Nothing where the program does not count them (a parent from before
it did), or ran no such chain."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'channelise.row_gulps' not in counts or \
            not counts.get('channelise.gulps'):
        return None
    return 100.0 * counts['channelise.row_gulps'] / counts['channelise.gulps']
