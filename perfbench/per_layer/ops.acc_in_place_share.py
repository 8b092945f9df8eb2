"""Share of the gulps an accumulate block integrated that it added
into the accumulator where it lay, donated to the gulp's program (its
counters ``accumulate.acc_in_place`` over ``accumulate.gulps``, the
whole run), of those that could be: the first gulp of an integration
makes the accumulator, so an integration of n gulps has n - 1 to add,
and ``accumulate.integrations`` says how many were begun and
finished (the benchmark's source stops between products, so none is
left begun).  100 where every such gulp was added in place; 0 where
each made a fresh sum.  Nothing where the program does not count them, or
integrated nothing."""

import progcounters


def read(run):
    counts = progcounters.counters()
    if not counts or 'accumulate.acc_in_place' not in counts:
        return None
    could = counts.get('accumulate.gulps', 0) \
        - counts.get('accumulate.integrations', 0)
    if could <= 0:
        return None
    return 100.0 * counts['accumulate.acc_in_place'] / could
