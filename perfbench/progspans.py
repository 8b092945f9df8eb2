"""The program's own spans, read after a run.

``bifrost_tpu/telemetry/spans.py`` records every instrumented operation
of the program (block compute, ring waits, the parts of H2D and D2H,
the dispatch-ahead wait, compilations, full garbage collections) into
bounded per-thread buffers, always, on ``time.perf_counter()`` less a
public origin: the clock the bench stamps its window and its anchors
with.  This file turns those events into what the readers under
``per_layer/`` need:

- ``intervals(events, origin)``: ``{name: [[t0, t1], ...]}`` in
  ``perf_counter`` seconds, the shape ``drive.Window.spans`` has, so
  ``tracered.attribute`` takes them as they are (once mapped to trace
  time like the bench's own spans);
- ``by_thread(events, origin, t_open, t_close, drops)``: for each
  thread, clipped to the window, the seconds inside any span
  (covered), the seconds it waited, and self time per span name;
- ``events_of(run)`` / ``threads(run)`` / ``bottleneck(run)``: the same
  for a finished run, each computed once, and the thread that bounds
  the program.

Parentage is by nesting on the thread: a child lies inside its
parent's interval, and a span's self time is its duration less its
direct children's.  Waiting is the self time of spans in the
categories ``ring`` (blocked in flow control) and ``wait`` (blocked on
the device or a transfer): self time, not the union, because a wait
span may hold work (``d2h.depth_wait`` holds the ``np.asarray`` and the
ring fill of the transfer it retires).

Where the program has no such recorder, or recorded nothing (a parent
commit from before recording was always on), everything here returns
None and raises nothing.
"""

WAITING = ('ring', 'wait')


def program_events():
    """(events, origin_s, drops) from the program's recorder, or None.
    ``events`` is ``[(thread, (name, cat, ts_us, dur_us, args)), ...]``,
    ``drops`` ``{thread: spans its buffer evicted}``."""
    try:
        from bifrost_tpu.telemetry import spans
        origin = spans.origin_s()
        events = spans.events()
        drops = spans.dropped_by_thread()
    except (ImportError, AttributeError):
        return None
    return (events, origin, drops) if events else None


def _seconds(ev, origin):
    t0 = origin + ev[2] * 1e-6
    return t0, t0 + ev[3] * 1e-6


def intervals(events, origin):
    """``{name: [[t0, t1], ...]}`` in ``perf_counter`` seconds, each
    list sorted by start: the shape ``Window.spans`` has.  Synthesized
    spans (a compiled segment's members, drawn over the one real
    dispatch) are left out."""
    out = {}
    for _thread, ev in events:
        if ev[4] and ev[4].get('synthesized'):
            continue
        out.setdefault(ev[0], []).append(list(_seconds(ev, origin)))
    for iv in out.values():
        iv.sort()
    return out


def _one_thread(evs, lo, hi):
    """(covered_s, waiting_s, {name: self_s}) of one thread's spans
    ``[(t0, t1, name, cat)]`` clipped to [lo, hi]."""
    clipped = sorted((max(t0, lo), -min(t1, hi), name, cat)
                     for t0, t1, name, cat in evs
                     if min(t1, hi) > max(t0, lo))
    covered = waiting = 0.0
    self_s = {}
    stack = []                 # [end, name, cat, self seconds so far]
    reach = lo                 # the top-level spans' union ends here

    def close(top):
        self_s[top[1]] = self_s.get(top[1], 0.0) + top[3]
        return top[3] if top[2] in WAITING else 0.0

    for t0, neg_t1, name, cat in clipped:
        t1 = -neg_t1
        while stack and stack[-1][0] <= t0:
            waiting += close(stack.pop())
        if stack:
            # a child: it takes its part out of the parent's self time
            # (cut at the parent's end: a recorded start may lie a
            # rounding off)
            t1 = min(t1, stack[-1][0])
            stack[-1][3] -= t1 - t0
        else:
            t0 = max(t0, reach)
            t1 = max(t1, t0)
            covered += t1 - t0
            reach = t1
        stack.append([t1, name, cat, t1 - t0])
    while stack:
        waiting += close(stack.pop())
    return covered, waiting, self_s


def by_thread(events, origin, t_open, t_close, drops=None, note=None):
    """``{thread: {'work', 'wait', 'uncovered', 'self': {name: s}}}``
    in seconds of the window [t_open, t_close], for every thread with a
    span in it.  ``work`` is covered less waiting.  Returns None (and
    says so through ``note``) where a thread's buffer evicted spans
    that ended after ``t_open``: its window would read emptier than it
    was."""
    per = {}
    first_end = {}
    for thread, ev in events:
        if ev[4] and ev[4].get('synthesized'):
            continue
        t0, t1 = _seconds(ev, origin)
        per.setdefault(thread, []).append((t0, t1, ev[0], ev[1]))
        # buffers are in order of completion: the first kept is the
        # oldest, and everything evicted ended before it did
        first_end.setdefault(thread, t1)
    for thread, n in sorted((drops or {}).items()):
        if n and first_end.get(thread, t_open) > t_open:
            if note:
                note('spans: thread %s evicted %d span(s) and its oldest '
                     'left ends %.3f s into the window: no per-thread '
                     'reading (raise BF_SPAN_BUFFER)'
                     % (thread, n, first_end[thread] - t_open))
            return None
    out = {}
    window = t_close - t_open
    for thread, evs in per.items():
        covered, waiting, self_s = _one_thread(evs, t_open, t_close)
        if covered > 0:
            out[thread] = {'work': covered - waiting, 'wait': waiting,
                           'uncovered': window - covered, 'self': self_s}
    return out


def events_of(run):
    """``program_events()`` for a finished run, drained once."""
    if not hasattr(run, '_progspans_events'):
        run._progspans_events = program_events()
    return run._progspans_events


def threads(run):
    """``by_thread`` over the run's window, computed once; every
    thread's split goes to the run's notes.  None where the program
    recorded nothing."""
    if not hasattr(run, '_progspans'):
        got = events_of(run)
        per = None
        if got is not None:
            events, origin, drops = got
            per = by_thread(events, origin, run.win.t_open,
                            run.win.t_close, drops, run.note)
        for name, t in sorted((per or {}).items(),
                              key=lambda kv: -kv[1]['work']):
            s = run.win.seconds
            run.note('spans: %-28s work %5.1f %%  wait %5.1f %%  '
                     'uncovered %5.1f %%'
                     % (name, 100 * t['work'] / s, 100 * t['wait'] / s,
                        100 * t['uncovered'] / s))
        run._progspans = per or None
    return run._progspans


def bottleneck(run):
    """(thread, its split) of the thread that bounds the program: over
    the program's own blocks and whoever reads their output rings (the
    bench's sink thread, where ring fills land), the one with the
    largest share of work.  Its largest self times go to the notes.
    None where there is nothing to read."""
    if not hasattr(run, '_progspans_bottleneck'):
        per = threads(run)
        best = None
        if per:
            acquires = set('%s.acquire' % r for _n, _i, orings
                           in run.win.blocks for r in orings)
            mine = set(name for name, _i, _o in run.win.blocks) | set(
                thread for thread, ev in events_of(run)[0]
                if ev[0] in acquires)
            cand = {n: t for n, t in per.items() if n in mine}
            if cand:
                name = max(cand, key=lambda n: cand[n]['work'])
                best = (name, cand[name])
                gulps = max(run.gulps(), 1)
                top = sorted(best[1]['self'].items(),
                             key=lambda kv: -kv[1])[:5]
                run.note('spans: the most worked thread is %s; its '
                         'largest self times: %s'
                         % (name, ', '.join(
                             '%s %.2f ms/gulp (%.1f %%)'
                             % (n, 1e3 * s / gulps,
                                100 * s / run.win.seconds)
                             for n, s in top)))
        run._progspans_bottleneck = best
    return run._progspans_bottleneck


def hist_share(run, name):
    """Sum of the program's histogram ``name`` over the window as a
    share of it in %, or None where nothing recorded it."""
    spent = run.hist_seconds(name)
    return 100.0 * spent / run.win.seconds if spent else None
