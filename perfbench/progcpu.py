"""The program's own account of its CPU seconds, read after a run.

``host_cpu_s_per_gsample`` is one number a process.  The program says
who spent it: ``bifrost_tpu/telemetry/threadcpu.py`` keeps a series of
readings of every OS thread's seconds on a CPU and seconds runnable but
waiting for one (the kernel's scheduler clock, one reading a second on
the pipeline's ``bf-metrics`` thread), and every span of
``telemetry/spans.py`` carries the CPU time its thread spent inside it
(``cpu_us``, the event's sixth field).  This file turns both into what
the readers under ``per_layer/`` need:

- ``families(run)``: the window's CPU seconds by thread family, each
  family in exactly one of four ROLES, so that they partition the
  process:

  ``bench``     the harness's own source and sink: ``Feed_*``,
                ``Sink_*``, ``bench-*``, ``MainThread``;
  ``transfer``  the program's blocks named ``CopyBlock_*`` and the
                transfer engine's completion threads, ``xfer-*``;
  ``runtime``   every thread that has no Python name: jaxlib's and
                libtpu's own (``pjrt-tpu-tasks``, ``futex-default-S``,
                ``EventFDAsyncWor``, ``tf_*`` ...);
  ``chain``     the program's other blocks, ``bf-*`` (metrics, health,
                watchdog) and any other thread with a Python name.

  Every family's CPU and run-queue seconds, ``ended_cpu_s`` (threads
  that ended inside the window), the machine's ``steal_s``, the
  cgroup's ``throttled_s`` and the reading's ``clock`` go to the run's
  notes.  Where the roles' sum with ``ended_cpu_s`` lies more than
  ``TOLERANCE`` from ``run.cpu_seconds()`` all four read None: a split
  that does not add up is worse than none.
- ``per_gsample(run, role)``: that role's CPU seconds per 10^9 samples.
- ``runq_share(run)``: all threads' run-queue seconds over their CPU
  seconds, in %.
- ``wait_cpu_per_gsample(run)``: from the spans' ``cpu_us``, the CPU
  self time of spans in the categories ``ring`` and ``wait`` on the
  threads of the roles ``transfer`` and ``chain``: CPU burnt while
  waiting.

Where the program has no such module, no series, a series that does
not bracket the window, or spans without the field (a parent commit
from before they were there), everything here returns None and raises
nothing.
"""

import progspans

ROLES = ('bench', 'transfer', 'runtime', 'chain')
#: how far the split may lie from the harness's own count
TOLERANCE = 0.05
#: families below this many CPU seconds are noted as one line
LISTED_S = 0.05


def window_cpu(t_open, t_close):
    """``threadcpu.between`` over the window, or None."""
    try:
        from bifrost_tpu.telemetry import threadcpu
        return threadcpu.between(t_open, t_close)
    except (ImportError, AttributeError):
        return None


def role(name, named, blocks):
    """The one role of a thread family (the module docstring);
    ``blocks`` are the names of the program's own blocks."""
    if not named:
        return 'runtime'
    short = name.rsplit('/', 1)[-1]        # less the pipeline's scope
    if short == 'MainThread' or \
            short.startswith(('Feed_', 'Sink_', 'bench-')):
        return 'bench'
    if short.startswith('xfer-') or \
            (name in blocks and short.startswith('CopyBlock_')):
        return 'transfer'
    return 'chain'


def _account(run):
    """(the window's reading, {family: role}, {role: CPU-s or None}),
    computed once; None where there is nothing to read."""
    if not hasattr(run, '_progcpu'):
        run._progcpu = None
        got = window_cpu(run.win.t_open, run.win.t_close)
        if got is not None:
            blocks = set(name for name, _i, _o in run.win.blocks)
            roles = {name: role(name, fam['named'], blocks)
                     for name, fam in got['families'].items()}
            split = dict.fromkeys(ROLES, 0.0)
            for name, fam in got['families'].items():
                split[roles[name]] += fam['cpu_s']
            _note(run, got, roles, split)
            total = sum(split.values()) + got['ended_cpu_s']
            cpu = run.cpu_seconds()
            if not cpu or abs(total - cpu) > TOLERANCE * cpu:
                run.note('cpu: the split adds up to %.2f CPU-s where the '
                         'harness counts %.2f: no reading' % (total, cpu))
                split = dict.fromkeys(ROLES)
            run._progcpu = (got, roles, split)
    return run._progcpu


def _note(run, got, roles, split):
    fams = sorted(got['families'].items(), key=lambda kv: -kv[1]['cpu_s'])
    small = [fam for _n, fam in fams if fam['cpu_s'] < LISTED_S]

    def line(name, what, cpu_s, runq_s, threads):
        return 'cpu: %-28s %-8s %8.3f CPU-s  %s run-queue s  %d thread(s)' \
            % (name, what, cpu_s,
               '   none' if runq_s is None else '%7.3f' % runq_s, threads)
    for name, fam in fams:
        if fam['cpu_s'] >= LISTED_S:
            run.note(line(name, roles[name], fam['cpu_s'], fam['runq_s'],
                          fam['threads']))
    if small:
        run.note(line('(%d families under %.2f)' % (len(small), LISTED_S),
                      '', sum(f['cpu_s'] for f in small),
                      sum(f['runq_s'] or 0.0 for f in small),
                      sum(f['threads'] for f in small)))
    cpu = run.cpu_seconds()
    run.note('cpu: clock %s; roles %s; ended_cpu_s %.3f; residual against '
             'the harness\'s %.3f CPU-s: %+.3f (%+.2f %%); steal_s %s; '
             'throttled_s %s'
             % (got['clock'],
                ', '.join('%s %.3f' % (r, split[r]) for r in ROLES),
                got['ended_cpu_s'], cpu,
                sum(split.values()) - cpu,
                100.0 * (sum(split.values()) - cpu) / cpu if cpu else 0.0,
                got['steal_s'], got['throttled_s']))


def families(run):
    """``{role: CPU seconds of the window}``, every role None where the
    split does not add up; None where the program keeps no series or
    it does not bracket the window."""
    got = _account(run)
    return got[2] if got else None


def per_gsample(run, which):
    """CPU seconds of role ``which`` per 10^9 samples, or None."""
    split = families(run)
    if not split or split[which] is None or not run.samples():
        return None
    return split[which] / (run.samples() / 1e9)


def runq_share(run):
    """Seconds the process's runnable threads stood in a queue for a
    core per second they ran, in %; None on the ``ticks`` clock."""
    got = _account(run)
    if not got:
        return None
    fams = got[0]['families'].values()
    cpu_s = sum(f['cpu_s'] for f in fams)
    if not cpu_s or any(f['runq_s'] is None for f in fams):
        return None
    return 100.0 * sum(f['runq_s'] for f in fams) / cpu_s


def wait_cpu(events, origin, t_open, t_close, threads):
    """``{span name: CPU self seconds}`` of the spans in the waiting
    categories on ``threads``, inside the window: a span's ``cpu_us``
    less its direct children's (None as 0), a span that straddles an
    edge of the window counted by its wall share inside.  ``h2d.hold``
    is left out (an interval across calls, not a thread's time).  None
    where no event has the field."""
    per = {}
    for thread, ev in events:
        if thread in threads and len(ev) > 5 and ev[0] != 'h2d.hold' \
                and not (ev[4] and ev[4].get('synthesized')):
            per.setdefault(thread, []).append(ev)
    if not per:
        return None
    out = {}
    for evs in per.values():
        evs.sort(key=lambda ev: (ev[2], -ev[3]))
        stack = []                 # [end_us, event, its children's CPU]
        done = []
        for ev in evs:
            while stack and stack[-1][0] <= ev[2]:
                done.append(stack.pop())
            if stack:
                stack[-1][2] += ev[5] or 0.0
            stack.append([ev[2] + ev[3], ev, 0.0])
        for _end, ev, children in done + stack:
            if ev[1] not in progspans.WAITING:
                continue
            t0, t1 = progspans._seconds(ev, origin)
            inside = min(t1, t_close) - max(t0, t_open)
            if inside <= 0:
                continue
            share = inside / (t1 - t0) if t1 > t0 else 1.0
            self_s = max((ev[5] or 0.0) - children, 0.0) * 1e-6 * share
            out[ev[0]] = out.get(ev[0], 0.0) + self_s
    return out


def wait_cpu_per_gsample(run):
    """CPU seconds burnt inside waiting spans per 10^9 samples, over
    the threads of the roles ``transfer`` and ``chain``; per span name
    to the notes.  None where ``progspans.threads(run)`` is."""
    got = _account(run)
    if not got or progspans.threads(run) is None or not run.samples():
        return None
    mine = set(name for name, r in got[1].items()
               if r in ('transfer', 'chain'))
    events, origin, _drops = progspans.events_of(run)
    spent = wait_cpu(events, origin, run.win.t_open, run.win.t_close, mine)
    if spent is None:
        return None
    for name, s in sorted(spent.items(), key=lambda kv: -kv[1]):
        run.note('cpu: waiting in %-24s %8.4f CPU-s' % (name, s))
    return sum(spent.values()) / (run.samples() / 1e9)
