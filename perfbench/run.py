#!/usr/bin/env python3
"""perfbench: one run of one cell.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell comes from data: ``BENCHMARK.json`` names the
cell's configuration and traffic mix and the metrics it reports, and
the files of those names under this directory say the rest (README.md).
The last line of standard output is the result, one JSON object.

It fails, and prints no result, unless JAX finds a TPU with the chips
the cell asks for.  ``--rehearse`` runs the same code at the
configuration's rehearsal size on whatever JAX finds (the CPU here):
its line says ``cpu`` and is never a record.  ``--selfcheck`` checks
the trace reduction, the work counts and the peak table against a
recorded trace and hand-counted shapes, and touches no device.
"""

import time
T_START = time.perf_counter()           # set-up is counted from here

import argparse                          # noqa: E402
import gc                                # noqa: E402
import importlib.util                    # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import shutil                            # noqa: E402
import sys                               # noqa: E402
from concurrent.futures import ThreadPoolExecutor   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, '.bench_cache')


def say(msg):
    print(msg, file=sys.stderr, flush=True)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload')
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, default=None)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--rehearse', action='store_true',
                    help='rehearsal size, any device; never a record')
    ap.add_argument('--control', action='store_true',
                    help='run the cell\'s lower-precision control in the '
                         'program\'s place; expected to end not correct')
    ap.add_argument('--keep-trace', metavar='DIR',
                    help='copy the traced run\'s .xplane.pb here')
    ap.add_argument('--selfcheck', action='store_true')
    args = ap.parse_args(argv)
    if not args.selfcheck and not args.workload:
        ap.error('--workload is required')
    return args


def load_cell(name):
    """(bench, cell, cfg, mod): found by name, nothing else."""
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cells = {w['name']: w for w in bench['workloads']}
    if name not in cells:
        raise SystemExit('no workload %r in BENCHMARK.json (%s)'
                         % (name, ', '.join(sorted(cells))))
    cell = cells[name]
    entry = {c['name']: c for c in bench['configs']}[cell['config']]
    with open(os.path.join(ROOT, entry['file'])) as f:
        cfg = json.load(f)
    mod = load_module(os.path.splitext(
        os.path.join(ROOT, entry['file']))[0] + '.py',
        'perfbench_config_' + cell['config'])
    return bench, cell, cfg, mod


def reader(kind, name):
    """The reader of a metric, a module with ``read(run)``:
    ``<kind>/<name>.py``, or that of the longest dotted prefix of the
    name that has a file, so that ``ops.chain_roofline.resident`` (the
    variant that moves the resident cell's end-to-end metric) shares
    ``ops.chain_roofline``'s."""
    parts = name.split('.')
    for n in range(len(parts), 0, -1):
        path = os.path.join(HERE, kind, '.'.join(parts[:n]) + '.py')
        if os.path.exists(path):
            return load_module(path, 'perfbench_%s_%s'
                               % (kind, name.replace('.', '_')))
    raise FileNotFoundError('no reader under %s/ for %r' % (kind, name))


def metrics_of(bench, cell, kind):
    """The ``kind`` metrics this cell reports, as BENCHMARK.json lists
    them: all that name it, or that name no cell."""
    return [m for m in bench[kind]
            if cell['name'] in m.get('workloads', [cell['name']])]


def set_environment(control_env):
    """The default selection, and caches at fixed paths inside the
    checkout (README.md).  BENCH_RUN is the driver's and is not read."""
    for k in [k for k in os.environ if k.startswith('BF_')]:
        del os.environ[k]
    os.makedirs(os.path.join(CACHE, 'bf'), exist_ok=True)
    os.environ['BF_CACHE_DIR'] = os.path.join(CACHE, 'bf')
    # the blocks' status files: the program's default is the fixed
    # /dev/shm/bifrost_tpu, which two checkouts would share
    os.environ['BF_PROCLOG_DIR'] = os.path.join(CACHE, 'proclog')
    # a coin-flip winner is re-raced after so many uses: never in a run
    os.environ['BF_MPROBE_REPROBE'] = '0'
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        os.environ['JAX_COMPILATION_CACHE_DIR'] = \
            os.path.join(CACHE, 'jax')
    os.makedirs(os.environ['JAX_COMPILATION_CACHE_DIR'], exist_ok=True)
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    os.environ.update(control_env or {})


class Tracer(object):
    """The profiler around the window: device planes only (tracered's
    docstring says why), tied to the host's clock by anchor programs."""

    def __init__(self, workload):
        import jax
        import jax.numpy as jnp
        self.dir = os.path.join(CACHE, 'trace', workload)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.started = False
        self.stamps = []

        def bench_anchor(x):
            return x + 1
        self._anchor = jax.jit(bench_anchor)
        self._x = jnp.zeros((8, 128), jnp.float32)
        self._anchor(self._x).block_until_ready()      # compiled in set-up

    def anchor(self):
        self._anchor(self._x).block_until_ready()
        self.stamps.append(time.perf_counter())

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = True
        self.anchor()

    def stop(self, keep=None):
        """Stop, read and delete the trace; returns its plain form."""
        import glob
        import jax
        import tracered
        if not self.started:
            return None
        self.anchor()
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        try:
            paths = sorted(glob.glob(os.path.join(
                self.dir, 'plugins', 'profile', '*', '*.xplane.pb')))
            if not paths:
                return None
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(paths[-1], keep)
            trace = tracered.load(paths[-1], keep=tracered.wanted)
            say('trace: %d bytes, stopped and read in %.1f s'
                % (os.path.getsize(paths[-1]), time.perf_counter() - t0))
            return trace
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def reduce_trace(trace, tracer, win):
    """tracered.reduce over the window, in the trace's own time."""
    import tracered
    clock = tracered.clock(trace, tracer.stamps) if trace else None
    if clock is None:
        return None
    to_ns, residual = clock
    say('trace: the last anchor lies %.6f s from where the first puts it'
        % residual)
    spans = {n: to_ns(iv) if len(iv) else [] for n, iv in win.spans.items()}
    return tracered.reduce(trace, (float(to_ns(win.t_open)),
                                   float(to_ns(win.t_close))), spans)


class Run(object):
    """What a metric's reader may read."""

    def __init__(self, win, cfg, mod, peak, trace, memory_peak_bytes):
        self.win, self.cfg, self.mod, self.peak = win, cfg, mod, peak
        self._trace, self.memory_peak_bytes = trace, memory_peak_bytes
        self.gpp = mod.gulps_per_product(cfg)
        self.notes = []

    def note(self, line):
        self.notes.append(line)

    def trace(self):
        """The reduced trace (tracered.reduce), or None."""
        return self._trace

    def gulps(self):
        """Gulps whose product reached the sink inside the window."""
        return self.win.products * self.gpp

    def samples(self):
        return self.gulps() * self.mod.work(self.cfg)['samples']

    def cpu_seconds(self):
        """User + system CPU time of the whole process in the window."""
        return self.win.cpu_close - self.win.cpu_open

    def setup_seconds(self):
        """First line of run.py to the opening of the window."""
        return self.win.t_open - T_START

    def hist_seconds(self, name):
        """Sum of the program's histogram ``name`` over the window
        (after minus before), or None where nothing recorded it."""
        before, after = self.win.hists
        if name not in after:
            return None
        return after[name]['sum'] - before.get(name, {}).get('sum', 0.0)

    def blocked_seconds(self):
        """{block: seconds of the window it spent blocked in ring
        calls}: ``acquire_s`` of its input rings plus ``reserve_s`` of
        its output rings, for each of the program's own blocks (the
        bench's source and sink are left out)."""
        def total(rings, what):
            return sum(self.hist_seconds('ring.%s.%s' % (r, what)) or 0.0
                       for r in rings)
        return {name: total(irings, 'acquire_s') + total(orings, 'reserve_s')
                for name, irings, orings in self.win.blocks}

    def exit_ages(self):
        w = self.win
        return [w.arrivals[k] - w.writes[(k + 1) * self.gpp - 1]
                for k in range(w.k_open + 1, w.k_close + 1)]

    def least_seconds_per_gulp(self):
        import peaks
        if self.peak is None:
            raise KeyError('no peak table entry for this device')
        return peaks.least_seconds(self.mod.work(self.cfg), self.peak)


def check_outputs(win, cfg, mod, pool, order, sampler, control):
    """Every product the sink kept against the plain reference, and
    the delivery guarantee.  Returns (checks, ok, attempted, failed)."""
    gpp = mod.gulps_per_product(cfg)
    limit_name, limit = next(iter(cfg['limits'].items()))
    lower = {'float32': 'bfloat16', 'int8': 'int4'}[cfg['precision']]

    def one(k):
        idx = sampler.where(k)
        gulps = [pool[order[g % len(order)]]
                 for g in range(k * gpp, (k + 1) * gpp)]
        want = mod.reference(gulps, idx, cfg)
        got = mod.reference(gulps, idx, cfg, precision=lower) \
            if control == 'reference' else win.kept[k]
        name, value = mod.compare(got, want)
        assert name == limit_name, (name, limit_name)
        return value
    t0 = time.perf_counter()
    # a few threads: numpy's transforms and sums leave the GIL
    with ThreadPoolExecutor(max_workers=4) as ex:
        values = list(ex.map(one, sorted(win.kept)))
    say('reference: %d products compared in %.1f s'
        % (len(values), time.perf_counter() - t0))
    worst = max(values, default=0.0)
    over = sum(v > limit for v in values)
    due = win.offered // gpp
    missing = due - len(win.arrivals)
    in_window = sum(1 for k in win.kept
                    if win.k_open < k <= win.k_close)
    checks = {
        limit_name: {'value': worst, 'limit': limit},
        'products_missing': {'value': missing, 'limit': 0},
        'products_over_limit': {'value': over, 'limit': 0},
        'compared_in_window_min': {'value': in_window, 'limit': 1},
    }
    ok = worst <= limit and missing == 0 and over == 0 and in_window >= 1
    return checks, ok, due, max(missing, 0) + over


def main(argv=None, wrap_chain=None):
    args = parse_args(sys.argv[1:] if argv is None else argv)
    sys.path.insert(0, HERE)
    if args.selfcheck:
        import selfcheck
        return selfcheck.main()
    bench, cell, cfg, mod = load_cell(args.workload)
    seconds = float(bench['run_seconds']) if args.seconds is None \
        else args.seconds
    if args.rehearse:
        cfg = merge(cfg, cfg.get('rehearse', {}))
    control_env = mod.control_env(cfg) if args.control else None
    control = None if not args.control else \
        ('program' if control_env and not args.rehearse else 'reference')
    set_environment(control_env if control == 'program' else None)
    sys.path.insert(0, ROOT)

    import jax
    import peaks
    import traffic
    import drive
    dev = jax.devices()
    platform, kind = dev[0].platform, dev[0].device_kind
    if not args.rehearse and (platform != 'tpu' or len(dev) < cell['chips']):
        say('perfbench: cell %s needs %d TPU chip(s); JAX found %d x %s. '
            'No result.' % (cell['name'], cell['chips'], len(dev), platform))
        return 3
    peak = peaks.for_device(kind) if platform == 'tpu' else None
    import bifrost_tpu as bf

    say('set-up: %.1f s to JAX and the package' % (time.perf_counter() - T_START))
    mix = traffic.load(cell['traffic'])
    pool = traffic.make_pool(cfg, mix, args.seed)
    say('set-up: %.1f s to the pool' % (time.perf_counter() - T_START))
    order = traffic.replay_order(mix, args.seed)
    sampler = traffic.Sampler(cfg, mix, args.seed, mod.pick)
    tracer = Tracer(cell['name']) if args.trace else None
    try:
        win = drive.run_window(bf, mod, cfg, mix, pool, order, sampler,
                               seconds, tracer=tracer,
                               wrap_chain=wrap_chain)
    finally:
        trace = tracer.stop(args.keep_trace) if tracer else None
    if args.keep_trace and tracer and tracer.started:
        # what ties the kept trace to the host's clock (the self-check's
        # fixture is cut from such a pair)
        with open(os.path.join(args.keep_trace, 'stamps.json'), 'w') as f:
            json.dump({'anchor_stamps': tracer.stamps,
                       't_open': win.t_open, 't_close': win.t_close,
                       'spans': win.spans}, f)
    if win.t_close is None:
        say('perfbench: the window never closed (opened: %s, products '
            'seen: %d). No result.' % (win.t_open is not None,
                                       len(win.arrivals)))
        return 4
    memory_peak = max(int((d.memory_stats() or {})
                          .get('peak_bytes_in_use', 0))
                      for d in dev[:cell['chips']])
    gc.collect()        # the pipeline's cycles, before the reference runs

    checks, ok, attempted, failed = check_outputs(
        win, cfg, mod, pool, order, sampler, control)

    run = Run(win, cfg, mod, peak,
              reduce_trace(trace, tracer, win) if tracer else None,
              memory_peak)
    metrics = {}
    which = 'per_layer' if args.trace else 'end_to_end'
    for m in metrics_of(bench, cell, which):
        value = reader(which, m['name']).read(run)
        if value is not None:
            metrics[m['name']] = {'value': float(value), 'unit': m['unit']}
    device = {'platform': platform, 'kind': kind,
              'count': cell['chips'] if platform == 'tpu' else len(dev),
              'memory_peak_bytes': memory_peak}
    result = {'correct': bool(ok), 'attempted': int(attempted),
              'failed': int(failed), 'metrics': metrics, 'device': device}
    if run.trace():
        device['busy_s'] = run.trace()['busy_s']
        device['window_s'] = run.trace()['window_s']
        result['breakdown'] = {'device_ops': run.trace()['device_ops'],
                               'idle_gaps': run.trace()['idle_gaps']}
    result['window'] = {'seconds': win.seconds, 'products': win.products,
                        'gulps': run.gulps(), 'offered': win.offered,
                        'impl': win.impl_info}
    if args.rehearse:
        result['rehearsal'] = True
    if control:
        result['control'] = control
    result['checks'] = checks
    for line in run.notes:
        say(line)
    say('set-up: first product at %.1f s, window open at %.1f s'
        % (win.arrivals[0] - T_START, win.t_open - T_START))
    say('window: %.3f s, %d products of %d gulp(s), implementation %s'
        % (win.seconds, win.products, run.gpp,
           json.dumps(win.impl_info, sort_keys=True, default=str)))
    for name, c in checks.items():
        say('check %s: %r (limit %r)' % (name, c['value'], c['limit']))
    say('correct: %s' % bool(ok))
    print(json.dumps(result, default=str), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
