"""The drive: one ``Pipeline.run()`` that warms up, is measured for the
window and drains.

    source (pool replay) -> [copy('tpu')] -> chain -> [copy('system')] -> sink

The source and the sink are the benchmark's; everything between them is
the system under test, built through its public API as the traffic mix
and the configuration say.  The same pipeline object that set-up warmed
up is the one the window measures: the window opens at the arrival of a
product at the sink (the first after the warm-up products, and after
the profiler has started in a traced run) and closes at the first
arrival ``seconds`` or more later.  Every rate is all the work between
those two arrivals over all the time between them.
"""

import contextlib
import os
import threading
import time

import numpy as np


class Window(object):
    """What the sink saw; filled by the run, read by the metrics."""

    def __init__(self):
        self.t_open = self.t_close = None
        self.cpu_open = self.cpu_close = None
        self.k_open = self.k_close = None     # product indices at the edges
        self.arrivals = []                    # perf_counter per product
        self.writes = []                      # perf_counter per gulp offered
        self.spans = {'bench.feed.write': [],  # [start, end] on the host
                      'bench.sink.take': []}   # clock, per call
        self.kept = {}                        # product index -> sampled part
        self.offered = 0
        self.hists = [None, None]             # program's histograms at the edges
        self.blocks = []                      # (name, in rings, out rings) of
        #                                       the program's own blocks
        self.impl_info = None

    @property
    def seconds(self):
        return self.t_close - self.t_open

    @property
    def products(self):
        return self.k_close - self.k_open


def _cpu_seconds():
    t = os.times()
    return t.user + t.system


def _histograms():
    from bifrost_tpu.telemetry import histograms
    return histograms.snapshot()


def run_window(bf, mod, cfg, mix, pool, order, sampler, seconds,
               tracer=None, wrap_chain=None, max_wait=300.0):
    """Build the pipeline, run it once, return its :class:`Window`.

    ``tracer``: an object with ``start()`` (called off the block
    threads once the warm-up products have arrived) or None.
    ``wrap_chain(where, block) -> block`` (``where`` is 'input' or
    'output' of the chain) lets the fault tests break the timed path
    underneath; the benchmark's own runs never pass it.
    """
    import jax
    from bifrost_tpu.pipeline import SourceBlock, SinkBlock
    from bifrost_tpu.devrep import to_device_rep

    win = Window()
    feed_spans = win.spans['bench.feed.write']
    sink_spans = win.spans['bench.sink.take']
    gpp = mod.gulps_per_product(cfg)
    ntime = cfg['gulp_nframe']
    warm = int(mix['warm_products'])
    on_device = mix['source_space'] == 'tpu'
    device_sink = mix['sink_space'] == 'tpu'
    if on_device:
        feed_pool = [to_device_rep(g, cfg['input']['dtype']) for g in pool]
        jax.block_until_ready(feed_pool)
    else:
        feed_pool = pool
    stop = threading.Event()          # the window has closed: offer no more
    warmed = threading.Event()        # the warm-up products have arrived
    ready = threading.Event()         # the window may open
    deadline = time.perf_counter() + max_wait + seconds

    class Feed(SourceBlock):
        def __init__(self):
            super(Feed, self).__init__(['pool'], ntime,
                                       space=mix['source_space'])

        def create_reader(self, name):
            return contextlib.nullcontext()

        def on_sequence(self, reader, name):
            return [mod.header(cfg)]

        def on_data(self, reader, ospans):
            k = win.offered
            # stop only between products, so that every gulp offered
            # is part of a product that reaches the sink
            if k % gpp == 0 and (stop.is_set() or
                                 time.perf_counter() > deadline):
                return [0]
            t0 = time.perf_counter()
            win.writes.append(t0)
            gulp = feed_pool[order[k % len(order)]]
            if on_device:
                ospans[0].set(gulp)
            else:
                # byte views: a structured (re, im) assignment copies
                # field by field, twenty times slower
                dst = ospans[0].data.as_numpy()
                np.copyto(dst.view(np.uint8), gulp.view(np.uint8))
            win.offered = k + 1
            feed_spans.append([t0, time.perf_counter()])
            return [ntime]

    class Sink(SinkBlock):
        def on_sequence(self, iseq):
            pass

        def on_data(self, ispan):
            t0 = time.perf_counter()
            product = ispan.data if device_sink \
                else ispan.data.as_numpy()
            k = len(win.arrivals)
            win.kept[k] = mod.take(product, sampler.where(k))   # a copy
            edge = (win.t_open is None and k + 1 >= warm
                    and ready.is_set()) or \
                (win.t_open is not None and win.t_close is None and
                 time.perf_counter() >= win.t_open + seconds)
            if edge and device_sink:
                jax.block_until_ready(product)
            now = time.perf_counter()
            win.arrivals.append(now)
            sink_spans.append([t0, now])
            if k + 1 == warm:
                warmed.set()
            if not edge:
                return
            if win.t_open is None:
                win.cpu_open, win.t_open, win.k_open = \
                    _cpu_seconds(), now, k
                win.hists[0] = _histograms()
            else:
                win.cpu_close, win.t_close, win.k_close = \
                    _cpu_seconds(), now, k
                win.hists[1] = _histograms()
                stop.set()

    def open_when_warm():
        warmed.wait()
        if tracer is not None:
            tracer.start()
        ready.set()

    opener = threading.Thread(target=open_when_warm, name='bench-opener',
                              daemon=True)
    with bf.Pipeline() as pipe:
        src = Feed()
        blk = src if on_device else bf.blocks.copy(src, space='tpu')
        if wrap_chain is not None:
            blk = wrap_chain('input', blk)
        blk = last = mod.chain(bf, blk, cfg)
        if wrap_chain is not None:
            blk = wrap_chain('output', blk)
        if not device_sink:
            blk = bf.blocks.copy(blk, space='system')
        snk = Sink(blk)
        win.blocks = [(b.name, [r.name for r in b.irings],
                       [r.name for r in b.orings])
                      for b in pipe.blocks if b is not src and b is not snk]
        opener.start()
        try:
            pipe.run()
        finally:
            warmed.set()
            opener.join()
    win.impl_info = getattr(last, 'impl_info', None)
    if device_sink:
        win.kept = {k: np.asarray(v) for k, v in win.kept.items()}
    return win
