"""FDMT FRB-search demo (reference: testbench/test_fdmt.py;
docs/perf.md "FDMT FRB search"): synthesize dispersed pulses in a
filterbank stream, dedisperse with the stage-backed FDMT engine,
matched-filter across pulse widths, threshold at a fixed false-alarm
rate, and report the detected DM/time.

  dispersed filterbank -> copy('tpu') -> fdmt_stage  [DM transform]
    -> matched_filter (boxcar) -> threshold -> copy('system') -> peak

Every device block is stage-backed (batch_safe), so under
``BF_SEGMENTS=auto`` the chain compiles into ONE XLA program per macro
gulp — the ``overlap`` boundaries are lifted by the in-program halo
carry (BF-I192) and the interior DM-transform rings never land in HBM.

Usage:
    python examples/fdmt_search.py             # single host
    python examples/fdmt_search.py --fabric    # two loopback
                                               # bf_fabric hosts:
                                               # 'capture' streams the
                                               # filterbank, 'search'
                                               # dedisperses
"""

import os
import socket
import sys
import threading

try:
    import bifrost_tpu as bf
except ImportError:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bifrost_tpu as bf

import numpy as np

from bifrost_tpu.xfer import to_host


def cff(f1, f2):
    """Quadratic dispersion delay factor between two frequencies."""
    return abs(f1 ** -2 - f2 ** -2)


NCHAN, NTIME, F0, DF = 64, 1024, 100.0, 1.0   # MHz
GULP = 256
MAX_DELAY = 64                                # DM trials (samples)
NTAP = 4                                      # boxcar matched filter
THRESH = 8.0                                  # ~5 sigma after the boxcar
D_TRUE, T0 = 40, 200                          # delay (samples), pulse time


class DispersedPulseSource(bf.SourceBlock):
    def __init__(self, **kwargs):
        super(DispersedPulseSource, self).__init__(
            ['pulse'], gulp_nframe=GULP, **kwargs)

    def create_reader(self, name):
        class R(object):
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False
        return R()

    def on_sequence(self, reader, name):
        rng = np.random.RandomState(0)
        x = rng.randn(NCHAN, NTIME).astype(np.float32) * 0.1
        band = cff(F0, F0 + NCHAN * DF)
        for c in range(NCHAN):
            delay = D_TRUE * cff(F0, F0 + c * DF) / band
            x[c, T0 + int(round(delay))] += 3.0
        self.data = x
        self.pos = 0
        return [{'name': 'pulse',
                 '_tensor': {'shape': [NCHAN, -1], 'dtype': 'f32',
                             'labels': ['freq', 'time'],
                             'scales': [[F0, DF], [0.0, 1e-3]],
                             'units': ['MHz', 's']}}]

    def on_data(self, reader, ospans):
        if self.pos >= NTIME:
            return [0]
        n = min(ospans[0].nframe, NTIME - self.pos)
        ospans[0].data.as_numpy()[:, :n] = \
            self.data[:, self.pos:self.pos + n]
        self.pos += n
        return [n]


class PeakFinder(bf.SinkBlock):
    """Tracks the strongest above-threshold candidate in the
    (dm, time) stream; everything below THRESH arrives zeroed."""

    def __init__(self, iring, **kwargs):
        super(PeakFinder, self).__init__(iring, **kwargs)
        self.best = (-np.inf, 0, 0)
        self.ncandidates = 0
        self.offset = 0

    def on_sequence(self, iseq):
        self.dm_step = iseq.header['_tensor']['scales'][-2][1]

    def on_data(self, ispan):
        dmt = np.asarray(to_host(ispan.data))
        self.ncandidates += int(np.count_nonzero(dmt))
        row, t = np.unravel_index(np.argmax(dmt), dmt.shape)
        if dmt[row, t] > self.best[0]:
            self.best = (float(dmt[row, t]), int(row),
                         self.offset + int(t))
        self.offset += ispan.nframe


def build_search_chain(b):
    """The dedispersion device chain (every block stage-backed: one
    halo-carried segment under BF_SEGMENTS=auto)."""
    b = bf.blocks.copy(b, space='tpu')
    b = bf.blocks.fdmt_stage(b, max_delay=MAX_DELAY)
    b = bf.blocks.matched_filter(b, NTAP)
    b = bf.blocks.threshold(b, THRESH)
    return bf.blocks.copy(b, space='system')


def run_single():
    with bf.Pipeline() as pipeline:
        peak = PeakFinder(build_search_chain(DispersedPulseSource()))
        pipeline.run()
    return peak


def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_fabric():
    """The same chain split over a two-host loopback fabric: the
    'capture' host streams the filterbank into the 'filterbank' link;
    the 'search' host dedisperses (docs/fabric.md)."""
    from bifrost_tpu import fabric

    spec = fabric.FabricSpec('fdmt_demo', hosts={
        'capture': {'address': '127.0.0.1', 'role': 'capture'},
        'search': {'address': '127.0.0.1', 'role': 'reduce'},
    }, links={
        'filterbank': {'kind': 'pipe', 'src': 'capture',
                       'dst': 'search', 'port': _free_port(),
                       'window': 2,
                       'gulp_nbyte': NCHAN * GULP * 4},
    })

    peaks = []

    def build_capture(ctx):
        ctx.sink('filterbank', DispersedPulseSource())

    def build_search(ctx):
        peaks.append(PeakFinder(
            build_search_chain(ctx.source('filterbank'))))

    hosts = {}
    for name, builder in (('search', build_search),
                          ('capture', build_capture)):
        hosts[name] = fabric.FabricHost(spec, name, builder,
                                        jitter=False)
        hosts[name].build()
    threads = [threading.Thread(target=fh.run,
                                kwargs={'install_signals': False})
               for fh in hosts.values()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    return peaks[0] if peaks else None


def main():
    peak = run_fabric() if '--fabric' in sys.argv[1:] else run_single()
    if peak is None:
        return
    snr, row, t = peak.best
    print("%d candidate samples above %.1f; peak %.1f at DM row %d "
          "(true %d), t=%d (true %d), DM = %.3f pc/cm^3"
          % (peak.ncandidates, THRESH, snr, row, D_TRUE, t, T0,
             row * peak.dm_step))


if __name__ == '__main__':
    main()
