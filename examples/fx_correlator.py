"""Distributed FX correlator demo (reference architecture: the
xGPU-style FX pipeline, arXiv:1107.4264; docs/perf.md "FX
correlator").

  synthetic ci8 stations -> copy('tpu') -> FFT(fine -> freq)  [F]
    -> requantize ci8 -> CorrelateStageBlock (raced X-engine)  [X]
    -> accumulate -> convert_visibilities('storage') -> sink

The whole device chain is stage-backed (batch_safe), so under
``BF_SEGMENTS=auto`` the five blocks compile into ONE XLA program per
macro gulp — no f32 voltage spectra and no intermediate rings ever
land in HBM.  The X-engine consumes the ci8 planes directly on its
exact int32 path (accuracy='int8' races the quantized candidates;
outputs stay bit-identical to the int64 oracle).

Usage:
    python examples/fx_correlator.py             # single host
    python examples/fx_correlator.py --fabric    # two loopback
                                                 # bf_fabric hosts:
                                                 # 'stations' captures,
                                                 # 'xhost' correlates
"""

import os
import socket
import sys
import threading

import numpy as np

try:
    import bifrost_tpu as bf
except ImportError:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bifrost_tpu as bf

NT, NW, NS, NP = 32, 64, 8, 2    # frames/gulp, window, stations, pols
R, A = 8, 2                      # frames/vis, visibilities accumulated
NGULP = 4
TONE_BIN = 11


class StationSource(bf.pipeline.SourceBlock):
    """Synthesizes ci8 dual-pol station voltages: a common tone at
    fine bin ``TONE_BIN`` with a per-station phase gradient (so the
    visibility matrix shows off-diagonal fringes) over weak noise."""

    def __init__(self, ngulp=NGULP, **kwargs):
        super(StationSource, self).__init__(['stations'], NT,
                                            space='system', **kwargs)
        self.ngulp = ngulp
        self.count = 0
        rng = np.random.RandomState(19)
        t = np.arange(NT * NW).reshape(NT, NW)
        tone = np.exp(2j * np.pi * TONE_BIN * (t % NW) / NW)
        phase = np.exp(2j * np.pi * np.arange(NS) / NS)
        v = tone[:, :, None, None] * phase[None, None, :, None] * 50
        v = v + 4 * (rng.randn(NT, NW, NS, NP) +
                     1j * rng.randn(NT, NW, NS, NP))
        self.gulp = np.zeros((NT, NW, NS, NP),
                             dtype=np.dtype([('re', 'i1'),
                                             ('im', 'i1')]))
        self.gulp['re'] = np.clip(np.round(v.real), -128, 127)
        self.gulp['im'] = np.clip(np.round(v.imag), -128, 127)

    def create_reader(self, name):
        class R(object):
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False
        return R()

    def on_sequence(self, reader, name):
        self.count = 0
        return [{'name': 'stations', 'time_tag': 0,
                 '_tensor': {'shape': [-1, NW, NS, NP],
                             'dtype': 'ci8',
                             'labels': ['time', 'fine', 'station',
                                        'pol'],
                             'scales': [[0, 1]] * 4,
                             'units': [None] * 4}}]

    def on_data(self, reader, ospans):
        if self.count >= self.ngulp:
            return [0]
        self.count += 1
        ospans[0].data.as_numpy()[...] = self.gulp
        return [NT]


class PrintVisibilities(bf.pipeline.SinkBlock):
    """Prints per-integration fringe diagnostics from the packed
    storage-format (time, baseline, freq, stokes) stream."""

    def on_sequence(self, iseq):
        shape = iseq.header['_tensor']['shape']
        print('visibilities: %d baselines x %d channels (storage '
              'IQUV)' % (shape[1], shape[2]))

    def on_data(self, ispan):
        from bifrost_tpu.xfer import to_host
        vis = to_host(ispan.data) if ispan.ring.space == 'tpu' \
            else np.asarray(ispan.data.as_numpy())
        stokes_i = np.abs(vis[..., 0])          # (t, nbl, f)
        for t in range(vis.shape[0]):
            peak = int(np.argmax(stokes_i[t].max(axis=0)))
            cross = stokes_i[t, :, peak]
            print('  integration: tone at channel %d, |I| auto %.0f '
                  'cross-mean %.0f'
                  % (peak, cross[0], float(np.mean(cross[1:]))))


def build_xchain(b):
    """The F -> requantize -> X -> accumulate -> storage device chain
    (every block stage-backed: one fused segment under
    BF_SEGMENTS=auto)."""
    b = bf.blocks.copy(b, space='tpu')
    b = bf.blocks.fft(b, axes='fine', axis_labels='freq')
    b = bf.blocks.quantize(b, 'ci8', scale=1. / NW)
    b = bf.blocks.correlate(b, R, accuracy='int8', fusable=True)
    b = bf.blocks.accumulate(b, A, fusable=True)
    b = bf.blocks.convert_visibilities(b, 'storage')
    return bf.blocks.copy(b, space='system')


def run_single():
    with bf.Pipeline() as p:
        PrintVisibilities(build_xchain(StationSource()))
        p.run()


def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_fabric():
    """The same chain split over a two-host loopback fabric: the
    'stations' host captures ci8 voltages into the 'voltages' link;
    the 'xhost' host runs the F/X chain (docs/fabric.md)."""
    from bifrost_tpu import fabric

    spec = fabric.FabricSpec('fxcorr_demo', hosts={
        'stations': {'address': '127.0.0.1', 'role': 'capture'},
        'xhost': {'address': '127.0.0.1', 'role': 'reduce'},
    }, links={
        'voltages': {'kind': 'pipe', 'src': 'stations',
                     'dst': 'xhost', 'port': _free_port(),
                     'window': 2,
                     'gulp_nbyte': NT * NW * NS * NP * 2},
    })

    def build_stations(ctx):
        ctx.sink('voltages', StationSource())

    def build_xhost(ctx):
        PrintVisibilities(build_xchain(ctx.source('voltages')))

    hosts = {}
    for name, builder in (('xhost', build_xhost),
                          ('stations', build_stations)):
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


def main():
    if '--fabric' in sys.argv[1:]:
        run_fabric()
    else:
        run_single()


if __name__ == '__main__':
    main()
