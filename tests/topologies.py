"""Build-only replicas of the pipeline topologies the repo ships, for
the static verifier (docs/analysis.md).

Every builder constructs its block/ring graph from the package's own
blocks and never runs it; ``tests/test_analysis.py::
test_shipped_topologies_validate_clean`` calls ``validate()`` on each.
A builder returns one Pipeline or a list of them.  The plain fused
spectroscopy chain is not here: ``test_clean_chain_validates_clean``
holds it to the stricter no-warning bar.
"""

from __future__ import annotations

import copy

import numpy as np

import bifrost_tpu as bf
from bifrost_tpu import fabric, scheduler, service
from bifrost_tpu.analysis.verify import verify_fabric, verify_placement
from bifrost_tpu.blocks.bridge import bridge_sink, bridge_source
from bifrost_tpu.stages import (DetectStage, FftStage, ReduceStage)
from tests.util import (GatherSink, NumpySourceBlock, free_ports,
                        port_block, simple_header)

CI8 = np.dtype([('re', 'i1'), ('im', 'i1')])


def _spectroscopy_chain(**pipe_kwargs):
    """host src -> copy h2d -> fused FFT->detect->reduce -> copy d2h ->
    sink, under the given pipeline tunables."""
    NT, NP, NF, RF = 64, 2, 256, 4
    raw = np.zeros((NT, NP, NF), dtype=CI8)
    hdr = simple_header([-1, NP, NF], 'ci8',
                        labels=['time', 'pol', 'fine_time'])
    with bf.Pipeline(sync_depth=4, **pipe_kwargs) as p:
        src = NumpySourceBlock([raw], hdr, gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        fb = bf.blocks.fused(
            b, [FftStage('fine_time', axis_labels='freq'),
                DetectStage('stokes', axis='pol'),
                ReduceStage('freq', RF)])
        GatherSink(bf.blocks.copy(fb, space='system'))
    return p


def macro_chain():
    """The chain at macro-gulp K=16: the ring-sizing bound (BF-E101)
    must hold with K-gulp spans, which is also the bound the
    auto-tuner's retune gate enforces online (docs/autotune.md)."""
    return _spectroscopy_chain(gulp_batch=16)


def mesh_chain():
    """The chain at K=4 under a mesh over the host's devices."""
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), ('sp',))
    return _spectroscopy_chain(gulp_batch=4, mesh=mesh)


def _bridge_pair(nframe, nchan, tx_kwargs=None, **sink_kwargs):
    raw = np.zeros((nframe, nchan), np.float32)
    hdr = simple_header([-1, nchan], 'f32', gulp_nframe=nframe)
    with bf.Pipeline() as prx:
        src_rx = bridge_source('127.0.0.1', 0)
        GatherSink(src_rx)
    with bf.Pipeline(**(tx_kwargs or {})) as ptx:
        src = NumpySourceBlock([raw], hdr, gulp_nframe=nframe)
        bridge_sink(src, '127.0.0.1', src_rx.port, **sink_kwargs)
    return [ptx, prx]


def bridge_pair():
    """Two pipelines joined by the ring bridge (sender: src ->
    BridgeSink; receiver: BridgeSource -> sink)."""
    return _bridge_pair(64, 256)


def shedding_bridge_pair():
    """A drop_oldest source ring under a restart policy feeding a
    BridgeSink at window=2: the sink declares its own shed tolerance,
    so the drop policy must not raise BF-E180."""
    return _bridge_pair(4, 64, window=2,
                        tx_kwargs={'overload_policy': 'drop_oldest',
                                   'on_failure': 'restart'})


def beamform_chain():
    """ci8 capture -> beamform at the 'int8' class -> Stokes detect ->
    integrate.  The class engages the integer candidates on the ci8
    ring, so no float-on-quantized warning (BF-W170)."""
    NT, NF, NS, NP, NB, RF = 32, 64, 256, 2, 128, 8
    raw = np.zeros((NT, NF, NS, NP), dtype=CI8)
    w = np.zeros((NP, NB, NS), np.complex64)
    hdr = simple_header([-1, NF, NS, NP], 'ci8',
                        labels=['time', 'freq', 'station', 'pol'],
                        gulp_nframe=NT)
    with bf.Pipeline(sync_depth=4) as p:
        src = NumpySourceBlock([raw], hdr, gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        beam = bf.blocks.beamform(b, w, accuracy='int8')
        fb = bf.blocks.fused(beam, [DetectStage('stokes', axis='pol'),
                                    ReduceStage('time', RF)])
        GatherSink(bf.blocks.copy(fb, space='system'))
    return p


def unfused_chain():
    """The spectroscopy math as SEPARATE fft/detect/reduce device
    blocks at K=16, built without segments engaged: the graph a
    segment compiler would fuse must itself be clean."""
    NT, NP, NF, RF = 64, 2, 256, 4
    raw = np.zeros((NT, NP, NF), dtype=CI8)
    hdr = simple_header([-1, NP, NF], 'ci8',
                        labels=['time', 'pol', 'fine_time'])
    with bf.Pipeline(sync_depth=4, gulp_batch=16) as p:
        src = NumpySourceBlock([raw], hdr, gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fft(b, axes='fine_time', axis_labels='freq')
        b = bf.blocks.detect(b, mode='stokes', axis='pol')
        b = bf.blocks.reduce(b, 'freq', RF)
        GatherSink(bf.blocks.copy(b, space='system'))
    return p


def fabric_hosts():
    """All four hosts' sub-pipelines of ONE FabricSpec on loopback
    (two capture hosts fan in to a reduce host, which fans out to a
    leg).  The spec passes ``verify_fabric`` first; the fan-out leg
    rings run drop_oldest with a shed-tolerant BridgeSink reader."""
    NT, NC = 4, 16
    cap_base = port_block(2)             # 2-origin fan-in: port, +1
    ports = [cap_base] + free_ports(2, exclude=(cap_base, cap_base + 1))
    spec = fabric.FabricSpec('shipped', hosts={
        'cap0': {'address': '127.0.0.1', 'role': 'capture'},
        'cap1': {'address': '127.0.0.1', 'role': 'capture'},
        'reduce': {'address': '127.0.0.1', 'role': 'reduce'},
        'leg0': {'address': '127.0.0.1', 'role': 'leg'},
    }, links={
        'capture': {'kind': 'fanin', 'src': ['cap0', 'cap1'],
                    'dst': 'reduce', 'port': ports[0], 'window': 2,
                    'gulp_nbyte': NT * NC * 4},
        'spectra': {'kind': 'fanout', 'src': 'reduce',
                    'dst': ['leg0'], 'port': ports[2], 'window': 2,
                    'buffer_spans': 8, 'gulp_nbyte': NT * NC * 4},
    })
    spec_errs = [d for d in verify_fabric(spec) if d.is_error]
    assert not spec_errs, spec_errs
    raw = np.zeros((NT, NC), np.float32)
    hdr = simple_header([-1, NC], 'f32', gulp_nframe=NT)

    def build_cap(ctx):
        ctx.sink('capture', NumpySourceBlock([raw], hdr, NT))

    def build_reduce(ctx):
        ctx.sink('spectra', ctx.source('capture'))

    def build_leg(ctx):
        GatherSink(ctx.source('spectra'))

    return [fabric.FabricHost(spec, host, builder, jitter=False).build()
            for host, builder in (('leg0', build_leg),
                                  ('reduce', build_reduce),
                                  ('cap0', build_cap),
                                  ('cap1', build_cap))]


def _admit(specs):
    """Tenant pipelines as a JobManager builds them; ``submit`` runs
    ``verify_service`` over the combined spec (no BF-E21x)."""
    service.reset_registry()
    mgr = service.JobManager(max_tenants=4, warm=False)
    return [mgr.submit(s).pipeline for s in specs]


def service_tenants():
    """Three tenants (replay, file ingest, synthetic), each source ->
    quota gate -> sink.  Sources open their files lazily, so nothing
    need exist on disk for the build."""
    return _admit([
        service.TenantSpec(
            'replay', priority=2, quota_bytes_per_s=64 * 1024,
            quota_policy='pace', gulp_nframe=32,
            source={'kind': 'replay', 'basenames': ['svc-src'],
                    'gulp_nframe': 32, 'loop': 3, 'restamp': True}),
        service.TenantSpec(
            'filein', quota_bytes_per_s=256 * 1024,
            quota_policy='pace', gulp_nframe=32,
            source={'kind': 'file', 'paths': ['svc-ingest.bin'],
                    'gulp_size': 256, 'gulp_nframe': 32,
                    'dtype': 'f32'}),
        service.TenantSpec(
            'synth', gulp_nframe=32,
            source={'kind': 'synthetic', 'nframe_total': 1280,
                    'gulp_nframe': 32, 'nchan': 16, 'seed': 3}),
    ])


def fx_correlator_chain():
    """ci8 stations -> F -> requantize -> X (stage-backed, raced
    X-engine) -> accumulate at K=4.  The X-engine's exact integer
    candidates race at every accuracy class, so no BF-W170."""
    NT, NW, NS, NP = 32, 64, 32, 2
    raw = np.zeros((NT, NW, NS, NP), dtype=CI8)
    hdr = simple_header([-1, NW, NS, NP], 'ci8',
                        labels=['time', 'fine', 'station', 'pol'])
    with bf.Pipeline(sync_depth=4, gulp_batch=4) as p:
        src = NumpySourceBlock([raw], hdr, gulp_nframe=NT)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fft(b, axes='fine', axis_labels='freq')
        b = bf.blocks.quantize(b, 'ci8', scale=1. / NW)
        b = bf.blocks.correlate(b, 8, accuracy='int8', fusable=True)
        b = bf.blocks.accumulate(b, 4, fusable=True)
        GatherSink(bf.blocks.copy(b, space='system'))
    return p


def scheduled_tenants():
    """A 3-host fabric spec and 3 tenants under pinning: the plan must
    pass the joint ``verify_placement`` pre-gate (no BF-E22x) and every
    tenant pipeline must be clean.  Declarative: no socket binds."""
    spec = {
        'name': 'sched',
        'hosts': {
            'head': {'address': '127.0.0.1', 'control_port': 47200,
                     'role': 'control', 'cores': [3]},
            'hostA': {'address': '127.0.0.1', 'control_port': 47201,
                      'role': 'worker', 'cores': [0, 1]},
            'hostB': {'address': '127.0.0.1', 'control_port': 47202,
                      'role': 'worker', 'cores': [0, 1, 2]},
        },
        'links': {
            'stream': {'kind': 'fanin', 'src': ['hostA', 'hostB'],
                       'dst': 'head', 'port': 47210, 'window': 2,
                       'gulp_nbyte': 32 * 64 * 4},
        },
    }

    def synthetic(nframe_total, nchan, seed):
        return {'kind': 'synthetic', 'nframe_total': nframe_total,
                'gulp_nframe': 32, 'nchan': nchan, 'seed': seed}

    tenants = [
        service.TenantSpec('vic', priority=2, ncores=2, gulp_nframe=32,
                           source=synthetic(1920, 64, 11)),
        service.TenantSpec('slo', priority=2, ncores=1, gulp_nframe=32,
                           slo_ms=2000, quota_bytes_per_s=4096.0,
                           quota_policy='pace',
                           source=synthetic(1600, 16, 5)),
        service.TenantSpec('bulk', priority=1, ncores=1, gulp_nframe=32,
                           quota_bytes_per_s=64000.0,
                           quota_policy='shed',
                           source=synthetic(16000, 16, 6)),
    ]
    placement = scheduler.plan_placement(
        spec, tenants, exclude=('head',),
        pinned={'vic': 'hostA', 'slo': 'hostB', 'bulk': 'hostB'})
    errs = [d for d in verify_placement(spec, tenants,
                                        placement.assignments)
            if d.is_error]
    assert not errs, errs
    return _admit(tenants)


def frb_search_chain():
    """Channelized intensities -> FDMT -> matched filter -> threshold
    at K=4: the overlap consumers' macro batching must be admitted."""
    NCHAN, GULP, MD, NTAP = 32, 64, 32, 8
    hdr = {'_tensor': {'shape': [NCHAN, -1], 'dtype': 'f32',
                       'labels': ['freq', 'time'],
                       'scales': [[100.0, 1.0], [0.0, 1e-3]],
                       'units': ['MHz', 's']},
           'name': 'frb_search', 'time_tag': 0}

    class _Reader(object):
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    class _Src(bf.SourceBlock):
        def create_reader(self, name):
            return _Reader()

        def on_sequence(self, reader, name):
            return [copy.deepcopy(hdr)]

        def on_data(self, reader, ospans):
            return [0]

    class _Sink(bf.SinkBlock):
        def on_sequence(self, iseq):
            pass

        def on_data(self, ispan):
            pass

    with bf.Pipeline(sync_depth=4, gulp_batch=4) as p:
        src = _Src(['frb'], gulp_nframe=GULP)
        b = bf.blocks.copy(src, space='tpu')
        b = bf.blocks.fdmt_stage(b, max_delay=MD)
        b = bf.blocks.matched_filter(b, NTAP)
        b = bf.blocks.threshold(b, 1.0)
        _Sink(bf.blocks.copy(b, space='system'))
    return p


def udp_capture_tenant():
    """A 'udp' service tenant on the sharded REUSEPORT engine
    (capture_threads=2).  ring_nframe and ingest_bytes_per_s agree
    with the quota, so the capture checks BF-W230 (ring below two
    capture spans) and BF-W231 (quota below declared ingest) are
    clean."""
    return _admit([service.TenantSpec(
        'wirecap', priority=2, quota_bytes_per_s=8 << 20,
        quota_policy='pace', gulp_nframe=64,
        source={'kind': 'udp', 'format': 'chips',
                'address': '127.0.0.1', 'port': 0, 'nsrc': 2,
                'payload': 1024, 'buffer_ntime': 64,
                'ring_nframe': 256, 'capture_threads': 2,
                'capture_vlen': 64, 'ingest_bytes_per_s': 4 << 20})])


#: case name -> builder.  The names are those the pre-chip benchmark
#: suite gave its topologies (the audit in CHANGES.md, PR 30).
TOPOLOGIES = {
    'config9_macro': macro_chain,
    'config10_bridge': bridge_pair,
    'config11_mesh': mesh_chain,
    'config13_beamform': beamform_chain,
    'config15_chaos': shedding_bridge_pair,
    'config16_segments': unfused_chain,
    'config17_fabric': fabric_hosts,
    'config18_service': service_tenants,
    'config19_fxcorr': fx_correlator_chain,
    'config20_sched': scheduled_tenants,
    'config22_fdmt': frb_search_chain,
    'config23_capture': udp_capture_tenant,
}
