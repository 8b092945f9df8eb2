"""Multi-tenant streaming service tier (bifrost_tpu.service —
docs/service.md): spec validation, admission control, core
partitioning, quota enforcement, blast-radius isolation, warm starts,
looped replay, and the per-tenant telemetry surfaces."""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import bifrost_tpu as bf
from bifrost_tpu import affinity, service
from bifrost_tpu.analysis import verify
from bifrost_tpu.blocks.serialize import DeserializeBlock
from bifrost_tpu.telemetry import counters, exporter
from bifrost_tpu.testing import faults

from util import GatherSink, NumpySourceBlock, simple_header

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_service_state():
    counters.reset()
    service.reset_registry()
    service.reset_warm_registry()
    faults.clear()
    yield
    faults.clear()
    service.reset_registry()
    service.reset_warm_registry()
    counters.reset()


def synth_spec(tid, nframe=128, gulp=16, nchan=8, seed=3, tick=0.0,
               **kw):
    return service.TenantSpec(tid, source={
        'kind': 'synthetic', 'nframe_total': nframe,
        'gulp_nframe': gulp, 'nchan': nchan, 'seed': seed,
        'tick_s': tick}, **kw)


def gather_build(store, tid):
    def build(gate):
        store[tid] = GatherSink(gate)
    return build


# ---------------------------------------------------------------------------
# spec & static validation
# ---------------------------------------------------------------------------

def test_spec_from_dict_roundtrip():
    spec = service.TenantSpec.coerce({
        'id': 'a-1', 'source': {'kind': 'synthetic'}, 'priority': 3,
        'ncores': 2, 'quota_bytes_per_s': 1e6,
        'quota_policy': 'pace', 'slo_ms': 250, 'gulp_nframe': 64})
    d = spec.as_dict()
    spec2 = service.TenantSpec.coerce(d)
    assert spec2.id == 'a-1' and spec2.priority == 3
    assert spec2.quota_bytes_per_s == 1e6
    assert spec2.quota_policy == 'pace'
    assert spec2.slo_ms == 250
    # bad ids / kinds / policies fail at construction, not at run
    with pytest.raises(ValueError):
        service.TenantSpec('bad id!')
    with pytest.raises(ValueError):
        service.TenantSpec('x', source={'kind': 'nope'})
    with pytest.raises(ValueError):
        service.TenantSpec('x', quota_policy='drop')
    with pytest.raises(ValueError):
        service.TenantSpec.coerce({'id': 'x', 'bogus_field': 1})


def test_verify_service_duplicate_id():
    diags = verify.verify_service([{'id': 'a'}, {'id': 'a'}],
                                  ncores=64)
    assert [d.code for d in diags] == ['BF-E210']
    assert diags[0].is_error and diags[0].block == 'tenant:a'


def test_verify_service_quota_below_gulp():
    diags = verify.verify_service(
        [{'id': 'a', 'quota_bytes_per_s': 100, 'gulp_nbyte': 4096}],
        ncores=64)
    assert [d.code for d in diags] == ['BF-E211']


def test_verify_service_pace_quota_exempt():
    diags = verify.verify_service(
        [{'id': 'a', 'quota_bytes_per_s': 100, 'gulp_nbyte': 4096,
          'quota_policy': 'pace'}], ncores=64)
    assert diags == []


def test_verify_service_core_oversubscription():
    diags = verify.verify_service(
        [{'id': 'a', 'ncores': 3}, {'id': 'b', 'ncores': 2}],
        ncores=4)
    assert [d.code for d in diags] == ['BF-W212']
    assert not diags[0].is_error


def test_verify_service_codes_catalogued():
    for code in ('BF-E210', 'BF-E211', 'BF-W212'):
        assert code in verify.CODES
        with open(os.path.join(ROOT, 'docs', 'analysis.md')) as f:
            assert code in f.read()


# ---------------------------------------------------------------------------
# affinity partitioning
# ---------------------------------------------------------------------------

def test_partition_cores_priority_weighted():
    shares = affinity.partition_cores({'a': 3, 'b': 1},
                                      cores=list(range(8)))
    assert sorted(shares['a'] + shares['b']) == list(range(8))
    assert len(shares['a']) == 6 and len(shares['b']) == 2


def test_partition_cores_floor_and_equal_split():
    shares = affinity.partition_cores({'a': 100, 'b': 1},
                                      cores=[0, 1])
    # the 1-core floor holds even under extreme weights
    assert len(shares['a']) == 1 and len(shares['b']) == 1
    eq = affinity.partition_cores({'a': 1, 'b': 1, 'c': 1},
                                  cores=list(range(6)))
    assert all(len(v) == 2 for v in eq.values())


def test_partition_cores_oversubscription():
    # more tenants than cores: round-robin sharing, >= 1 core each
    shares = affinity.partition_cores(
        {'a': 1, 'b': 1, 'c': 1}, cores=[4, 5])
    assert [shares[t] for t in 'abc'] == [[4], [5], [4]]
    assert affinity.partition_cores({}, cores=[0]) == {}
    assert affinity.partition_cores({'a': 1}, cores=[]) == {'a': []}


def test_manager_counts_affinity_applied():
    before = counters.get('service.affinity.applied')
    mgr = service.JobManager(max_tenants=4, cores=[0], warm=False)
    store = {}
    mgr.submit(synth_spec('aff0', nframe=16), gather_build(store,
                                                           'aff0'))
    applied = counters.get('service.affinity.applied') - before
    job = mgr.job('aff0')
    assert applied == len(job.pipeline.blocks)
    assert all(b.core == 0 for b in job.pipeline.blocks)


# ---------------------------------------------------------------------------
# looped replay (blocks/serialize.py hardening)
# ---------------------------------------------------------------------------

def _record_stream(tmpdir, nframe=64, nchan=8, gulp=16):
    rng = np.random.RandomState(11)
    data = rng.randn(nframe, nchan).astype(np.float32)
    hdr = simple_header([-1, nchan], 'f32', name='rec',
                        gulp_nframe=gulp)
    with bf.Pipeline() as p:
        src = NumpySourceBlock(
            [data[i:i + gulp] for i in range(0, nframe, gulp)], hdr,
            gulp_nframe=gulp)
        bf.blocks.serialize(src, path=tmpdir)
    p.run()
    return os.path.join(tmpdir, 'rec'), data


def test_deserialize_loop_roundtrip(tmp_path):
    base, data = _record_stream(str(tmp_path))
    with bf.Pipeline() as p:
        b = DeserializeBlock([base], 16, loop=3, restamp=True)
        sink = GatherSink(b)
    p.run()
    assert np.array_equal(sink.result(), np.tile(data, (3, 1)))
    assert len(sink.headers) == 3


def test_deserialize_loop_renumber_and_restamp(tmp_path):
    base, _data = _record_stream(str(tmp_path))
    with bf.Pipeline() as p:
        b = DeserializeBlock([base], 16, loop=3, restamp=True)
        sink = GatherSink(b)
    p.run()
    names = [h.get('name') for h in sink.headers]
    tags = [h.get('time_tag') for h in sink.headers]
    traces = [h.get('_trace', {}).get('id') for h in sink.headers]
    assert names == ['rec', 'rec.loop1', 'rec.loop2']
    # renumbered on EVERY pass: unique, strictly increasing,
    # independent of whatever tag the recording carried
    assert tags == [0, 1, 2], tags
    assert all(traces) and len(set(traces)) == 3, traces


def test_deserialize_default_keeps_recorded_identity(tmp_path):
    # loop=1 / restamp=False: checkpoint/resume fidelity is unchanged
    base, data = _record_stream(str(tmp_path))
    with bf.Pipeline() as p:
        b = DeserializeBlock([base], 16)
        sink = GatherSink(b)
    p.run()
    assert np.array_equal(sink.result(), data)
    assert sink.headers[0]['name'] == 'rec'


# ---------------------------------------------------------------------------
# quotas
# ---------------------------------------------------------------------------

def test_quota_gate_sheds_counted():
    store = {}
    mgr = service.JobManager(max_tenants=2, warm=False)
    # 8 gulps of 16x8 f32 = 512 B each arrive un-paced; the bucket's
    # burst (quota x 0.1s = 600 B) covers the first gulp, the refill
    # cannot keep up with the burst — most gulps must shed, counted
    spec = synth_spec('shedq', nframe=128, gulp=16, nchan=8,
                      quota_bytes_per_s=6000, quota_policy='shed')
    mgr.submit(spec, gather_build(store, 'shedq'))
    mgr.start()
    states = mgr.wait(30)
    assert states['shedq'] == 'DONE'
    admitted = counters.get('service.shedq.admitted_gulps')
    shed = counters.get('service.shedq.quota_shed_gulps')
    assert admitted + shed == 8
    assert admitted >= 1 and shed >= 4
    assert counters.get('service.shedq.quota_shed_bytes') == shed * 512
    # delivered output is exactly the admitted gulps, nothing silent
    assert store['shedq'].result().shape[0] == admitted * 16


def test_quota_burst_floored_at_one_gulp():
    # a gulp larger than the burst window (quota x 0.1s = 100 B vs
    # 512 B gulps) but smaller than one second of quota: the bucket's
    # one-gulp capacity floor must still admit a trickle instead of
    # shedding 100% of a lint-clean (no BF-E211) spec
    assert verify.verify_service(
        [{'id': 'floorq', 'quota_bytes_per_s': 1000,
          'gulp_nbyte': 512}], ncores=64) == []
    store = {}
    mgr = service.JobManager(max_tenants=2, warm=False)
    spec = synth_spec('floorq', nframe=128, gulp=16, nchan=8,
                      quota_bytes_per_s=1000, quota_policy='shed')
    mgr.submit(spec, gather_build(store, 'floorq'))
    mgr.start()
    assert mgr.wait(30)['floorq'] == 'DONE'
    admitted = counters.get('service.floorq.admitted_gulps')
    shed = counters.get('service.floorq.quota_shed_gulps')
    assert admitted >= 1 and admitted + shed == 8


def test_quota_gate_paces_rate():
    store = {}
    mgr = service.JobManager(max_tenants=2, warm=False)
    # 16 KiB at 16 KiB/s -> ~1 s paced; nothing may be lost
    spec = synth_spec('paceq', nframe=512, gulp=32, nchan=8,
                      quota_bytes_per_s=16384, quota_policy='pace')
    job = mgr.submit(spec, gather_build(store, 'paceq'))
    mgr.start()
    assert mgr.wait(30)['paceq'] == 'DONE'
    assert counters.get('service.paceq.quota_shed_gulps') == 0
    assert store['paceq'].result().shape[0] == 512
    elapsed = job.finished_at - job.first_data_at
    achieved = 512 * 32 / elapsed          # bytes/s (32 B per frame)
    # generous bounds: a shared CI host's sleeps overshoot
    assert achieved <= 16384 * 1.5, achieved
    assert elapsed >= 0.5, elapsed


# ---------------------------------------------------------------------------
# admission + lifecycle
# ---------------------------------------------------------------------------

def test_submit_duplicate_rejected():
    mgr = service.JobManager(max_tenants=4, warm=False)
    store = {}
    mgr.submit(synth_spec('dup', nframe=4096, gulp=16, tick=0.05),
               gather_build(store, 'dup'))
    before = counters.get('service.admission.rejected')
    with pytest.raises(service.ServiceAdmissionError):
        mgr.submit(synth_spec('dup'), gather_build(store, 'dup2'))
    assert counters.get('service.admission.rejected') == before + 1
    mgr.shutdown()


def test_capacity_admission():
    mgr = service.JobManager(max_tenants=1, warm=False)
    store = {}
    mgr.submit(synth_spec('cap1', nframe=4096, gulp=16, tick=0.05),
               gather_build(store, 'cap1'))
    with pytest.raises(service.ServiceAdmissionError):
        mgr.submit(synth_spec('cap2'), gather_build(store, 'cap2'))
    mgr.shutdown()


def test_submit_strict_rejects_spec_errors():
    mgr = service.JobManager(max_tenants=4, warm=False)
    bad = service.TenantSpec('badq', source={'kind': 'synthetic'},
                             quota_bytes_per_s=10, gulp_nbyte=4096)
    with pytest.raises(service.ServiceSpecError) as ei:
        mgr.submit(bad)
    assert any(d.code == 'BF-E211' for d in ei.value.diagnostics)


def test_two_tenants_concurrent_byte_correct():
    store = {}
    mgr = service.JobManager(max_tenants=4, warm=False)
    for tid in ('alpha', 'beta'):
        mgr.submit(synth_spec(tid, nframe=192, gulp=16, seed=5,
                              tick=0.01), gather_build(store, tid))
    mgr.start()
    states = mgr.wait(60)
    assert states == {'alpha': 'DONE', 'beta': 'DONE'}
    exp = service.SyntheticSource.payload(192, 8, 5)
    for tid in ('alpha', 'beta'):
        assert np.array_equal(store[tid].result(), exp), tid
    a, b = mgr.job('alpha'), mgr.job('beta')
    overlap = (min(a.finished_at, b.finished_at) -
               max(a.run_started_at, b.run_started_at))
    assert overlap > 0, 'tenants did not run concurrently'


def test_fault_isolation_blast_radius():
    store = {}
    mgr = service.JobManager(max_tenants=4, warm=False)
    mgr.submit(synth_spec('victim', nframe=640, gulp=16, tick=0.01),
               gather_build(store, 'victim'))
    mgr.submit(synth_spec('bystander', nframe=640, gulp=16,
                          tick=0.01), gather_build(store,
                                                   'bystander'))
    faults.inject('block.on_data', match='tenant.victim', count=1,
                  after=20)
    mgr.start()
    states = mgr.wait(60)
    assert states['victim'] == 'FAILED'
    assert states['bystander'] == 'DONE'
    victim, bystander = mgr.job('victim'), mgr.job('bystander')
    assert isinstance(victim.error, bf.PipelineRuntimeError)
    # the bystander's stream is complete and byte-correct
    exp = service.SyntheticSource.payload(640, 8, 3)
    assert np.array_equal(store['bystander'].result(), exp)
    # zero cross-tenant blast radius: no shed, no poisoned rings, no
    # failures recorded against the bystander
    bs = bystander.stats()
    assert bs['ring_shed_gulps'] == 0
    assert bs['rings_poisoned'] == 0
    assert bs['health'] in ('OK', 'DEGRADED')
    assert bystander.pipeline.supervisor.failures == []
    assert victim.stats()['rings_poisoned'] > 0


def test_job_registry_and_states():
    store = {}
    mgr = service.JobManager(max_tenants=2, warm=False)
    job = mgr.submit(synth_spec('reg', nframe=32), gather_build(
        store, 'reg'))
    assert job.state == 'PENDING'
    assert service.live_jobs()['reg'] is job
    mgr.start()
    assert job.wait(30) == 'DONE'
    assert job.start_latency_s is not None and job.start_latency_s > 0
    # a PENDING job stops to CANCELLED without ever running
    j2 = mgr.submit(synth_spec('reg2', nframe=32),
                    gather_build(store, 'reg2'))
    assert j2.stop() == 'CANCELLED'


# ---------------------------------------------------------------------------
# warm starts
# ---------------------------------------------------------------------------

def _device_build(sinks):
    from bifrost_tpu.stages import DetectStage, FftStage, ReduceStage

    def build(gate):
        b = bf.blocks.copy(gate, space='tpu')
        fbk = bf.blocks.fused(
            b, [FftStage('chan', axis_labels='freq'),
                DetectStage('scalar'), ReduceStage('freq', 3)])
        sinks.append(GatherSink(bf.blocks.copy(fbk, space='system')))
    return build


def _dev_spec(tid, nchan=64):
    return synth_spec(tid, nframe=96, gulp=32, nchan=nchan, seed=1)


def test_warm_start_zero_recompiles():
    sinks = []
    mgr = service.JobManager(max_tenants=4)
    cold = mgr.submit(_dev_spec('cold0'), _device_build(sinks))
    assert not cold.warm
    cold.start()
    assert cold.wait(120) == 'DONE'
    builds0 = counters.get('fused.plan_builds')
    hits0 = counters.get('fused.plan_depot_hits')
    adopt0 = counters.get('autotune.profile_adoptions')
    warm = mgr.submit(_dev_spec('warm0'), _device_build(sinks))
    assert warm.warm and not warm.warm_rejected
    assert warm.topology_hash == cold.topology_hash
    warm.start()
    assert warm.wait(120) == 'DONE'
    # zero recompiles: every plan came out of the depot
    assert counters.get('fused.plan_builds') == builds0
    assert counters.get('fused.plan_depot_hits') > hits0
    # knob-profile adoption (skipping convergence) is counted
    assert counters.get('autotune.profile_adoptions') == adopt0 + 1
    assert np.array_equal(sinks[0].result(), sinks[1].result())


def test_warm_stale_mismatch_rejected():
    from bifrost_tpu.stages import DetectStage, FftStage, ReduceStage
    sinks = []
    mgr = service.JobManager(max_tenants=4)
    cold = mgr.submit(_dev_spec('stale0'), _device_build(sinks))
    cold.start()
    assert cold.wait(120) == 'DONE'

    # SAME structural topology (block types + ring roles), DIFFERENT
    # stage math: the reduce factor changes, so the plan signature
    # must veto depot reuse even though the topology hash matches
    def build_other(gate):
        b = bf.blocks.copy(gate, space='tpu')
        fbk = bf.blocks.fused(
            b, [FftStage('chan', axis_labels='freq'),
                DetectStage('scalar'), ReduceStage('freq', 11)])
        sinks.append(GatherSink(bf.blocks.copy(fbk, space='system')))
    before = counters.get('service.warm.rejected_stale')
    other = mgr.submit(_dev_spec('stale1'), build_other)
    assert other.topology_hash == cold.topology_hash
    assert not other.warm and other.warm_rejected
    assert counters.get('service.warm.rejected_stale') == before + 1
    other.start()
    assert other.wait(120) == 'DONE'


def test_warm_disabled_by_env(monkeypatch):
    monkeypatch.setenv('BF_SERVE_WARM', '0')
    store = {}
    mgr = service.JobManager(max_tenants=4)
    assert not mgr.warm_enabled
    j1 = mgr.submit(synth_spec('nw0', nframe=32),
                    gather_build(store, 'nw0'))
    j1.start()
    assert j1.wait(30) == 'DONE'
    j2 = mgr.submit(synth_spec('nw1', nframe=32),
                    gather_build(store, 'nw1'))
    assert not j2.warm


# ---------------------------------------------------------------------------
# UDP capture tenants
# ---------------------------------------------------------------------------

def test_udp_capture_tenant(monkeypatch):
    import time

    from bifrost_tpu.io.packet_writer import HeaderInfo, UDPTransmit
    from bifrost_tpu.io.udp_socket import Address, UDPSocket
    monkeypatch.setenv('BF_NO_NATIVE_CAPTURE', '1')
    NSRC, PAYLOAD, BUF, NSEQ = 2, 64, 8, 32
    store = {}
    mgr = service.JobManager(max_tenants=2, warm=False)
    spec = service.TenantSpec('udp0', gulp_nframe=BUF, source={
        'kind': 'udp', 'port': 0, 'nsrc': NSRC, 'payload': PAYLOAD,
        'buffer_ntime': BUF, 'timeout_s': 0.2})
    job = mgr.submit(spec, build=lambda gate: store.setdefault(
        's', GatherSink(gate)))
    assert job._pump is not None and job._pump.port > 0
    job.start()
    time.sleep(0.3)                # let the ring reader attach
    tx_sock = UDPSocket().connect(Address('127.0.0.1',
                                          job._pump.port))
    rng = np.random.RandomState(0)
    data = rng.randint(0, 255, size=(NSEQ, NSRC,
                                     PAYLOAD)).astype(np.uint8)
    hi = HeaderInfo()
    hi.set_nsrc(NSRC)
    with UDPTransmit('chips', tx_sock) as tx:
        tx.send(hi, 1, 1, 0, 1, data[:1])
        # a mid-sequence gap longer than the socket timeout: the
        # service pump must keep listening, not end the stream
        time.sleep(0.3)
        tx.send(hi, 2, 1, 0, 1, data[1:])
        tx.send(hi, NSEQ + 1, 1, 0, 1,
                np.zeros((BUF * 2, NSRC, PAYLOAD), np.uint8))
    time.sleep(0.5)
    assert job.state == 'RUNNING'  # live capture runs until stopped
    assert job.stop(15) == 'DONE'
    out = store['s'].result()
    assert out is not None and out.shape[0] >= NSEQ
    assert np.array_equal(out[:NSEQ], data)
    assert counters.get('service.udp0.admitted_gulps') >= NSEQ // BUF


# ---------------------------------------------------------------------------
# telemetry surfaces
# ---------------------------------------------------------------------------

def test_snapshot_tenants_section():
    store = {}
    mgr = service.JobManager(max_tenants=2, warm=False)
    mgr.submit(synth_spec('tele', nframe=64, slo_ms=60000),
               gather_build(store, 'tele'))
    mgr.start()
    assert mgr.wait(30)['tele'] == 'DONE'
    snap = exporter.snapshot()
    assert 'tele' in snap['tenants']
    d = snap['tenants']['tele']
    assert d['state'] == 'DONE' and d['health'] == 'OK'
    assert d['gulps'] == 4 and d['bytes'] == 4 * 16 * 8 * 4
    assert d['quota_shed_gulps'] == 0
    slo = d['slo']
    assert slo['budget_ms'] == 60000 and slo['ok'] is True
    assert len(slo['trace_ids']) == 1


def test_prometheus_tenant_series():
    store = {}
    mgr = service.JobManager(max_tenants=2, warm=False)
    mgr.submit(synth_spec('prom', nframe=64),
               gather_build(store, 'prom'))
    mgr.start()
    assert mgr.wait(30)['prom'] == 'DONE'
    text = exporter.prometheus_text()
    assert 'bifrost_tpu_tenant{tenant="prom",kind="gulps"} 4' in text
    assert 'bifrost_tpu_tenant_health{tenant="prom",state="OK"} 1' \
        in text


def test_like_top_tenants_pane():
    sys.path.insert(0, os.path.join(ROOT, 'tools'))
    try:
        import like_top
    finally:
        sys.path.pop(0)
    tenants = {1234: {'ntenants': 2,
                      't.replay.state': 'RUNNING',
                      't.replay.health': 'OK',
                      't.replay.gulps': 42, 't.replay.q_shed': 3,
                      't.replay.warm': 1, 't.replay.age99_ms': 12.5,
                      't.synth.state': 'FAILED',
                      't.synth.health': 'FAILED',
                      't.synth.gulps': 7, 't.synth.q_shed': 0,
                      't.synth.warm': 0}}
    lines = like_top.render_text(
        like_top.get_load_average(), {}, like_top.
        get_memory_swap_usage(), None, {}, tenants=tenants)
    text = '\n'.join(lines)
    assert '[tenants] pid 1234  2 tenant(s)' in text
    assert 'replay' in text and 'RUNNING' in text and '12.5' in text
    assert 'FAILED' in text


def test_service_proclog_pane_published():
    from bifrost_tpu import proclog
    store = {}
    mgr = service.JobManager(max_tenants=2, warm=False)
    mgr.submit(synth_spec('pane', nframe=64),
               gather_build(store, 'pane'))
    mgr.start()
    assert mgr.wait(30)['pane'] == 'DONE'
    mgr.shutdown()
    logs = proclog.load_by_pid(os.getpid())
    pane = logs.get('service', {}).get('tenants')
    assert pane and pane.get('t.pane.state') == 'DONE'


# ---------------------------------------------------------------------------
# CLI + gate wiring
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bf_serve_validate_cli(tmp_path):
    spec = {'tenants': [
        {'id': 'synth0',
         'source': {'kind': 'synthetic', 'nframe_total': 64,
                    'gulp_nframe': 16, 'nchan': 8}},
        {'id': 'synth1', 'quota_bytes_per_s': 1e6,
         'quota_policy': 'pace', 'gulp_nframe': 16,
         'source': {'kind': 'synthetic', 'nframe_total': 64,
                    'gulp_nframe': 16, 'nchan': 8}},
    ]}
    path = str(tmp_path / 'svc.json')
    with open(path, 'w') as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'tools', 'bf_serve.py'),
         path, '--validate'],
        capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=180)
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'validate PASS' in out.stdout
    # a duplicate id must fail static validation with BF-E210
    spec['tenants'][1]['id'] = 'synth0'
    with open(path, 'w') as f:
        json.dump(spec, f)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'tools', 'bf_serve.py'),
         path, '--validate'],
        capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=180)
    assert out.returncode == 3
    assert 'BF-E210' in out.stdout
