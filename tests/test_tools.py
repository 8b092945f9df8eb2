"""CLI tools smoke tests (reference analogue: test/test_scripts.py)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import bifrost_tpu as bf
from tests.util import NumpySourceBlock, GatherSink, simple_header

TOOLS = os.path.join(os.path.dirname(os.path.dirname(__file__)), 'tools')


def _run_pipeline_and_leave_proclogs():
    data = np.ones((8, 4), np.float32)
    with bf.Pipeline() as p:
        hdr = simple_header([-1, 4], 'f32')
        src = NumpySourceBlock([data], hdr, gulp_nframe=8)
        b = bf.blocks.copy(src)
        sink = GatherSink(b)
        p.run()
    return sink


def _tool(name, *args):
    env = dict(os.environ)
    return subprocess.run([sys.executable, os.path.join(TOOLS, name)]
                          + list(args), capture_output=True, text=True,
                          env=env, timeout=60)


def test_like_top_once():
    """like_top renders the reference's panes: load average, process
    counts, CPU/memory/swap, and per-block perf rows with core + %CPU
    columns (reference: tools/like_top.py:52-200)."""
    _run_pipeline_and_leave_proclogs()
    res = _tool('like_top.py', '--once')
    assert res.returncode == 0, res.stderr
    assert 'load average:' in res.stdout
    assert 'Processes:' in res.stdout and 'running' in res.stdout
    assert 'CPU(s):' in res.stdout and '%us' in res.stdout
    assert 'Mem:' in res.stdout and 'Swap:' in res.stdout
    assert 'Block' in res.stdout and 'Core' in res.stdout
    assert '%CPU' in res.stdout and 'Cmd' in res.stdout
    assert 'Acquire' in res.stdout and 'Reserve' in res.stdout
    assert 'CopyBlock' in res.stdout


def test_like_top_device_pane_reads_proclogs():
    """The accelerator-memory pane comes from the ``devices/<n>``
    ProcLogs of the process that owns the chip (MetricsPublisher); the
    monitor starts no process and never initialises JAX — a chip
    belongs to one process at a time."""
    from bifrost_tpu.proclog import ProcLog
    sys.path.insert(0, TOOLS)
    try:
        import like_top
    finally:
        sys.path.remove(TOOLS)
    assert 'subprocess' not in open(like_top.__file__).read()
    ProcLog('devices/0').update({'platform': 'tpu',
                                 'bytes_limit': 16 << 30,
                                 'bytes_in_use': 3 << 30}, force=True)
    devs = {}
    like_top.collect_blocks(pids=[os.getpid()], devices=devs)
    pane = like_top.device_memory_usage(devs)
    assert pane == {'devCount': 1, 'memTotal': (16 << 30) // 1024,
                    'memUsed': (3 << 30) // 1024,
                    'memFree': (13 << 30) // 1024}
    res = _tool('like_top.py', '--once')
    assert res.returncode == 0, res.stderr
    assert 'Dev(s):' in res.stdout and '1 device(s)' in res.stdout


def test_like_ps():
    """like_ps lists process details, rings with space/size, and block
    ring wiring (reference: tools/like_ps.py:120-196)."""
    _run_pipeline_and_leave_proclogs()
    res = _tool('like_ps.py', str(os.getpid()))
    assert res.returncode == 0, res.stderr
    assert 'PID: %d' % os.getpid() in res.stdout
    assert 'User:' in res.stdout and 'CPU Usage:' in res.stdout
    assert 'Thread Count:' in res.stdout
    assert 'Rings:' in res.stdout and 'Blocks:' in res.stdout
    assert 'on system of size' in res.stdout     # ring geometry pane
    assert 'read ring(s):' in res.stdout
    assert 'write ring(s):' in res.stdout
    assert 'log(s):' in res.stdout


def test_pipeline2dot():
    """pipeline2dot annotates blocks with CPU binding and shape, rings
    with space/size, and emits association edges
    (reference: tools/pipeline2dot.py:97-330)."""
    _run_pipeline_and_leave_proclogs()
    res = _tool('pipeline2dot.py', str(os.getpid()))
    assert res.returncode == 0, res.stderr
    assert 'digraph graph%d' % os.getpid() in res.stdout
    assert 'label="Pipeline:' in res.stdout
    assert 'CPU' in res.stdout or 'Unbound' in res.stdout
    assert 'shape="box"' in res.stdout
    assert 'ring:' in res.stdout and '->' in res.stdout
    assert 'system' in res.stdout          # ring space annotation


def test_like_bmon_once():
    """like_bmon renders per-PID RX/TX rate summaries and per-block
    loss detail (reference: tools/like_bmon.py:108-330)."""
    res = _tool('like_bmon.py', '--once')
    assert res.returncode == 0, res.stderr
    assert 'RX Rate' in res.stdout and 'TX Rate' in res.stdout
    assert 'RX pkt/s' in res.stdout and 'TX pkt/s' in res.stdout


def test_like_bmon_rates_from_capture(tmp_path, monkeypatch):
    """A real capture's proclog stats appear in like_bmon's panes with
    good/missing/loss columns."""
    monkeypatch.setenv('BF_PROCLOG_DIR', str(tmp_path))
    base = os.path.join(str(tmp_path), str(os.getpid()),
                        'rx_capture')
    os.makedirs(base)
    with open(os.path.join(base, 'stats'), 'w') as f:
        f.write('ngood_bytes : 8192\nnmissing_bytes : 1024\n'
                'ninvalid : 3\nnignored : 1\nnpackets : 128\n')
    tx = os.path.join(str(tmp_path), str(os.getpid()),
                      'chips_transmit_1')
    os.makedirs(tx)
    with open(os.path.join(tx, 'stats'), 'w') as f:
        f.write('npackets : 64\nnbytes : 4096\n')
    res = _tool('like_bmon.py', '--once')
    assert res.returncode == 0, res.stderr
    assert 'rx_capture' in res.stdout
    assert 'chips_transmit_1' in res.stdout
    assert 'good_bytes' in res.stdout and 'missing' in res.stdout
    assert '8192' in res.stdout and '1024' in res.stdout
    assert 'loss' in res.stdout


def test_like_pmap():
    """like_pmap reports NUMA-classified memory areas and per-ring
    mapping details (reference: tools/like_pmap.py)."""
    _run_pipeline_and_leave_proclogs()
    res = _tool('like_pmap.py', str(os.getpid()))
    assert res.returncode == 0, res.stderr
    assert 'Rings:' in res.stdout
    assert 'Anonymous Memory Areas:' in res.stdout
    assert 'File Backed Memory Areas:' in res.stdout
    assert 'Ring Mappings:' in res.stdout
    assert 'Space: system' in res.stdout
    assert 'Node:' in res.stdout or 'Area:' in res.stdout
    assert 'Other Non-Ring Areas:' in res.stdout


def test_proclog_roundtrip():
    from bifrost_tpu import proclog
    _run_pipeline_and_leave_proclogs()
    contents = proclog.load_by_pid(os.getpid())
    blocks = [b for b in contents if 'CopyBlock' in b]
    assert blocks
    perf = contents[blocks[0]].get('perf', {})
    assert 'process_time' in perf


def test_telemetry_decorators_inert_when_disabled(monkeypatch,
                                                  tmp_path):
    """The decorator API works regardless of state; with aggregation
    off (the isolated default) nothing is recorded.  Full behavior:
    tests/test_telemetry.py."""
    monkeypatch.setenv('BF_CACHE_DIR', str(tmp_path))
    import bifrost_tpu.telemetry as tel
    client = tel._LocalClient()
    monkeypatch.setattr(tel, '_client', client)
    assert tel.is_active() is False
    tel.track_module()

    @tel.track_function
    def f(x):
        return x + 1
    assert f(1) == 2
    assert not client._cache


def test_header_standard():
    from bifrost_tpu.header_standard import enforce_header_standard
    good = {'nchans': 4, 'nifs': 1, 'nbits': 8, 'fch1': 1400.0,
            'foff': -1.0, 'tstart': 58000.0, 'tsamp': 1e-3}
    assert enforce_header_standard(good)
    bad = dict(good)
    del bad['tsamp']
    assert not enforce_header_standard(bad)


def test_object_cache_and_envvars():
    from bifrost_tpu.utils import ObjectCache, EnvVars
    c = ObjectCache(capacity=2)
    c.put('a', 1)
    c.put('b', 2)
    c.put('c', 3)
    assert 'a' not in c and c.get('c') == 3
    os.environ['BF_TEST_VAR'] = 'hello'
    EnvVars.clear()
    assert EnvVars.get('BF_TEST_VAR') == 'hello'


def test_proclog_throttling(tmp_path, monkeypatch):
    """ProcLog rate-limits file writes (BF_PROCLOG_INTERVAL) but
    force=True always writes."""
    monkeypatch.setenv('BF_PROCLOG_DIR', str(tmp_path))
    from bifrost_tpu import proclog as plmod
    monkeypatch.setattr(plmod, '_gc_done', True)
    monkeypatch.setattr(plmod.ProcLog, 'MIN_INTERVAL', None)
    monkeypatch.setenv('BF_PROCLOG_INTERVAL', '100')
    log = plmod.ProcLog('throttle/perf')
    log.update({'n': 1})
    log.update({'n': 2})          # throttled away
    text = open(log.path).read()
    assert 'n : 1' in text
    log.update({'n': 3}, force=True)
    assert 'n : 3' in open(log.path).read()
    monkeypatch.setattr(plmod.ProcLog, 'MIN_INTERVAL', None)


def test_lint_envvars_invariant():
    """Repo invariant: every BF_* env var read in bifrost_tpu/ is
    documented in docs/envvars.md and every documented var is read
    somewhere (tools/lint_envvars.py; exit 3 on violations)."""
    res = _tool('lint_envvars.py')
    assert res.returncode == 0, res.stdout + res.stderr
    assert '0 undocumented, 0 phantom' in res.stdout


EXAMPLES = os.path.join(os.path.dirname(TOOLS), 'examples')
#: scripts that print their usage and exit when given no argument
EXAMPLE_ARGS = {'gpuspec_simple.py': ['--demo']}


@pytest.mark.parametrize('script', sorted(
    f for f in os.listdir(EXAMPLES) if f.endswith('.py')))
def test_bf_lint_script_mode(script):
    """bf_lint lints an example script without running its pipeline
    and exits 0 under --strict: every example the repo ships builds a
    topology the verifier finds no error in."""
    res = _tool('bf_lint.py', '--strict',
                os.path.join(EXAMPLES, script),
                *EXAMPLE_ARGS.get(script, []))
    assert res.returncode == 0, res.stdout + res.stderr
    assert ' 0 error(s)' in res.stdout
    assert 'BF-E' not in res.stdout


def test_bf_lint_codes_catalog():
    """--codes prints the stable diagnostic catalog used by
    docs/analysis.md."""
    res = _tool('bf_lint.py', '--codes')
    assert res.returncode == 0, res.stderr
    for code in ('BF-E101', 'BF-E121', 'BF-E130', 'BF-W140', 'BF-E150'):
        assert code in res.stdout, code


def test_mprobe_report_dump_and_clear(tmp_path):
    """mprobe_report renders the disk winner cache (winner, per-
    candidate ms, margin, coin-flip flag) and --clear drops it so the
    next session re-measures."""
    import json
    cache = tmp_path / 'mp'
    cache.mkdir()
    (cache / 'beamform.json').write_text(json.dumps({
        'cpu:x:v0|acc=int8 w=(1,4,8) v=(8,2,1,8) int8': {
            'winner': 'int8_wide',
            'ms': {'int8_wide': 1.0, 'xla': 5.0}},
        'cpu:x:v0|acc=f32 w=(1,4,8) v=(8,2,1,8) float32': {
            'winner': 'planar',
            'ms': {'planar': 1.00, 'xla': 1.01}},
    }))
    # foreign state in the same dir (telemetry_usage.json-style list
    # entries): must be neither rendered nor deleted by --clear
    (cache / 'telemetry_usage.json').write_text(
        json.dumps({'counters.inc': [12, 3, 0.5]}))
    env = dict(os.environ, BF_CACHE_DIR=str(cache))
    run = lambda *a: subprocess.run(
        [sys.executable, os.path.join(TOOLS, 'mprobe_report.py')]
        + list(a), capture_output=True, text=True, env=env, timeout=60)

    res = run()
    assert res.returncode == 0, res.stdout + res.stderr
    assert 'winner=int8_wide' in res.stdout
    assert 'margin=5.000x' in res.stdout
    assert 'COIN-FLIP' in res.stdout          # the 1.01/1.00 key

    res = run('--json', '--family', 'beamform')
    data = json.loads(res.stdout)
    assert set(data) == {'beamform'}
    assert len(data['beamform']) == 2

    res = run('--clear', '--family', 'beamform')
    assert res.returncode == 0
    assert not (cache / 'beamform.json').exists()

    res = run('--clear')
    assert res.returncode == 0
    assert (cache / 'telemetry_usage.json').exists()  # foreign: kept

    res = run()
    assert 'no winner caches' in res.stdout
