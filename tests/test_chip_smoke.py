"""chip_smoke.py and what it relies on, as far as a CPU host can show:
the rehearsal runs, a host without a TPU is refused, the compile cache
can be placed from outside, a forced implementation that cannot be
built raises, and a candidate the automatic selection tried and the
backend refused is loud — one warning, one entry in the record the
block publishes."""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, 'chip_smoke.py')


def _run(args, tmp_path, **env):
    environ = dict(os.environ, JAX_PLATFORMS='cpu', **env)
    return subprocess.run(
        [sys.executable, SMOKE, '--out', str(tmp_path / 'out')] + args,
        capture_output=True, text=True, env=environ, timeout=300)


def test_rehearsal_passes_at_a_tiny_size(tmp_path):
    res = _run(['--allow-cpu', '--ntime', '64', '--nfine', '256'],
               tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last == {'ok': True, 'rehearsal': True,
                    'device': {'platform': 'cpu', 'kind': 'cpu',
                               'count': last['device']['count']}}
    assert 'rehearsal: true' in res.stdout
    assert 'phases B and C skipped' in res.stdout
    assert 'two runs of seed 0 bit-identical: True' in res.stdout
    assert 'gulps offered 8, delivered 8' in res.stdout


def test_refuses_a_host_without_a_tpu(tmp_path):
    res = _run([], tmp_path)
    assert res.returncode != 0
    assert 'chip_smoke: no TPU' in res.stderr
    # no result: nothing on stdout parses as the JSON summary
    assert '"ok"' not in res.stdout


@pytest.mark.parametrize('placed', [True, False])
def test_compile_cache_directory(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it and nothing sets
    another; unset: <checkout>/.jax_cache."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    want = os.path.join(ROOT, '.jax_cache')
    if placed:
        want = env['JAX_COMPILATION_CACHE_DIR'] = str(tmp_path / 'cc')
    code = ('import sys; sys.path.insert(0, %r)\n'
            'import jax, bifrost_tpu as bf\n'
            'print(bf.enable_compilation_cache())\n'
            'print(jax.config.jax_compilation_cache_dir)\n' % ROOT)
    out = subprocess.run([sys.executable, '-c', code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]


def _flagship_chain(nfine=256, rfactor=4):
    from bifrost_tpu.stages import (FftStage, DetectStage, ReduceStage,
                                    walk_headers)
    hdr = {'_tensor': {'shape': [-1, 2, nfine], 'dtype': 'ci8',
                       'labels': ['time', 'pol', 'fine_time'],
                       'scales': [[0, 1]] * 3, 'units': [None] * 3}}
    stages = [FftStage('fine_time', axis_labels='freq'),
              DetectStage('stokes', axis='pol'),
              ReduceStage('freq', rfactor)]
    return stages, walk_headers(stages, hdr)


def _refusing_kernel(monkeypatch):
    """The selection admits the kernel (as on a TPU) and its build
    raises like a Mosaic refusal."""
    from bifrost_tpu.ops import mprobe
    from bifrost_tpu.ops import spectrometer as spec

    def refuse(*args, **kwargs):
        raise RuntimeError('Mosaic failed to compile TPU kernel: '
                           'scoped vmem limit exceeded\nmore detail')

    monkeypatch.setattr(spec, 'choose_precision', lambda *a, **k: None)
    monkeypatch.setattr(spec, 'fused_spectrometer', refuse)
    monkeypatch.setattr(spec, '_usable_cache', {})
    monkeypatch.setattr(mprobe, '_refusals', {})


def test_forced_spectrometer_that_cannot_be_built_raises(monkeypatch):
    from bifrost_tpu.stages import compose_stages
    _refusing_kernel(monkeypatch)
    monkeypatch.setenv('BF_SPEC_IMPL', 'pallas')
    stages, headers = _flagship_chain()
    with pytest.raises(RuntimeError, match='Mosaic failed to compile'):
        compose_stages(stages, headers, (16, 2, 256, 2), 'int8')


def test_auto_tried_candidate_that_raises_is_loud(monkeypatch):
    """Through the pipeline: the XLA chain runs, exactly one warning
    carries the compiler's message, and impl_info lists the refusal."""
    import bifrost_tpu as bf
    from bifrost_tpu.dtype import ci8
    from tests.util import NumpySourceBlock, GatherSink, simple_header
    _refusing_kernel(monkeypatch)
    monkeypatch.delenv('BF_SPEC_IMPL', raising=False)
    stages, _ = _flagship_chain()
    T, NF = 16, 256
    raw = np.zeros((T, 2, NF), dtype=ci8)
    raw['re'] = np.random.RandomState(0).randint(-8, 8, raw.shape)
    hdr = simple_header([-1, 2, NF], 'ci8',
                        labels=['time', 'pol', 'fine_time'])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter('always')
        with bf.Pipeline() as p:
            src = NumpySourceBlock([raw, raw, raw], hdr, gulp_nframe=T)
            fb = bf.blocks.fused(bf.blocks.copy(src, space='tpu'),
                                 stages)
            sink = GatherSink(bf.blocks.copy(fb, space='system'))
            p.run()
    assert sink.result().shape == (3 * T, 4, NF // 4)
    refusals = [w for w in caught
                if 'refused' in str(w.message)
                and 'spectrometer' in str(w.message)]
    assert len(refusals) == 1, [str(w.message) for w in caught]
    assert 'scoped vmem limit exceeded' in str(refusals[0].message)
    assert fb.impl_info['impl'] == 'xla-fused'
    (key, line), = fb.impl_info['refused'].items()
    assert key.startswith('spectrometer/pallas[')
    assert line == ('RuntimeError: Mosaic failed to compile TPU '
                    'kernel: scoped vmem limit exceeded')
