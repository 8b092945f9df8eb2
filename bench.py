"""Benchmark: Guppi-style spectroscopy pipeline throughput on one chip.

Mirrors the reference's north-star pipeline (reference:
testbench/gpuspec_simple.py:44-58 — FFT(fine_time) -> detect('stokes')
-> reduce) running through the REAL bifrost_tpu machinery: ring buffers,
thread-per-block pipeline, the fused FFT->Stokes->reduce stage chain as
ONE jitted computation per gulp.

Prints ONE JSON line:
  {"metric": ..., "value": Msamples/s, "unit": "Msamples/s",
   "vs_baseline": value / A100_BASELINE_MSPS}

Timing: the clock stops on a scalar read back from the final gulp (TPU
programs execute in enqueue order, so the last gulp's value
materializing implies the whole queue drained); the same read-back
bounds the warmup phase before the clock starts.  On the local v5e
``block_until_ready`` waits for the device just as well (chip_smoke.py
fact i, PR 21); replacing ``_force`` with it is ROADMAP D6's.

Baseline derivation (BASELINE.md publishes no absolute number, so we use
a bandwidth model of the same device-resident chain on an A100 running
the CUDA reference): per complex sample, cuFFT 4096-pt c2c fp32 does
~2 r/w passes (32 B) plus detect read+write (~20 B) and reduce (~4 B)
≈ 56 B of HBM traffic; at ~1.55 TB/s effective that is ~28 Gsamples/s.
A100_BASELINE_MSPS = 28000 — a model, not a measurement.
"""

import json
import os
import sys
import time

import numpy as np

import bifrost_tpu  # noqa: F401

A100_BASELINE_MSPS = 28000.0

# HBM traffic of the XLA fused chain, per input sample: ci8 read (2 B)
# + unpack kernel c64 write (8) + XLA FFT custom-call read + write
# (8 + 8) + fused detect/reduce read (8) + reduced Stokes f32 write
# (2) = 36 B.  (The 56 B figure in the baseline model above is the
# UNFUSED cuFFT chain on the A100 and is used only for vs_baseline.)
CHAIN_BYTES_PER_SAMPLE = 36.0
# ... and of the fused Pallas spectrometer kernel: ci8 read (2 B) +
# reduced Stokes f32 write (2 B); nothing else leaves VMEM.  The
# BF_SPEC_TRANSPOSE=epilogue variant adds an XLA reorder of the
# reduced output (+4 B).
CHAIN_BYTES_PER_SAMPLE_PALLAS = 4.0
CHAIN_BYTES_PER_SAMPLE_PALLAS_EPI = 8.0


def flagship_header():
    """The flagship gulp's ring header (shared by the bench pipeline
    and the roofline probe so the two can never drift apart)."""
    return {'name': 'bench', 'time_tag': 0,
            '_tensor': {'shape': [-1, NPOL, NFINE],
                        'dtype': 'ci8',
                        'labels': ['time', 'pol', 'fine_time'],
                        'scales': [[0, 1]] * 3,
                        'units': [None] * 3}}


def flagship_stages():
    """The flagship FFT->detect->reduce stage chain (single source of
    truth for build_and_run and the traffic model)."""
    from bifrost_tpu.stages import FftStage, DetectStage, ReduceStage
    return [FftStage('fine_time', axis_labels='freq'),
            DetectStage('stokes', axis='pol'),
            ReduceStage('freq', RFACTOR)]


def chain_traffic_model(impl_info):
    """(bytes_per_sample, impl_label) for the flagship chain from the
    impl record the FusedBlock PUBLISHED for the plan it executed
    (FusedBlock.impl_info / ProcLog ``<block>/impl``).  Pure
    bookkeeping — no probes, no env reads — so the label can never
    disagree with the path that ran (VERDICT r3 item 4)."""
    info = impl_info or {}
    if info.get('impl') == 'pallas-spectrometer':
        label = 'pallas-spectrometer[%s,%s]' % (
            info.get('precision', 'default'),
            info.get('transpose', 'kernel'))
        if info.get('transpose') == 'epilogue':
            return CHAIN_BYTES_PER_SAMPLE_PALLAS_EPI, label
        return CHAIN_BYTES_PER_SAMPLE_PALLAS, label
    return CHAIN_BYTES_PER_SAMPLE, 'xla-fused'

NTIME = 16384        # frames per gulp
NPOL = 2
NFINE = 4096         # fine-time samples -> FFT length
RFACTOR = 4
NGULP_WARM = 3
NGULP_BENCH = 32
SYNC_DEPTH = 4       # gulps of dispatch-ahead per block


def _force(arr):
    """Force REAL device completion of ``arr``'s dependency chain by
    materializing a scalar on the host."""
    import jax.numpy as jnp
    return float(jnp.sum(arr))


def build_and_run():
    import jax
    import jax.numpy as jnp
    import bifrost_tpu as bf
    bf.enable_compilation_cache()    # reuse XLA programs across runs
    from bifrost_tpu.pipeline import SourceBlock, SinkBlock

    class VoltageSource(SourceBlock):
        """Emits device-resident ci8 voltage gulps (device rep: int8
        with trailing (re, im) axis), pre-staged so the bench measures
        the device pipeline, not host RNG."""

        def __init__(self, ngulp, **kwargs):
            super(VoltageSource, self).__init__(['bench'], NTIME,
                                                space='tpu', **kwargs)
            self.ngulp = ngulp
            rng = np.random.RandomState(0)
            host = rng.randint(-64, 64,
                               size=(NTIME, NPOL, NFINE, 2)).astype(np.int8)
            self.gulp = jnp.asarray(host)
            self.count = 0

        def create_reader(self, name):
            class R(object):
                def __enter__(self):
                    return self

                def __exit__(self, *exc):
                    return False
            return R()

        def on_sequence(self, reader, name):
            self.count = 0
            return [flagship_header()]

        def on_data(self, reader, ospans):
            if self.count >= self.ngulp:
                return [0]
            self.count += 1
            ospans[0].set(self.gulp)
            return [NTIME]

    class SpectraSink(SinkBlock):
        def __init__(self, iring, **kwargs):
            super(SpectraSink, self).__init__(iring, **kwargs)
            self.n = 0
            self.t_start = None
            self.elapsed = None
            self.checksum = 0.0

        def on_sequence(self, iseq):
            pass

        def on_data(self, ispan):
            self.n += 1
            if self.n == NGULP_WARM:
                # drain the queue (forces everything enqueued so far),
                # then start the clock
                self.checksum += _force(ispan.data)
                self.t_start = time.time()
            elif self.n == NGULP_WARM + NGULP_BENCH:
                # force the final gulp -> whole benched queue has
                # really executed
                self.checksum += _force(ispan.data)
                self.elapsed = time.time() - self.t_start

    with bf.Pipeline(sync_depth=SYNC_DEPTH) as p:
        src = VoltageSource(NGULP_WARM + NGULP_BENCH)
        # the whole FFT->detect->reduce chain fuses into ONE XLA
        # computation per gulp (blocks/fused.py)
        fb = bf.blocks.fused(src, flagship_stages())
        sink = SpectraSink(fb)
        p.run()
    if sink.elapsed is None:
        raise RuntimeError(
            "Benchmark incomplete: sink received %d gulps, expected %d"
            % (sink.n, NGULP_WARM + NGULP_BENCH))
    nsamples = NGULP_BENCH * NTIME * NPOL * NFINE
    # what ran, as recorded by the block that ran it (also published to
    # ProcLog <block>/impl) — the roofline/label source of truth
    return nsamples / sink.elapsed / 1e6, fb.impl_info


def run_correctness_gate():
    """On-hardware correctness gate (VERDICT r1 item 7): run the ring +
    fused FFT->detect->reduce chain on the REAL chip, force completion
    via readback, and check the Stokes output:

    - TPU-vs-TPU determinism must be BIT-IDENTICAL (two runs of the
      same pipeline byte-compare equal);
    - the int8 correlation path (integer MXU arithmetic) must be
      BIT-IDENTICAL to the numpy integer oracle;
    - the float FFT chain must match the float64 numpy oracle to f32
      accuracy (different FFT algorithms cannot be bit-equal; the
      BASELINE bit-exactness bar applies to the integer paths and
      run-to-run determinism).

    Returns a dict; nonzero 'failures' means the gate failed.
    """
    import jax
    import jax.numpy as jnp
    import bifrost_tpu as bf
    from bifrost_tpu.stages import FftStage, DetectStage, ReduceStage

    platform = jax.devices()[0].platform
    failures = []

    NT, NP, NF, RF = 64, 2, 1024, 4
    rng = np.random.RandomState(7)
    volt = rng.randint(-64, 64, size=(NT, NP, NF, 2)).astype(np.int8)

    def run_chain():
        import sys as _sys
        import os as _os
        _sys.path.insert(0, _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)), 'tests'))
        from util import NumpySourceBlock, GatherSink, simple_header
        hdr = simple_header([-1, NP, NF], 'ci8',
                            labels=['time', 'pol', 'fine_time'])
        raw = np.zeros((NT, NP, NF), dtype=np.dtype([('re', 'i1'),
                                                     ('im', 'i1')]))
        raw['re'] = volt[..., 0]
        raw['im'] = volt[..., 1]
        with bf.Pipeline() as p:
            src = NumpySourceBlock([raw], hdr, gulp_nframe=NT)
            b = bf.blocks.copy(src, space='tpu')
            b = bf.blocks.fused(b, [
                FftStage('fine_time', axis_labels='freq'),
                DetectStage('stokes', axis='pol'),
                ReduceStage('freq', RF)])
            b = bf.blocks.copy(b, space='system')
            sink = GatherSink(b)
            p.run()
        return sink.result()

    out1 = run_chain()
    out2 = run_chain()
    if not np.array_equal(out1, out2):
        failures.append('run-to-run Stokes output not bit-identical')

    # float64 numpy oracle for the FFT chain
    v = volt[..., 0].astype(np.float64) + 1j * volt[..., 1]
    s = np.fft.fft(v, axis=-1)
    x, y = s[:, 0], s[:, 1]
    xy = x * np.conj(y)
    stokes = np.stack([np.abs(x)**2 + np.abs(y)**2,
                       np.abs(x)**2 - np.abs(y)**2,
                       2 * xy.real, -2 * xy.imag], axis=1)
    oracle = stokes.reshape(NT, 4, NF // RF, RF).sum(-1)
    rel = np.max(np.abs(out1 - oracle) /
                 (np.max(np.abs(oracle)) + 1e-30))
    if rel > 1e-5:
        failures.append('Stokes vs numpy oracle rel err %.3g' % rel)

    # int8 correlation: integer arithmetic must be exactly the oracle's
    T, F, S, P = 32, 8, 4, 2
    ci = rng.randint(-64, 64, size=(T, F, S, P, 2)).astype(np.int8)
    xr = jnp.asarray(ci)
    re = ci[..., 0].astype(np.int64).reshape(T, F, S * P)
    im = ci[..., 1].astype(np.int64).reshape(T, F, S * P)
    rr = np.einsum('tfi,tfj->fij', re, re)
    ii = np.einsum('tfi,tfj->fij', im, im)
    k = np.einsum('tfi,tfj->fij', im, re)
    want = (rr + ii).astype(np.float32) + \
        1j * (k - np.swapaxes(k, -1, -2)).astype(np.float32)

    def corr(x):
        r8 = x[..., 0].reshape(T, F, S * P)
        i8 = x[..., 1].reshape(T, F, S * P)
        rr = jnp.einsum('tfi,tfj->fij', r8, r8,
                        preferred_element_type=jnp.int32)
        ii = jnp.einsum('tfi,tfj->fij', i8, i8,
                        preferred_element_type=jnp.int32)
        kk = jnp.einsum('tfi,tfj->fij', i8, r8,
                        preferred_element_type=jnp.int32)
        return (rr + ii).astype(jnp.float32), \
            (kk - jnp.swapaxes(kk, -1, -2)).astype(jnp.float32)

    gr, gi = jax.jit(corr)(xr)
    _force(gr)
    got = np.asarray(gr) + 1j * np.asarray(gi)
    if not np.array_equal(got, want):
        failures.append('int8 correlation not bit-identical to oracle')

    return {
        'metric': 'on-%s correctness gate' % platform,
        'platform': platform,
        'stokes_rel_err': float(rel),
        'deterministic': np.array_equal(out1, out2),
        'failures': failures,
        'ok': not failures,
    }


def _probe_backend(timeout=180.0, retries=None):
    """(healthy, history): probe the backend in FRESH subprocesses
    with backoff, never touching this process's PJRT state (a chip
    belongs to one process at a time: each probe exits before the next
    child starts).  ``history`` records every attempt for the
    artifact, so a dead-backend run still documents what was tried."""
    import subprocess
    if retries is None:
        try:
            retries = int(os.environ.get('BF_BENCH_INIT_RETRIES', '3'))
        except ValueError:
            retries = 3
    here = os.path.dirname(os.path.abspath(__file__))
    probe_py = os.path.join(here, 'tools', 'tpu_probe.py')
    history = []
    if not os.path.exists(probe_py):
        return True, [{'note': 'no probe tool; assuming alive'}]
    env = dict(os.environ, BF_PROBE_DEADLINE=str(timeout))
    for attempt in range(1 + max(retries, 0)):
        if attempt:
            time.sleep(min(45.0 * attempt, 120.0))
        entry = {'t': time.strftime('%Y-%m-%dT%H:%M:%SZ',
                                    time.gmtime())}
        try:
            p = subprocess.run([sys.executable, probe_py], env=env,
                               capture_output=True, text=True,
                               timeout=timeout + 60)
            entry['rc'] = p.returncode
            try:
                entry.update(json.loads(
                    (p.stdout or '').strip().splitlines()[-1]))
            except (ValueError, IndexError):
                pass
        except subprocess.TimeoutExpired:
            entry['rc'] = 'timeout'
        history.append(entry)
        if entry.get('rc') == 0:
            return True, history
    return False, history


def _backend_alive(timeout=180.0, retries=None):
    """Probe in fresh subprocesses (a hung in-process init cannot be
    retried: the second call just blocks on the same PJRT init lock),
    then initialize THIS process's backend once a probe succeeds.  A
    failed (raised, not hung) in-process init after a healthy probe is
    re-probed and retried rather than given up on.  Only child entrypoints call this; the parent
    aggregator never initializes a backend in-process (VERDICT r4
    item 5).  BF_SKIP_PROBE=1 (set by _run_isolated: the parent just
    proved health) skips the redundant probe subprocess."""
    import threading

    def init_inprocess(deadline):
        ok = []

        def probe():
            try:
                import jax
                jax.devices()
                ok.append(True)
            except Exception:
                pass

        t = threading.Thread(target=probe, daemon=True)
        t.start()
        t.join(deadline)
        return bool(ok)

    if retries is None:
        try:
            retries = int(os.environ.get('BF_BENCH_INIT_RETRIES', '3'))
        except ValueError:
            retries = 3
    skip_probe = os.environ.get('BF_SKIP_PROBE') == '1'
    for attempt in range(1 + max(retries, 0)):
        if attempt:
            time.sleep(min(45.0 * attempt, 120.0))
        if skip_probe:
            return init_inprocess(timeout)
        healthy, _hist = _probe_backend(timeout, retries=0)
        if healthy and init_inprocess(timeout):
            return True
    return False


def bench_fft_impls():
    """Micro-compare the spectroscopy FFT step between jnp.fft and the
    4-step DFT-as-matmul MXU path (BF_FFT_IMPL=dftmm), on the bench
    shape.  Settles VERDICT r2 item 2's first question with one
    artifact."""
    import jax
    import jax.numpy as jnp
    from bifrost_tpu.ops.fft import dft_matmul_fft
    from bifrost_tpu.xfer import to_device

    T = 2048
    rng = np.random.RandomState(3)
    # complex input via re/im planes (the xfer.py convention)
    x = to_device((rng.randn(T, NPOL, NFINE) +
                   1j * rng.randn(T, NPOL, NFINE))
                  .astype(np.complex64))
    n = x.size

    def force_c(arr):
        # complex outputs: force via |.| (float(<complex>) raises)
        return float(jnp.sum(jnp.abs(arr)))

    def timeit(fn):
        f = jax.jit(fn)
        force_c(f(x))                      # compile + drain
        t0 = time.perf_counter()
        iters = 8
        for _ in range(iters):
            y = f(x)
        force_c(y)
        return n * iters / (time.perf_counter() - t0) / 1e6

    out = {'jnp_fft_msps': round(timeit(
        lambda a: jnp.fft.fft(a, axis=-1)), 1)}
    out['dftmm_msps'] = round(timeit(
        lambda a: dft_matmul_fft(a, axis=-1)), 1)
    out['dftmm_speedup'] = round(out['dftmm_msps'] /
                                 max(out['jnp_fft_msps'], 1e-9), 3)
    return out


def bench_spectrometer_kernel():
    """Measure the fused Pallas spectrometer (ops/spectrometer.py) at
    the bench shape: accuracy vs the float64 oracle and throughput per
    precision/tile, plus which precision the auto mode would pick.
    The flagship number above already reflects auto mode (BF_SPEC_IMPL);
    this entry documents the kernel's standalone envelope."""
    import jax
    import jax.numpy as jnp
    from bifrost_tpu.ops.spectrometer import (fused_spectrometer,
                                              spectrometer_accuracy,
                                              choose_precision)
    if jax.devices()[0].platform != 'tpu':
        return {'skipped': 'tpu-only measurement'}
    out = {'chosen_by_auto': str(choose_precision(NFINE, RFACTOR))}
    rng = np.random.RandomState(5)
    T = 4096
    big = rng.randint(-64, 64,
                      size=(T, NPOL, NFINE, 2)).astype(np.int8)
    xb = jnp.asarray(big)
    n = T * NPOL * NFINE
    for prec, name in ((None, 'default'), ('high', 'high'),
                       ('highest', 'highest')):
        entry = {'rel_err': spectrometer_accuracy(prec, NFINE, RFACTOR)}
        if entry['rel_err'] >= 1e9:
            from bifrost_tpu.ops import mprobe
            entry['probe_error'] = {
                k: v for k, v in mprobe.refusals().items()
                if k.startswith('spectrometer/')}
        best = None
        for tile in (8, 16):
            for trans in ('kernel', 'epilogue'):
                try:
                    f = jax.jit(
                        lambda v, p=prec, t=tile, m=trans:
                        fused_spectrometer(v, rfactor=RFACTOR,
                                           time_tile=t, precision=p,
                                           transpose=m))
                    _force(f(xb))
                    t0 = time.perf_counter()
                    iters = 8
                    for _ in range(iters):
                        y = f(xb)
                    _force(y)
                    msps = n * iters / (time.perf_counter() - t0) / 1e6
                    if best is None or msps > best[2]:
                        best = (tile, trans, msps)
                except Exception as e:
                    entry.setdefault('tile_errors', {})[
                        '%d/%s' % (tile, trans)] = \
                        '%s: %s' % (type(e).__name__, str(e)[:120])
        if best:
            entry['best_tile'] = best[0]
            entry['best_transpose'] = best[1]
            entry['msps'] = round(best[2], 1)
            entry['vs_baseline'] = round(best[2] / A100_BASELINE_MSPS, 4)
        out[name] = entry
    return out


def bench_traffic_probe():
    """Cross-check chain_traffic_model's hand bytes-per-sample
    constants against the compiled program's own accounting (VERDICT
    r4 item 8): jit-lower the SAME composed stage chain the FusedBlock
    runs, at the bench gulp shape, and read XLA's 'bytes accessed' for
    the compiled executable.  The roofline's denominator can no longer
    drift silently — the artifact records modeled vs compiled and
    whether they agree within 15%.

    Caveat recorded in the result: for the Pallas whole-chain kernel,
    XLA models only the custom call's operands and results — which IS
    the model's claim (nothing else leaves VMEM), so agreement there
    confirms the interface traffic, not the kernel's internals."""
    import jax
    import jax.numpy as jnp
    from bifrost_tpu.stages import compose_stages, walk_headers
    stages = flagship_stages()
    headers = walk_headers(stages, flagship_header())
    shape = (NTIME, NPOL, NFINE, 2)
    fn, info = compose_stages(stages, headers, shape, 'int8')
    modeled, label = chain_traffic_model(info)
    nsamples = NTIME * NPOL * NFINE
    out = {'impl': label,
           'modeled_bytes_per_sample': modeled,
           'nsamples_per_gulp': nsamples}
    try:
        compiled = jax.jit(fn).lower(
            jax.ShapeDtypeStruct(shape, jnp.int8)).compile()
        ca = compiled.cost_analysis()
    except Exception as e:
        out['error'] = '%s: %s' % (type(e).__name__, str(e)[:200])
        return out
    d = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
    bytes_acc = float(d.get('bytes accessed', 0.0) or 0.0)
    if not bytes_acc:
        out['error'] = 'cost_analysis reported no bytes accessed'
        return out
    measured = bytes_acc / nsamples
    out['compiled_bytes_per_sample'] = round(measured, 2)
    out['ratio_compiled_over_model'] = round(measured / modeled, 3)
    out['within_15pct'] = bool(abs(measured / modeled - 1.0) <= 0.15)
    return out


def _run_isolated(argv, timeout=900, env_extra=None):
    """Run a bench entrypoint in a FRESH subprocess and parse the last
    JSON line of its stdout.  Each config gets its own backend, one
    child at a time: a failure in one cannot reach the next, and the
    chip is never asked for by two processes at once."""
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    # the parent already proved the backend alive; a child that loses
    # it mid-suite must fail fast with its graceful rc=2 JSON rather
    # than burn the isolation timeout in _backend_alive retries
    env = dict(os.environ, BF_BENCH_INIT_RETRIES='0',
               BF_SKIP_PROBE='1')
    if env_extra:
        env.update(env_extra)
    try:
        p = subprocess.run([sys.executable] + argv, cwd=here,
                           capture_output=True, text=True,
                           timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return {'error': 'subprocess timeout after %ds' % timeout}
    line = None
    for ln in (p.stdout or '').splitlines():
        ln = ln.strip()
        # skip preamble lines (e.g. bench_suite's chip_ceilings echo):
        # a crash between the preamble and the result must not record
        # the preamble as the config's result
        if ln.startswith('{') and '"chip_ceilings"' not in ln:
            line = ln
    if line is None or p.returncode != 0:
        err = 'rc=%d, stderr: %s' % (
            p.returncode, (p.stderr or '')[-200:].replace('\n', ' '))
        if line is None:
            return {'error': 'no JSON output (%s)' % err}
        try:
            parsed = json.loads(line)
        except ValueError:
            return {'error': 'unparseable output: %s' % line[:200]}
        parsed.setdefault('error', 'subprocess failed (%s)' % err)
        return parsed
    try:
        return json.loads(line)
    except ValueError:
        return {'error': 'unparseable output: %s' % line[:200]}


def run_suite_into(result):
    """Fold the bench_suite configs + chip ceilings + the correctness
    gate + the FFT-impl comparison into ``result`` (VERDICT r2 item 1:
    BENCH_r03.json alone must prove configs 1-6), and write the full
    detail next to this file: BENCH_SUITE_r04.json on real hardware,
    BENCH_SUITE_cpu_validation.json for CPU fallback runs (so a
    validation run can never clobber chip-measured numbers)."""
    here = os.path.dirname(os.path.abspath(__file__))
    platform = result.get('platform', 'unknown')
    detail = {'primary': dict(result), 'platform': platform}

    # every device-touching step runs in its own subprocess — the
    # parent aggregates JSON and never initializes PJRT, so no hung
    # init can cost the whole artifact (VERDICT r4 item 5)
    gate = _run_isolated(['bench.py', '--check'])
    result['check_ok'] = bool(gate.get('ok'))
    result['check'] = {k: gate[k] for k in
                       ('stokes_rel_err', 'deterministic', 'failures',
                        'error') if k in gate}
    detail['gate'] = gate

    ceil = _run_isolated(['bench.py', '--ceilings'])
    detail['ceilings'] = ceil
    result['ceilings'] = {k: round(v, 2) for k, v in ceil.items()
                          if isinstance(v, float)}
    if 'error' in ceil:
        # keep the root failure visible in the driver-recorded line,
        # not just as downstream KeyErrors in configs 3-5
        result['ceilings']['error'] = ceil['error']

    configs = {}
    # config 2 is the flagship measurement already in `result`.
    # the fraction of the MEASURED HBM ceiling the fused chain
    # sustains is the roofline verdict on the chain (VERDICT r2 item 2)
    chain_bytes_per_sample, impl = chain_traffic_model(
        result.get('impl_record'))
    c2 = {'config': 'Guppi spectroscopy (flagship, above)',
          'value': result['value'],
          'unit': result['unit'],
          'impl': impl,
          'vs_baseline': result['vs_baseline']}
    if isinstance(ceil.get('hbm_gbs'), float):
        achieved = result['value'] * 1e6 * chain_bytes_per_sample / 1e9
        c2['roofline'] = {
            'chain_bytes_per_sample': chain_bytes_per_sample,
            'achieved_GBs': round(achieved, 1),
            'hbm_GBs': round(ceil['hbm_gbs'], 1),
            'hbm_frac': round(achieved / ceil['hbm_gbs'], 3),
            'bound': ('HBM in/out (whole chain resident in VMEM)'
                      if impl.startswith('pallas') else
                      'HBM bandwidth (FFT custom call caps fusion; '
                      'see pallas fused-spectrometer path)')}
    configs['2'] = c2
    ceil_f = {k: v for k, v in ceil.items() if isinstance(v, float)}
    for cid in (1, 3, 4, 5, 6, 7, 8, 9):
        argv = ['bench_suite.py', '--config', str(cid)]
        if cid in (3, 4, 5) and ceil_f:
            # pass ceilings only when actually measured — an empty
            # dict would stop the fresh subprocess from measuring its
            # own after a parent-process backend failure
            argv += ['--ceil-json', json.dumps(ceil_f)]
        if cid == 7:
            argv += ['--msps-pipe', str(result['value'])]
        res = _run_isolated(argv)
        compact = _compact_config(res)
        detail['config_%d' % cid] = res
        configs[str(cid)] = compact
    result['configs'] = configs

    fft_cmp = _run_isolated(['bench.py', '--fft-impl'])
    result['fft_impl'] = fft_cmp
    detail['fft_impl'] = fft_cmp

    spec = _run_isolated(['bench.py', '--spectrometer'])
    result['spectrometer'] = spec
    detail['spectrometer'] = spec

    traffic = _run_isolated(['bench.py', '--traffic'])
    # the probe re-derives the impl in its own subprocess; if the
    # substitution decision diverged from the flagship run's published
    # record, the probe validated the WRONG denominator — flag it
    # rather than letting the artifact read as 'roofline validated'
    if 'impl' in traffic and traffic['impl'] != impl:
        traffic['impl_mismatch'] = (
            'probe compiled %s but the flagship ran %s; the roofline '
            'denominator is unvalidated' % (traffic['impl'], impl))
        traffic['within_15pct'] = False
    result['traffic_model'] = traffic
    detail['traffic_model'] = traffic

    # capture label from the watcher (BF_BENCH_ROUND, default stamped
    # by capture date) so future runs are never mislabeled with a
    # stale hardcoded round number
    round_tag = os.environ.get('BF_BENCH_ROUND') or \
        time.strftime('r%Y%m%d', time.gmtime())
    name = 'BENCH_SUITE_%s.json' % round_tag if platform == 'tpu' \
        else 'BENCH_SUITE_%s_validation.json' % platform
    try:
        with open(os.path.join(here, name), 'w') as f:
            json.dump(detail, f, indent=1, default=str)
    except OSError:
        pass
    return result


# the one projection both the healthy and the degraded artifact use,
# so the two can never silently report different fields
_COMPACT_KEYS = ('config', 'value', 'unit', 'vs_baseline', 'error',
                 'serial_s', 'pipeline_s', 'reference_bar',
                 'delivered_frac', 'delivery_ok')
_COMPACT_ROOF_KEYS = ('bw_frac', 'mfu', 'bound', 'pps_native_engine',
                      'goodput_Gbps', 'burst_eff', 'offered_pkts')


def _compact_config(res):
    """Project a config subprocess result onto the driver-line keys."""
    res.pop('config_id', None)
    compact = {}
    for k in _COMPACT_KEYS:
        if k in res:
            compact[k] = (round(res[k], 2)
                          if isinstance(res[k], float) else res[k])
    roof = res.get('roofline', {})
    for k in _COMPACT_ROOF_KEYS:
        if k in roof:
            compact[k] = (round(roof[k], 3)
                          if isinstance(roof[k], float) else roof[k])
    if 'core_compare' in res:
        compact['core_compare'] = res['core_compare']
    return compact


def _captured_date(here, pathn):
    """Commit date of an artifact, not mtime: a fresh checkout resets
    mtimes, and 'captured' must mean when the measurement was taken."""
    try:
        import subprocess
        p = subprocess.run(
            ['git', 'log', '-1', '--format=%cI', '--',
             os.path.basename(pathn)],
            cwd=here, capture_output=True, text=True, timeout=30)
        captured = (p.stdout or '').strip() or None
        if captured:
            return captured
    except Exception:
        pass
    return time.strftime('%Y-%m-%dT%H:%M:%SZ',
                         time.gmtime(os.path.getmtime(pathn)))


def degraded_result(history, reason=None):
    """Dead-backend artifact that still proves everything provable
    without a chip (VERDICT r4 item 4): host-only configs 1/6, the
    last-known-good chip artifact flagged stale, and the probe
    history — instead of a bare error line."""
    here = os.path.dirname(os.path.abspath(__file__))
    result = {
        'metric': 'Guppi spectroscopy pipeline (FFT-detect-reduce) '
                  'throughput per chip',
        'error': reason or (
            'jax backend failed to initialize after repeated probes '
            'with backoff (no accelerator reachable?); host-only '
            'evidence below'),
        'platform': 'none',
        'value': 0.0, 'unit': 'Msamples/s', 'vs_baseline': 0.0,
        'probe_history': history,
        'configs': {},
    }
    # configs 1 (host sigproc) and 6 (capture loopback) need no chip
    for cid in (1, 6):
        res = _run_isolated(['bench_suite.py', '--config', str(cid)],
                            env_extra={'JAX_PLATFORMS': 'cpu'})
        result['configs'][str(cid)] = _compact_config(res)
    # newest chip-measured suite artifact, clearly flagged stale
    import glob
    best = None
    for pathn in sorted(glob.glob(
            os.path.join(here, 'BENCH_SUITE_r*.json'))):
        try:
            with open(pathn) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        if d.get('platform') == 'tpu':
            best = (pathn, d)
    if best:
        pathn, d = best
        captured = _captured_date(here, pathn)
        result['last_known_good'] = {
            'file': os.path.basename(pathn),
            'stale': True,
            'captured': captured,
            'flagship': d.get('primary', {}),
        }
    # the CPU-validation artifact proves the whole suite executes
    # end-to-end (pipeline, gate, traffic cross-check) even without a
    # chip — embed its summary, clearly labeled as validation numbers
    try:
        with open(os.path.join(
                here, 'BENCH_SUITE_cpu_validation.json')) as f:
            val = json.load(f)
        vpath = os.path.join(here, 'BENCH_SUITE_cpu_validation.json')
        prim = val.get('primary', {})
        result['cpu_validation'] = {
            'validation_only': True,
            'platform': val.get('platform'),
            'captured': _captured_date(here, vpath),
            'flagship_msps': prim.get('value'),
            'check_ok': val.get('gate', {}).get('ok'),
            'traffic_model': val.get('traffic_model'),
        }
    except (OSError, ValueError):
        pass
    # round-long watcher history, when a watcher has been running
    watch = os.path.join(here, 'bench_watch.log')
    try:
        with open(watch) as f:
            result['watch_log_tail'] = f.read().splitlines()[-12:]
    except OSError:
        pass
    return result


#: byte budget for the FINAL stdout line in degraded mode: the driver
#: tail-captures stdout and a fat one-line JSON defeats its parser
#: (VERDICT r5 item 3/5: `BENCH_r05.json parsed: null` — the degraded
#: line inlined the whole probe history + watch log).  ≤2 KB with
#: metric/error/pointer; the full detail goes to a side file.
DEGRADED_LINE_LIMIT = 2048


def _last_json_line(text):
    """The driver's parse path (mirrors _run_isolated): the last
    stdout line that is a JSON object, skipping preamble echoes.
    Returns the parsed dict or None — a line the driver cannot parse
    is exactly the `parsed: null` failure the compaction exists to
    prevent, so tests exercise THIS function."""
    line = None
    for ln in (text or '').splitlines():
        ln = ln.strip()
        if ln.startswith('{') and '"chip_ceilings"' not in ln:
            line = ln
    if line is None or len(line) > DEGRADED_LINE_LIMIT:
        return None
    try:
        return json.loads(line)
    except ValueError:
        return None


def _compact_probe_history(history):
    """Probe attempts compressed to counts + the last entry (VERDICT
    r5 item 5: the full history made the degraded line unparseable)."""
    history = list(history or [])
    rcs = [h.get('rc') for h in history]
    out = {'attempts': len(history),
           'rc_counts': {}}
    for rc in rcs:
        key = str(rc)
        out['rc_counts'][key] = out['rc_counts'].get(key, 0) + 1
    if history:
        last = dict(history[-1])
        err = last.get('error')
        if isinstance(err, str) and len(err) > 160:
            last['error'] = err[:160] + '...'
        out['last'] = last
    return out


def compact_degraded_line(result, limit=DEGRADED_LINE_LIMIT,
                          detail_name=None):
    """Project a degraded artifact onto a driver-parseable final line.

    Writes the FULL ``result`` to a side file (pointer included in the
    line), truncates the probe history to counts + last error, and
    drops progressively less-essential fields until the serialized
    line fits ``limit`` bytes.  The essentials — metric, error,
    value/unit/vs_baseline, platform — always survive."""
    here = os.path.dirname(os.path.abspath(__file__))
    if detail_name is None:
        round_tag = os.environ.get('BF_BENCH_ROUND') or \
            time.strftime('r%Y%m%d', time.gmtime())
        detail_name = 'BENCH_DEGRADED_%s.json' % round_tag
    try:
        with open(os.path.join(here, detail_name), 'w') as f:
            json.dump(result, f, indent=1, default=str)
        detail_ref = detail_name
    except OSError:
        detail_ref = None

    line = {k: result[k] for k in
            ('metric', 'error', 'platform', 'value', 'unit',
             'vs_baseline', 'flagship_error') if k in result}
    if isinstance(line.get('error'), str):
        line['error'] = line['error'][:300]
    line['probe'] = _compact_probe_history(result.get('probe_history'))
    if detail_ref:
        line['detail_file'] = detail_ref
    lkg = result.get('last_known_good')
    if isinstance(lkg, dict):
        line['last_known_good'] = {
            'file': lkg.get('file'), 'stale': True,
            'captured': lkg.get('captured'),
            'flagship_msps': (lkg.get('flagship') or {}).get('value')}
    val = result.get('cpu_validation')
    if isinstance(val, dict):
        line['cpu_validation'] = {
            'validation_only': True,
            'flagship_msps': val.get('flagship_msps'),
            'check_ok': val.get('check_ok')}
    cfgs = result.get('configs') or {}
    line['configs'] = {cid: {k: c[k] for k in
                             ('value', 'unit', 'error') if k in c}
                       for cid, c in cfgs.items()
                       if isinstance(c, dict)}
    # progressive drops until the line fits; the order is
    # least-essential first (everything dropped remains in the side
    # file, which the pointer names)
    drops = ['cpu_validation', 'configs', 'last_known_good', 'probe',
             'flagship_error']
    while len(json.dumps(line)) > limit and drops:
        line.pop(drops.pop(0), None)
    if len(json.dumps(line)) > limit:     # pathological error string
        line['error'] = (line.get('error') or '')[:100]
        line = {k: line[k] for k in ('metric', 'error', 'value',
                                     'unit', 'vs_baseline',
                                     'detail_file') if k in line}
    return line


_CHILD_MODES = ('--check', '--fft-impl', '--spectrometer',
                '--ceilings', '--traffic', '--flagship-only')


def main():
    if any(m in sys.argv for m in _CHILD_MODES):
        # child entrypoints own a backend; the parent below never does
        if not _backend_alive():
            print(json.dumps({
                'metric': 'backend initialization',
                'error': 'jax backend failed to initialize',
                'value': 0.0, 'unit': 'Msamples/s',
                'vs_baseline': 0.0}))
            return 2
        if '--check' in sys.argv:
            res = run_correctness_gate()
            print(json.dumps(res))
            return 0 if res['ok'] else 1
        if '--fft-impl' in sys.argv:
            print(json.dumps(bench_fft_impls()))
            return 0
        if '--spectrometer' in sys.argv:
            print(json.dumps(bench_spectrometer_kernel()))
            return 0
        if '--ceilings' in sys.argv:
            import bench_suite
            print(json.dumps(bench_suite.measure_ceilings()))
            return 0
        if '--traffic' in sys.argv:
            print(json.dumps(bench_traffic_probe()))
            return 0
        # --flagship-only: the ring-pipeline measurement itself
        msps, impl_record = build_and_run()
        import jax
        print(json.dumps({
            'metric': 'Guppi spectroscopy pipeline (FFT-detect-reduce) '
                      'throughput per chip',
            # a 'cpu' platform marks a fallback-validation run, NOT
            # chip numbers — keep the label so artifacts can't be
            # misread
            'platform': jax.devices()[0].platform,
            'value': round(msps, 1),
            'unit': 'Msamples/s',
            'vs_baseline': round(msps / A100_BASELINE_MSPS, 4),
            # the impl record the executed FusedBlock published
            # (ProcLog <block>/impl): the artifact's label provably
            # comes from the executed pipeline, not a re-derivation
            'impl_record': impl_record,
            'impl': chain_traffic_model(impl_record)[1],
        }))
        return 0

    # PARENT AGGREGATOR: probes via subprocesses, runs every
    # measurement via _run_isolated, and only assembles JSON — no code
    # path here can hit the documented un-retryable PJRT init hang
    # (VERDICT r4 item 5)
    healthy, history = _probe_backend()
    if not healthy:
        # compact final line (≤2 KB, driver-parseable); the full
        # degraded detail lands in the side file the line points to
        print(json.dumps(compact_degraded_line(
            degraded_result(history))))
        return 2
    result = _run_isolated(['bench.py', '--flagship-only'],
                           timeout=2400)
    if 'value' not in result or result.get('error'):
        # healthy probe but the flagship child failed: degrade with
        # the child's error attached — and a reason that does NOT
        # claim an infra outage the probe history would contradict
        deg = degraded_result(
            history,
            reason='flagship pipeline subprocess failed (backend '
                   'probes were healthy — see flagship_error); '
                   'host-only evidence below')
        deg['flagship_error'] = result.get('error', 'no output')
        print(json.dumps(compact_degraded_line(deg)))
        return 2
    # fold gate + all suite configs + ceilings + FFT-impl compare
    # into the one line the driver records (VERDICT r2 item 1); any
    # sub-benchmark failure degrades to an error field instead of
    # losing the whole artifact
    try:
        result = run_suite_into(result)
    except Exception as e:
        result['suite_error'] = '%s: %s' % (type(e).__name__,
                                            str(e)[:300])
    print(json.dumps(result))


if __name__ == '__main__':
    sys.exit(main())
