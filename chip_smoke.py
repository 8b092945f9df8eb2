#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that bifrost_tpu still starts on
the chip.  One process, no arguments, exit 0 within 1200 s:

    python3 chip_smoke.py

Phase A drives the main path through the public API at the flagship's
own width (16384 frames x 2 pol x 4096 fine-time ci8, reduce 4 — 268
MB in, 268 MB of Stokes f32 out, per gulp; this file owns the
flagship chain, below): a host source of
seeded gulps -> blocks.copy('tpu') -> blocks.fused(flagship_stages())
-> blocks.copy('system') -> sink.  Sampled frames of EVERY gulp are
compared with the float64 numpy oracle at rel <= 1e-5, two runs of one
seed must be bit-identical, and the chain runs under the default
selection and again with the other spectrometer implementation forced.
Phase B builds every Pallas kernel and raced family at its
BASELINE.json shape with a real compile and reports one verdict per
candidate.  Phase C records three facts the later queue needs.

It fails (non-zero, one clear line, no result) unless
``jax.devices()[0].platform == 'tpu'``.  ``--allow-cpu`` is the CPU
rehearsal tier-1 runs: sizes from the arguments, output marked
``rehearsal: true``, phases B and C skipped and said so.

A failed phase prints its traceback and the run exits 1; nothing here
turns a failure into a zero exit.  The last line of stdout is one JSON
object: ``{"ok": true, "device": {...}}`` on success.
"""

import argparse
import contextlib
import gc
import json
import os
import shutil
import subprocess
import sys
import time
import traceback
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))

#: the flagship gulp (BASELINE.json config 2, upstream
#: testbench/gpuspec_simple.py): frames per gulp, polarisations,
#: fine-time samples (the FFT length) and the frequency reduction
NTIME, NPOL, NFINE, RFACTOR = 16384, 2, 4096, 4

#: rel. error bar against the float64 oracle
#: (ops/spectrometer.choose_precision uses the same)
ORACLE_RTOL = 1e-5
NGULP_WARM = 2
NGULP_STEADY = 6
NSAMPLE = 64          # frames of every gulp compared with the oracle


def say(msg=''):
    print(msg, flush=True)


def first_line(exc):
    text = str(exc).strip()
    return '%s: %s' % (type(exc).__name__,
                       text.splitlines()[0][:400] if text else '')


def flagship_header(npol=NPOL, nfine=NFINE):
    """The flagship gulp's ring header."""
    return {'name': 'flagship', 'time_tag': 0,
            '_tensor': {'shape': [-1, npol, nfine], 'dtype': 'ci8',
                        'labels': ['time', 'pol', 'fine_time'],
                        'scales': [[0, 1]] * 3, 'units': [None] * 3}}


def flagship_stages(rfactor=RFACTOR):
    """The flagship FFT -> detect -> reduce stage chain."""
    from bifrost_tpu.stages import FftStage, DetectStage, ReduceStage
    return [FftStage('fine_time', axis_labels='freq'),
            DetectStage('stokes', axis='pol'),
            ReduceStage('freq', rfactor)]


def rel_err(got, want):
    """Max abs error relative to the reference's peak."""
    import numpy as np
    return float(np.max(np.abs(got - want)) /
                 (np.max(np.abs(want)) or 1.0))


class Failures(object):
    """What went wrong, in order.  A phase that raises lands here with
    its traceback printed; the run goes on to the next phase (one chip
    call should say everything it can) and exits 1 at the end."""

    def __init__(self):
        self.items = []

    def add(self, what):
        self.items.append(what)
        say('FAILED: %s' % what)

    def phase(self, name, fn, *args, **kwargs):
        say('\n== phase %s ==' % name)
        # the pipelines of the phase before are cycles of blocks and
        # rings, and their device arrays go only with a collection
        # (6.7 GB after phase A without it, 2.15 GB with: the last
        # pipeline's rings and queues are still held somewhere).
        # Phase B's X-engine gate at the BASELINE shape wants more
        # than is left either way: it has refused since PR 27 at the
        # latest, on the parent of PR 28 as on PR 28 (CHANGES.md)
        gc.collect()
        say('device bytes in use: %s' % device_bytes_in_use())
        t0 = time.time()
        try:
            out = fn(*args, **kwargs)
        except Exception as exc:
            traceback.print_exc()
            sys.stderr.flush()
            self.add('phase %s raised %s' % (name, first_line(exc)))
            out = None
        say('-- phase %s took %.1f s' % (name, time.time() - t0))
        return out


def device_bytes_in_use():
    """``bytes_in_use`` of the first device, or None where the backend
    keeps no such statistic (the CPU)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get('bytes_in_use')


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--seed', type=int, default=0,
                    help='seed of the voltages (and of phase B\'s data)')
    ap.add_argument('--allow-cpu', action='store_true',
                    help='CPU rehearsal: phase A at the sizes below, '
                         'phases B and C skipped')
    ap.add_argument('--ntime', type=int, default=None,
                    help='frames per gulp (rehearsal only; chip runs '
                         'use the flagship 16384)')
    ap.add_argument('--nfine', type=int, default=None,
                    help='FFT length (rehearsal only; flagship 4096)')
    ap.add_argument('--devices', type=int, default=1,
                    help='the four-chip check (not gating): phase A '
                         'over a mesh of this many devices, then the '
                         'ring_permute kernel, and nothing else')
    ap.add_argument('--out', default=os.path.join(HERE, 'chiprun_out',
                                                  'chip_smoke'),
                    help='output directory (probe caches, result.json)')
    args = ap.parse_args(argv)
    if not args.allow_cpu and (args.ntime or args.nfine):
        ap.error('--ntime/--nfine are for the --allow-cpu rehearsal; '
                 'a chip run is at the flagship width')
    return args


def clean_environment(out_dir):
    """The smoke runs the DEFAULT selection and reads nothing it did
    not write in this run: every BF_* variable goes, and BF_CACHE_DIR
    (mprobe winners, telemetry state) points at a fresh directory."""
    dropped = sorted(k for k in os.environ if k.startswith('BF_'))
    for k in dropped:
        del os.environ[k]
    cache = os.path.join(out_dir, 'bf_cache')
    shutil.rmtree(cache, ignore_errors=True)
    os.makedirs(cache)
    os.environ['BF_CACHE_DIR'] = cache
    return dropped, cache


def rebuild_native():
    """native/build/ is git-ignored but travels with the tree as it
    stands, so the .so is rebuilt from the committed native/*.cpp.
    ``make`` is a child that never imports JAX."""
    t0 = time.time()
    proc = subprocess.run(['make', '-C', os.path.join(HERE, 'native'),
                           '-B'], capture_output=True, text=True)
    if proc.returncode:
        raise RuntimeError('make -C native -B failed (rc %d):\n%s'
                           % (proc.returncode, proc.stderr[-2000:]))
    return time.time() - t0


def count_files(path):
    return sum(len(files) for _, _, files in os.walk(path))


def versions():
    from importlib import metadata
    out = {}
    for pkg in ('jax', 'jaxlib', 'libtpu', 'numpy'):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = 'not installed'
    out['python'] = '%d.%d.%d' % sys.version_info[:3]
    return out


# ---------------------------------------------------------------------------
# phase A: the pipeline
# ---------------------------------------------------------------------------

def make_gulps(seed, ngulp, ntime, npol, nfine):
    """``ngulp`` DIFFERENT seeded ci8 gulps (host storage dtype), made
    once and fed to every run — so a staging slot recycled before its
    DMA finished shows as wrong data, not as a repeat."""
    import numpy as np
    from bifrost_tpu.dtype import ci8
    rng = np.random.default_rng(seed)
    gulps = []
    for _ in range(ngulp):
        raw = rng.integers(-64, 64, size=(ntime, npol, nfine, 2),
                           dtype=np.int8)
        gulps.append(raw.view(ci8).reshape(ntime, npol, nfine))
    return gulps


def sample_frames(seed, ntime):
    """The fixed sample: first, last and NSAMPLE - 2 seeded frames."""
    import numpy as np
    n = min(NSAMPLE, ntime)
    rng = np.random.default_rng(seed + 1)
    idx = set([0, ntime - 1])
    while len(idx) < n:
        idx.add(int(rng.integers(0, ntime)))
    return np.array(sorted(idx))


def run_chain(gulps, ntime, npol, nfine, rfactor, sample_idx, mesh=None):
    """One pipeline run through the public API; returns what it saw."""
    import numpy as np
    import jax
    import bifrost_tpu as bf
    from bifrost_tpu.pipeline import SourceBlock, SinkBlock
    from bifrost_tpu.telemetry import counters

    header = flagship_header(npol, nfine)

    class VoltageSource(SourceBlock):
        """Host source: copies the next seeded gulp into the ring."""

        def __init__(self):
            super(VoltageSource, self).__init__(['voltages'], ntime,
                                                space='system')
            self.offered = 0

        def create_reader(self, name):
            return contextlib.nullcontext()

        def on_sequence(self, reader, name):
            return [dict(header)]

        def on_data(self, reader, ospans):
            if self.offered >= len(gulps):
                return [0]
            ospans[0].data.as_numpy()[...] = gulps[self.offered]
            self.offered += 1
            return [ntime]

    class SpectraSink(SinkBlock):
        """Keeps the sampled frames and a whole-gulp digest of every
        gulp (compared AFTER the run) and the arrival times."""

        def __init__(self, iring):
            super(SpectraSink, self).__init__(iring)
            self.samples, self.digests, self.times = [], [], []
            self.nframe = 0

        def on_sequence(self, iseq):
            pass

        def on_data(self, ispan):
            arr = ispan.data.as_numpy()
            self.nframe += arr.shape[0]
            if arr.shape[0] == ntime:
                self.samples.append(np.array(arr[sample_idx]))
            words = arr.view(np.uint32).ravel()
            self.digests.append(
                (int(np.bitwise_xor.reduce(words)),
                 int(words.sum(dtype=np.uint64))))
            self.times.append(time.perf_counter())

    class ShardTap(SinkBlock):
        """Mesh runs only: a second reader of a device ring recording
        on which devices each committed gulp's shards live."""

        def __init__(self, iring):
            super(ShardTap, self).__init__(iring)
            self.devices = []

        def on_sequence(self, iseq):
            pass

        def on_data(self, ispan):
            self.devices.append(sorted(
                s.device.id for s in ispan.data.addressable_shards))

    before = counters.snapshot()
    scope = bf.block_scope(mesh=mesh) if mesh is not None \
        else contextlib.nullcontext()
    taps = {}
    with bf.Pipeline() as pipe:
        src = VoltageSource()
        with scope:
            h2d = bf.blocks.copy(src, space='tpu')
            fused = bf.blocks.fused(h2d, flagship_stages(rfactor))
            d2h = bf.blocks.copy(fused, space='system')
        if mesh is not None:
            taps = {'h2d': ShardTap(h2d), 'fused': ShardTap(fused)}
        sink = SpectraSink(d2h)
        pipe.run()
    after = counters.snapshot()

    def delta(name):
        return int(after.get(name, 0)) - int(before.get(name, 0))

    stats = jax.devices()[0].memory_stats() or {}
    times = sink.times
    return {
        'offered': src.offered,
        'delivered': len(sink.digests),
        'frames_delivered': sink.nframe,
        'impl_info': fused.impl_info,
        'ring_cores': {'source(system)': type(src.orings[0]).__name__,
                       'h2d(tpu)': type(h2d.orings[0]).__name__,
                       'd2h(system)': type(d2h.orings[0]).__name__},
        'samples': sink.samples,
        'digests': sink.digests,
        'steady_wall_s': (times[-1] - times[NGULP_WARM - 1])
        if len(times) > NGULP_WARM else None,
        'h2d_bytes': delta('xfer.h2d_bytes'),
        'd2h_bytes': delta('xfer.d2h_bytes'),
        'h2d_staged': delta('xfer.h2d_staged'),
        'h2d_unstaged': delta('xfer.h2d_unstaged'),
        'sync_waits': delta('pipeline.sync_waits'),
        'frame_local_fallback': delta('mesh.frame_local_fallback'),
        'reshards': delta('mesh.reshards'),
        'peak_bytes_in_use': stats.get('peak_bytes_in_use'),
        # the distinct placements seen over the run, per device ring
        'shard_devices': {k: sorted(set(map(tuple, t.devices)))
                          for k, t in taps.items()},
    }


def check_run(label, run, oracle, ngulp, ntime, fails):
    """Delivery and oracle checks of one run; returns the worst rel."""
    import numpy as np
    if run['offered'] != ngulp or run['delivered'] != ngulp or \
            run['frames_delivered'] != ngulp * ntime:
        fails.add('%s: offered %d gulps, delivered %d (%d frames, '
                  'expected %d)' % (label, run['offered'],
                                    run['delivered'],
                                    run['frames_delivered'],
                                    ngulp * ntime))
        return None
    worst = 0.0
    for g, (got, want) in enumerate(zip(run['samples'], oracle)):
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            fails.add('%s: gulp %d has shape %s (expected %s) or '
                      'non-finite values' % (label, g, got.shape,
                                             want.shape))
            return None
        rel = rel_err(got, want)
        worst = max(worst, rel)
        if rel > ORACLE_RTOL:
            fails.add('%s: gulp %d differs from the float64 oracle by '
                      'rel %.3g > %g' % (label, g, rel, ORACLE_RTOL))
    return worst


def describe_run(label, run, worst):
    say('%s: gulps offered %d, delivered %d; implementation %s'
        % (label, run['offered'], run['delivered'],
           json.dumps(run['impl_info'], sort_keys=True)))
    say('  worst rel. error vs float64 oracle over %d sampled frames '
        'of every gulp: %s (bar %g)'
        % (NSAMPLE, 'n/a' if worst is None else '%.3g' % worst,
           ORACLE_RTOL))
    say('  ring cores: %s' % json.dumps(run['ring_cores']))
    say('  H2D %d bytes (%d staged, %d unstaged ships), D2H %d bytes, '
        '%d hard sync waits'
        % (run['h2d_bytes'], run['h2d_staged'], run['h2d_unstaged'],
           run['d2h_bytes'], run['sync_waits']))
    say('  peak_bytes_in_use: %s'
        % (run['peak_bytes_in_use']
           if run['peak_bytes_in_use'] is not None
           else 'not reported by this backend'))
    say('  information, not a rate: the %d steady gulps took %s s of '
        'wall time (host source, oracle sampling and digests included)'
        % (NGULP_STEADY, 'n/a' if run['steady_wall_s'] is None
           else '%.3f' % run['steady_wall_s']))


def phase_a(args, sizes, on_tpu, fails):
    import numpy as np
    from bifrost_tpu.ops import spectrometer as spec
    ntime, npol, nfine, rfactor = sizes
    ngulp = NGULP_WARM + NGULP_STEADY
    result = {'sizes': {'ntime': ntime, 'npol': npol, 'nfine': nfine,
                        'rfactor': rfactor, 'ngulp': ngulp}}
    t0 = time.time()
    gulps = make_gulps(args.seed, ngulp, ntime, npol, nfine)
    sample_idx = sample_frames(args.seed, ntime)
    oracle = []
    for g in gulps:
        frames = g[sample_idx]
        volt = np.stack([frames['re'], frames['im']], axis=-1)
        oracle.append(spec.spectrometer_oracle(volt, rfactor=rfactor))
    say('made %d seeded gulps of %d x %d x %d ci8 (%.0f MB each) and '
        'their oracle samples in %.1f s'
        % (ngulp, ntime, npol, nfine, gulps[0].nbytes / 1e6,
           time.time() - t0))

    mesh = None
    if args.devices > 1:
        from bifrost_tpu.parallel.mesh import create_mesh
        mesh = create_mesh({'sp': args.devices})
        say('mesh: %s over devices %s'
            % (dict(mesh.shape), [d.id for d in mesh.devices.ravel()]))

    def one(label):
        run = run_chain(gulps, ntime, npol, nfine, rfactor, sample_idx,
                        mesh=mesh)
        worst = check_run(label, run, oracle, ngulp, ntime, fails)
        describe_run(label, run, worst)
        return run, worst

    # default selection, twice: oracle + run-to-run bit-identity
    run1, worst1 = one('default selection, run 1')
    run2, _ = one('default selection, run 2')
    identical = run1['digests'] == run2['digests'] and all(
        np.array_equal(a, b)
        for a, b in zip(run1['samples'], run2['samples']))
    say('two runs of seed %d bit-identical: %s' % (args.seed, identical))
    if not identical:
        fails.add('two runs of one seed are not bit-identical')
    default_impl = (run1['impl_info'] or {}).get('impl')
    result.update(default_impl=run1['impl_info'], default_rel=worst1,
                  deterministic=identical,
                  peak_bytes_in_use=run1['peak_bytes_in_use'])
    if mesh is not None:
        result['mesh'] = {
            'devices': [d.id for d in mesh.devices.ravel()],
            'shard_devices': run1['shard_devices'],
            'frame_local_fallback': run1['frame_local_fallback'],
            'reshards': run1['reshards']}
        say('mesh: committed gulps\' shards lived on devices %s; '
            'mesh.frame_local_fallback counted %d, mesh.reshards %d'
            % (json.dumps(run1['shard_devices']),
               run1['frame_local_fallback'], run1['reshards']))

    # the OTHER spectrometer implementation, forced
    if not on_tpu:
        say('second pass skipped: off the TPU the selection has one '
            'implementation (the XLA chain), by platform')
        result['other_impl'] = 'skipped (rehearsal)'
    else:
        other = 'xla' if default_impl == 'pallas-spectrometer' \
            else 'pallas'
        os.environ['BF_SPEC_IMPL'] = other
        try:
            refusal = None
            if other == 'pallas':
                # forced-only here (the default selection did not take
                # it): a kernel that cannot be built is reported, with
                # the compiler's message, and does not gate
                prec = spec.choose_precision(nfine, rfactor)
                tile = min(16, ntime)
                trans = spec.resolve_transpose('auto', nfine, rfactor)
                try:
                    spec.kernel_usable(nfine, rfactor, tile, prec, trans)
                except Exception as exc:
                    refusal = first_line(exc)
            if refusal is not None:
                say('forced BF_SPEC_IMPL=pallas: refused: %s' % refusal)
                result['other_impl'] = 'refused: %s' % refusal
            else:
                run3, worst3 = one('forced BF_SPEC_IMPL=%s' % other)
                got = (run3['impl_info'] or {}).get('impl')
                want = 'xla-fused' if other == 'xla' \
                    else 'pallas-spectrometer'
                if got != want:
                    fails.add('BF_SPEC_IMPL=%s ran %r' % (other, got))
                result.update(other_impl=run3['impl_info'],
                              other_rel=worst3)
        finally:
            del os.environ['BF_SPEC_IMPL']
    return result


# ---------------------------------------------------------------------------
# phase B: kernels that compile
# ---------------------------------------------------------------------------

def verdict(build, want, rtol, hard=False):
    """One candidate: ``build()`` compiles and runs it (a real
    compile); the result is held to ``want`` — exactly when ``rtol`` is
    0 (the integer paths), else within ``rtol`` of its peak.  ``hard``
    marks a bar the smoke itself sets (kernels, exact paths): missing
    it fails the run.  A soft bar is the engine's own accuracy class,
    whose gate drops the candidate from the race instead."""
    import numpy as np
    try:
        got = np.asarray(build())
    except Exception as exc:
        return {'status': 'refused', 'hard': hard,
                'text': 'refused: %s' % first_line(exc)}
    rel = rel_err(got, want)
    ok = bool(np.array_equal(got, want)) if rtol == 0 else rel <= rtol
    return {'status': 'compiled' if ok else 'gate_failed', 'hard': hard,
            'text': ('compiled (rel %.3g)' if ok
                     else 'gate_failed: rel %.3g') % rel}


def b_fdmt(seed):
    """FDMT nchan 256 / max_delay 100 / T 8192 (BASELINE.json config
    3)."""
    import numpy as np
    import jax.numpy as jnp
    from bifrost_tpu.ops.fdmt import Fdmt, fdmt_numpy, fdmt_gate_rtol
    nchan, md, T, f0, df = 256, 100, 8192, 1400.0, -0.1
    x = np.random.default_rng(seed).standard_normal(
        (nchan, T)).astype(np.float32)
    want = fdmt_numpy(nchan, md, f0, df, x)
    xj = jnp.asarray(x)
    out = {}
    for core in ('xla', 'rolls', 'pallas'):
        os.environ['BF_FDMT_IMPL'] = core
        try:
            out[core] = verdict(
                lambda: Fdmt().init(nchan, md, f0, df).execute(xj),
                want, fdmt_gate_rtol(), hard=True)
        finally:
            del os.environ['BF_FDMT_IMPL']
    plan = Fdmt().init(nchan, md, f0, df)
    plan.execute(xj).block_until_ready()
    return out, plan.chosen_core


def b_beamform(seed):
    """Beamform 256 ant x 64 beams x 512 chan, T 512 (BASELINE.json
    config 4), ci8 voltages, every candidate of the widest accuracy
    class ('int8': they all race there)."""
    import numpy as np
    import jax.numpy as jnp
    from bifrost_tpu.ops import pallas_kernels as pk
    from bifrost_tpu.ops.beamform import (Beamformer, BEAM_CLASSES,
                                          _wide_weight_block)
    T, F, S, B = 512, 512, 256, 64
    rng = np.random.default_rng(seed)
    w = (rng.standard_normal((B, S)) +
         1j * rng.standard_normal((B, S))).astype(np.complex64)
    re = rng.integers(-64, 64, (T, F, 1, S), dtype=np.int8)
    im = rng.integers(-64, 64, (T, F, 1, S), dtype=np.int8)
    rej, imj = jnp.asarray(re), jnp.asarray(im)
    x64 = (re.astype(np.float64) + 1j * im).reshape(T * F, S)
    want = (x64 @ w.astype(np.complex128).T).reshape(T, F, 1, B)
    rtol = {'xla': BEAM_CLASSES['f32'], 'planar': BEAM_CLASSES['f32'],
            'planar_bf16': BEAM_CLASSES['bf16'],
            'pallas_bf16': BEAM_CLASSES['bf16'],
            'int8_wide': BEAM_CLASSES['int8'],
            'pallas': BEAM_CLASSES['int8']}
    out = {}
    for name in rtol:
        out[name] = verdict(
            lambda: Beamformer(w, accuracy='int8', impl=name)(rej, imj),
            want, rtol[name])
    # the integer cores are EXACT: int32 accumulation of the quantized
    # weights, bit-identical to the integer oracle (computed in float64,
    # where every partial sum is an exact integer)
    eng = Beamformer(w, accuracy='int8')
    wr8 = eng.wr8[0].astype(np.float64)
    wi8 = eng.wi8[0].astype(np.float64)
    r64 = re.reshape(T * F, S).astype(np.float64)
    i64 = im.reshape(T * F, S).astype(np.float64)
    want_r = (r64 @ wr8.T - i64 @ wi8.T).astype(np.int32)
    want_i = (r64 @ wi8.T + i64 @ wr8.T).astype(np.int32)
    want_ri = np.stack([want_r, want_i]).reshape(2, T, F, B)
    w2 = jnp.asarray(_wide_weight_block(eng.wr8, eng.wi8))
    out['int8_wide integer core'] = verdict(
        lambda: jnp.stack(Beamformer.int8_planes(
            rej, imj, w2=w2, nbeam=B))[:, :, :, 0],
        want_ri, 0, hard=True)
    out['beamform_int8 kernel'] = verdict(
        lambda: jnp.stack(pk.beamform_int8(
            jnp.asarray(eng.wr8[0]), jnp.asarray(eng.wi8[0]),
            rej[:, :, 0], imj[:, :, 0], interpret=False)),
        want_ri, 0, hard=True)
    # the fused beamform -> Stokes -> integrate kernel, both pols fed
    # the same weights and voltages: I = 2|b|^2, Q = 0, U = 2|b|^2, V = 0
    rf = 4
    bq = (want_r.astype(np.float64) + 1j * want_i).reshape(T, F, B) \
        * eng.wscale
    p = (np.abs(bq) ** 2).reshape(T // rf, rf, F, B).sum(1)
    want_s = np.stack([2 * p, 0 * p, 2 * p, 0 * p])
    from bifrost_tpu.ops.beamform import fused_detect
    x5 = jnp.stack([jnp.stack([rej[:, :, 0], imj[:, :, 0]], -1)] * 2,
                   axis=3)                       # (T, F, S, pol, re/im)
    out['beamform_detect kernel'] = verdict(
        lambda: jnp.moveaxis(fused_detect(eng, x5, rf), 2, 0),
        want_s, 1e-5, hard=True)
    winner = Beamformer(w, accuracy='int8').prewarm(T, F, npol=1,
                                                    seed=seed)
    return out, winner


def b_correlate(seed):
    """ci8 correlation 256 ant x 2 pol x 1024 chan, T 512
    (BASELINE.json config 5): the X-engine's candidates and the two
    Pallas kernels, against the integer oracle on 8 of the channels."""
    import numpy as np
    import jax.numpy as jnp
    from bifrost_tpu.ops import pallas_kernels as pk
    from bifrost_tpu.ops.linalg import XEngine, XCORR_CLASSES
    T, F, n = 512, 1024, 512
    rng = np.random.default_rng(seed)
    re = rng.integers(-64, 64, (T, F, n), dtype=np.int8)
    im = rng.integers(-64, 64, (T, F, n), dtype=np.int8)
    rej, imj = jnp.asarray(re), jnp.asarray(im)
    chans = np.arange(0, F, F // 8)

    def vis(ri, ii, rj, ij):
        # float64 holds every partial sum exactly (|sum| < 2^53)
        out = []
        for f in chans:
            a = ri[:, f].astype(np.float64)
            b = ii[:, f].astype(np.float64)
            c = rj[:, f].astype(np.float64)
            d = ij[:, f].astype(np.float64)
            out.append((a.T @ c + b.T @ d) + 1j * (b.T @ c - a.T @ d))
        return np.stack(out).astype(np.complex64)

    want = vis(re, im, re, im)
    pick = lambda y: y[chans]
    rtol = {'xla': XCORR_CLASSES['f32'], 'planar': XCORR_CLASSES['f32'],
            'planar_bf16': XCORR_CLASSES['bf16'],
            'int8_3mm': 0, 'int8_wide': 0, 'pallas': 0}
    out = {}
    for name in rtol:
        out[name] = verdict(
            lambda: pick(XEngine(accuracy='bf16', impl=name)(rej, imj)),
            want, rtol[name], hard=rtol[name] == 0)
    out['xcorr_herm kernel'] = verdict(
        lambda: pick(pk.xcorr_herm(rej, imj, interpret=False)),
        want, 0, hard=True)
    ni = 128
    out['xcorr_cross kernel'] = verdict(
        lambda: pick(pk.xcorr_cross(rej[..., :ni], imj[..., :ni],
                                    rej, imj, interpret=False)),
        vis(re[..., :ni], im[..., :ni], re, im), 0, hard=True)
    winner = XEngine().prewarm(T, F, n, seed=seed)
    return out, winner


def b_spectrometer(seed, sizes):
    """Every precision x transpose form of the fused spectrometer at
    the full flagship gulp (VMEM limits bind at the real shape).  The
    one the default selection takes went through phase A; the rest are
    forced-only."""
    import numpy as np
    import jax.numpy as jnp
    from bifrost_tpu.ops.spectrometer import (fused_spectrometer,
                                              spectrometer_oracle)
    ntime, npol, nfine, rfactor = sizes
    volt = np.random.default_rng(seed).integers(
        -64, 64, (ntime, npol, nfine, 2), dtype=np.int8)
    idx = sample_frames(seed, ntime)
    want = spectrometer_oracle(volt[idx], rfactor=rfactor)
    vj = jnp.asarray(volt)
    out = {}
    for prec in (None, 'high', 'highest'):
        for trans in ('kernel', 'epilogue'):
            # one bf16 pass ('default') has no accuracy bar of its own:
            # the auto mode's 1e-5 gate decides whether it substitutes
            bar = float('inf') if prec is None else ORACLE_RTOL
            out['%s/%s' % (prec or 'default', trans)] = verdict(
                lambda: fused_spectrometer(
                    vj, rfactor=rfactor, time_tile=16, precision=prec,
                    transpose=trans)[idx],
                want, bar)
    return out, None


def b_stokes(seed):
    """The standalone Stokes-detect kernel (BF_USE_PALLAS=1 only:
    forced-only) at the flagship's fine-channel count."""
    import numpy as np
    import jax.numpy as jnp
    from bifrost_tpu.ops import pallas_kernels as pk
    T, NF = 2048, 4096
    rng = np.random.default_rng(seed)
    xr, xi, yr, yi = (rng.standard_normal((T, NF)).astype(np.float32)
                      for _ in range(4))
    xx, yy = xr * xr + xi * xi, yr * yr + yi * yi
    want = np.stack([xx + yy, xx - yy, 2 * (xr * yr + xi * yi),
                     -2 * (xi * yr - xr * yi)], axis=1)
    return {'stokes_detect kernel': verdict(
        lambda: pk.stokes_detect(*(jnp.asarray(a)
                                   for a in (xr, xi, yr, yi)),
                                 interpret=False),
        want, 1e-6, hard=True)}, None


def b_ring_permute(seed):
    """One remote-DMA ring hop over all devices (more than one only)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from bifrost_tpu.ops import pallas_kernels as pk
    from bifrost_tpu.parallel.mesh import create_mesh
    ndev = len(jax.devices())
    mesh = create_mesh({'sp': ndev})
    x = np.random.default_rng(seed).standard_normal(
        (ndev * 8, 128)).astype(np.float32)
    hop = jax.jit(jax.shard_map(
        lambda b: pk.ring_permute(b, 'sp', ndev), mesh=mesh,
        in_specs=P('sp'), out_specs=P('sp'), check_vma=False))
    return {'ring_permute kernel': verdict(
        lambda: hop(jnp.asarray(x)),
        np.roll(x, 8, axis=0), 0, hard=True)}, None


#: families only a force variable reaches (BF_USE_PALLAS; the
#: spectrometer forms other than the default selection's, which went
#: through phase A): a refusal there is reported and does not gate.
#: Everything else in phase B is tried by some automatic selection.
FORCED_ONLY = {'spectrometer', 'stokes_detect'}


def phase_b(args, sizes, fails, mesh_only=False):
    if mesh_only:
        families = [('ring_permute', b_ring_permute, (args.seed,))]
    else:
        families = [('fdmt', b_fdmt, (args.seed,)),
                    ('beamform', b_beamform, (args.seed,)),
                    ('correlate', b_correlate, (args.seed,)),
                    ('spectrometer', b_spectrometer, (args.seed, sizes)),
                    ('stokes_detect', b_stokes, (args.seed,))]
    table = {}
    for family, fn, fargs in families:
        t0 = time.time()
        verdicts, winner = fn(*fargs)
        table[family] = {'winner': winner, 'candidates': {
            cand: v['text'] for cand, v in verdicts.items()}}
        say('%s (%.0f s): default selection takes %s'
            % (family, time.time() - t0, winner or 'n/a'))
        for cand, v in verdicts.items():
            say('  %-30s %s' % (cand, v['text']))
            if v['status'] == 'refused' and family not in FORCED_ONLY:
                fails.add('%s: %s is on a default path and was %s'
                          % (family, cand, v['text']))
            if v['status'] == 'gate_failed' and v['hard']:
                fails.add('%s: %s %s' % (family, cand, v['text']))
        if winner is not None and \
                verdicts[winner]['status'] != 'compiled':
            fails.add('%s: the default selection took %r, which %s'
                      % (family, winner, verdicts[winner]['text']))
    return table


# ---------------------------------------------------------------------------
# phase C: three facts
# ---------------------------------------------------------------------------

def phase_c(args):
    import numpy as np
    import jax
    import jax.numpy as jnp
    facts = {}

    # (i) does block_until_ready return when a scalar read-back does?
    n = 4096
    x = jnp.ones((n, n), jnp.bfloat16) * 0.01

    def make(iters):
        def body(_, a):
            return (a @ x).astype(jnp.bfloat16)
        return jax.jit(lambda a: jax.lax.fori_loop(0, iters, body, a))

    def readback(y):
        return float(y[0, 0])

    probe = make(64)
    readback(probe(x))
    t0 = time.perf_counter()
    readback(probe(x))
    per_iter = (time.perf_counter() - t0) / 64
    iters = int(min(max(1.0 / max(per_iter, 1e-6), 64), 1 << 16))
    prog = make(iters)
    readback(prog(x))                          # compile + drain
    t0 = time.perf_counter()
    readback(prog(x))
    t_ref = time.perf_counter() - t0           # read-back alone
    t0 = time.perf_counter()
    y = prog(x)
    t_dispatch = time.perf_counter() - t0
    y.block_until_ready()
    t_bur = time.perf_counter() - t0
    readback(y)
    t_read = time.perf_counter() - t0
    facts['i_block_until_ready'] = {
        'program_s_by_readback_alone': t_ref,
        'dispatch_returned_s': t_dispatch,
        'block_until_ready_returned_s': t_bur,
        'readback_after_it_returned_s': t_read,
        'waits_for_the_device': bool(
            t_bur >= 0.9 * t_ref and t_read - t_bur <= 0.1 * t_ref)}

    # (ii) does a complex64 array survive device_put / np.asarray?
    rng = np.random.default_rng(args.seed)
    c = (rng.standard_normal(1 << 16) +
         1j * rng.standard_normal(1 << 16)).astype(np.complex64)
    try:
        back = np.asarray(jax.device_put(c))
        facts['ii_complex64_round_trip'] = {
            'survives': bool(np.array_equal(back, c)),
            'dtype_back': str(back.dtype)}
    except Exception as exc:
        facts['ii_complex64_round_trip'] = {
            'survives': False, 'raised': first_line(exc)}

    # (iii) needs a host without a chip: the rehearsal answers it
    facts['iii_jax_platforms_cpu_alone_selects_cpu'] = \
        'checked by the --allow-cpu rehearsal (this process holds a chip)'
    return facts


# ---------------------------------------------------------------------------

def main(argv=None):
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, 'bifrost_tpu')) or \
            not os.path.isdir(os.path.join(HERE, 'native')):
        sys.stderr.write('chip_smoke: %s holds no bifrost_tpu/ and '
                         'native/ — run it from the root of a checkout\n'
                         % HERE)
        return 2
    sys.path.insert(0, HERE)
    os.makedirs(args.out, exist_ok=True)
    dropped, bf_cache = clean_environment(args.out)

    # the ONE touch of JAX, in this process only
    import jax
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(jax.devices())}
    on_tpu = dev.platform == 'tpu'
    if not on_tpu and not args.allow_cpu:
        sys.stderr.write(
            'chip_smoke: no TPU: jax.devices()[0].platform is %r '
            '(JAX_PLATFORMS=%r).  This script checks the chip; the CPU '
            'rehearsal needs --allow-cpu.\n'
            % (dev.platform, os.environ.get('JAX_PLATFORMS')))
        return 2
    if args.devices > device['count']:
        sys.stderr.write('chip_smoke: --devices %d but JAX reports %d\n'
                         % (args.devices, device['count']))
        return 2
    rehearsal = not on_tpu
    say('device: %s' % json.dumps(device))
    vers = versions()
    say('versions: %s' % json.dumps(vers))
    if rehearsal:
        say('rehearsal: true (CPU; nothing below is a statement about '
            'the chip)')
    if dropped:
        say('removed from the environment: %s' % ', '.join(dropped))
    say('BF_CACHE_DIR (fresh): %s' % bf_cache)
    say('native: rebuilt native/build/libbifrost_tpu.so from '
        'native/*.cpp in %.1f s' % rebuild_native())

    import bifrost_tpu as bf
    from bifrost_tpu import native
    fails = Failures()
    if not native.available():
        fails.add('the native ring core was expected and is not loaded')
    cache_dir = bf.enable_compilation_cache()
    ncache0 = count_files(cache_dir)
    say('compile cache: %s (%s), %d files before'
        % (cache_dir, 'from JAX_COMPILATION_CACHE_DIR'
           if os.environ.get('JAX_COMPILATION_CACHE_DIR')
           else 'the checkout default', ncache0))

    sizes = (args.ntime or NTIME, NPOL, args.nfine or NFINE, RFACTOR)
    result = {'device': device, 'rehearsal': rehearsal,
              'seed': args.seed, 'versions': vers}
    with warnings.catch_warnings():
        # every warning prints, every time: a refusal must be seen
        warnings.simplefilter('always')
        result['phase_a'] = fails.phase('A', phase_a, args, sizes,
                                        on_tpu, fails)
        if rehearsal:
            say('\nphases B and C skipped: CPU rehearsal')
            result['facts'] = {
                'iii_jax_platforms_cpu_alone_selects_cpu': bool(
                    os.environ.get('JAX_PLATFORMS') == 'cpu'
                    and dev.platform == 'cpu')}
        elif args.devices > 1:
            result['phase_b'] = fails.phase('B (ring_permute alone)',
                                            phase_b, args, sizes, fails,
                                            mesh_only=True)
            say('\nthe rest of phase B and phase C skipped: the '
                '--devices run is the four-chip check')
        else:
            result['phase_b'] = fails.phase('B', phase_b, args, sizes,
                                            fails)
            result['facts'] = fails.phase('C', phase_c, args)
    if 'facts' in result:
        say('facts: %s' % json.dumps(result['facts'], indent=1))
    # forced implementations raise; what lands here was tried by an
    # automatic selection, on a default path
    from bifrost_tpu.ops import mprobe
    refused = mprobe.refusals()
    result['refused_by_automatic_selection'] = refused
    if refused:
        fails.add('the automatic selection tried %d candidate(s) that '
                  'were refused: %s' % (len(refused),
                                        json.dumps(refused, indent=1)))
    ncache1 = count_files(cache_dir)
    say('\ncompile cache: %d files before, %d after' % (ncache0, ncache1))
    result.update(compile_cache={'dir': cache_dir, 'files_before': ncache0,
                                 'files_after': ncache1},
                  failures=fails.items, ok=not fails.items)
    with open(os.path.join(args.out, 'result.json'), 'w') as f:
        json.dump(result, f, indent=1, default=str)
    summary = {'ok': not fails.items, 'device': device}
    if rehearsal:
        summary['rehearsal'] = True
    if fails.items:
        summary['failures'] = fails.items
    say(json.dumps(summary))
    return 0 if not fails.items else 1


if __name__ == '__main__':
    sys.exit(main())
