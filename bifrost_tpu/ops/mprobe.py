"""Shared measured-implementation selection.

The FDMT core probe (ops/fdmt.py) established the policy; this module
generalizes it for other ops (LinAlg GEMM paths):

- candidates are MEASURED at the actual shape, never asserted — r3's
  artifact caught a hard-coded "TPU default" running 2.3x slower than
  the alternative at the bench shape;
- timing is best-of-N so first-session jitter (compile residue)
  cannot freeze a slower winner into the cache;
- winners are cached in-process and on disk, keyed by backend, device
  kind, package version and a caller-supplied shape signature;
- the disk entry is written only when every candidate ran clean AND the
  winner's margin over the runner-up exceeds a noise threshold — a
  transient compile failure or a coin-flip ranking is re-measured next
  session instead of being frozen (ADVICE r4);
- a COIN-FLIP winner (margin inside the noise threshold — the flag
  ``tools/mprobe_report.py`` renders) is additionally re-raced WITHIN
  a session after ``BF_MPROBE_REPROBE`` uses (default 200; 0 disables)
  instead of being served from the in-process cache forever — long-
  lived pipelines whose shapes shift under the auto-tuner
  (docs/autotune.md) keep their kernel races honest;
- a candidate the selection TRIES and the backend refuses (Mosaic or
  XLA raising at compile or run) is never dropped without a word:
  :func:`refused` warns once with the compiler's message and keeps it
  in the record blocks publish (``impl_info`` / ProcLog
  ``<block>/impl``).  Every selection seam — the spectrometer's
  accuracy and compile probes, the FDMT core gate, the engines'
  accuracy gates and :func:`select` itself — reports through it.

Reference analogue: the reference hand-picks kernels per shape at
compile time (src/linalg.cu:210-226 drops to a custom cherk below
n=896); on TPU the ranking depends on XLA's lowering, so it is probed.
"""

from __future__ import annotations

import json
import os
import threading
import time
import warnings

__all__ = ['select', 'peek', 'backend_tag', 'cache_path', 'refused',
           'refusals', 'accuracy_gate']

_refusals = {}
_refusals_lock = threading.Lock()


def refused(family, candidate, exc):
    """Record that the automatic selection of ``family`` tried
    ``candidate`` and the backend refused it with ``exc``.  Emits ONE
    RuntimeWarning per distinct (family, candidate, message) carrying
    the compiler's message, and keeps its first line for
    :func:`refusals`.  Returns the recorded line."""
    text = str(exc).strip()
    line = '%s: %s' % (type(exc).__name__,
                       text.splitlines()[0] if text else '')
    key = '%s/%s' % (family, candidate)
    with _refusals_lock:
        new = _refusals.get(key) != line
        _refusals[key] = line
    if new:
        warnings.warn(
            '%s: candidate %r was tried by the automatic selection and '
            'refused; the selection continues without it.  %s: %s'
            % (family, candidate, type(exc).__name__, text[:2000]),
            RuntimeWarning, stacklevel=2)
    return line


def refusals():
    """{'family/candidate': first line of the refusal} for every
    candidate this process's automatic selection tried and the backend
    refused — what FusedBlock publishes under ``impl_info['refused']``
    and chip_smoke.py reads."""
    with _refusals_lock:
        return dict(_refusals)

_cache = {}
#: (name, full_key) -> uses served from cache for a COIN-FLIP winner
#: (margin inside the noise threshold); when a counter reaches the
#: BF_MPROBE_REPROBE budget the entry is evicted and re-measured
_flip_uses = {}


def _reprobe_budget():
    """Cache-uses budget for coin-flip winners (``BF_MPROBE_REPROBE``,
    default 200; 0 disables the re-race)."""
    try:
        return int(os.environ.get('BF_MPROBE_REPROBE', '') or 200)
    except ValueError:
        return 200


def _coin_flip(ms, noise):
    """Whether a measurement's ranking is inside the noise threshold
    (the same margin tools/mprobe_report.py flags as COIN-FLIP)."""
    try:
        ranked = sorted(float(v) for v in ms.values())
    except (TypeError, ValueError):
        return False
    return (len(ranked) >= 2 and ranked[0] > 0 and
            ranked[1] < ranked[0] * noise)


def _flip_spent(name, full_key, ms, noise):
    """Count one cache use of a coin-flip winner; True when the
    reprobe budget is exhausted (caller evicts and re-measures)."""
    budget = _reprobe_budget()
    if budget <= 0 or not _coin_flip(ms, noise):
        return False
    key = (name, full_key)
    uses = _flip_uses.get(key, 0) + 1
    if uses >= budget:
        _flip_uses.pop(key, None)
        return True
    _flip_uses[key] = uses
    return False


def accuracy_gate(family, fns, make_args, rtol, lossy=(), base='xla',
                  max_bytes=None):
    """(keep, had_errors): the candidates of ``fns`` ({name: fn}) whose
    output at the actual shape stays within ``rtol`` (relative to the
    baseline's peak) of the ``base`` candidate's.  Runs once per
    (family, shape), before any timing.  A candidate that raises is
    reported through :func:`refused` and sets ``had_errors``: the
    caller must not freeze a winner chosen from the reduced field to
    disk.  If the baseline itself raised, no accuracy can be
    evaluated: ``lossy`` candidates are dropped rather than admitted
    unchecked.

    ONE candidate's output is alive beside the baseline's at a time.
    At production shapes that matters: the ci8 correlation's (F, n, n)
    complex64 visibilities are 2 GB per candidate at the BASELINE
    shape, and holding all five at once exhausted the v5e's 16 GB —
    the last candidate was refused for HBM, not for anything it did
    (measured on the chip, PR 21).  With ``max_bytes`` the baseline's
    output is counted from its shape before anything runs, and a gate
    that would make more is an error of the caller, which is to hand
    the gate a tile of its work (ops.beamform.probe_nframe): the
    beamformer's baseline at a deployment's gulp is 14.5 GB."""
    import jax
    import jax.numpy as jnp
    args = make_args()
    if max_bytes is not None and base in fns:
        out = jax.eval_shape(fns[base], *args)
        nbytes = int(out.size) * out.dtype.itemsize
        if nbytes > max_bytes:
            raise ValueError(
                '%s: the gate\'s %r baseline would make %d bytes at '
                'shape %s, over the %d a gate may; gate a tile of it'
                % (family, base, nbytes, tuple(out.shape), max_bytes))
    errored = []

    def run(name):
        try:
            return fns[name](*args)
        except Exception as e:
            refused(family, name, e)
            errored.append(name)
            return None

    ref = run(base) if base in fns else None
    keep = [base] if ref is not None else []
    scale = (float(jnp.max(jnp.abs(ref))) or 1.0) \
        if ref is not None else None
    for name in fns:
        if name == base:
            continue
        y = run(name)
        if y is None:
            continue
        if ref is None:
            if name not in lossy:
                keep.append(name)
        elif float(jnp.max(jnp.abs(y - ref))) / scale <= rtol:
            keep.append(name)
        del y
    return keep, bool(errored)


def peek(name, key):
    """Cached (winner, ms, errors) for ``key`` or None — consults the
    in-process and disk caches without measuring anything.  Safe to
    call under a jax trace (pure-Python file read)."""
    full_key = '%s|%s' % (backend_tag(), key)
    fam = _cache.get(name, {})
    if full_key in fam:
        return fam[full_key]
    try:
        with open(cache_path(name)) as f:
            disk = json.load(f)
    except (OSError, ValueError):
        return None
    if full_key in disk:
        entry = (disk[full_key].get('winner'),
                 disk[full_key].get('ms', {}), {})
        _cache.setdefault(name, {})[full_key] = entry
        return entry
    return None


def cache_path(name):
    base = os.environ.get('BF_CACHE_DIR')
    if base is None:
        base = os.path.join(os.path.expanduser('~'), '.bifrost_tpu')
    return os.path.join(base, '%s.json' % name)


_backend_tag = None


def backend_tag():
    """backend:device-kind:version prefix for probe keys — a winner
    measured on one TPU generation or package version must not be
    reused where the ranking can differ.  Constant per process, so
    memoized: peek() sits on the gulp hot path."""
    global _backend_tag
    if _backend_tag is not None:
        return _backend_tag
    try:
        import jax
        backend = jax.default_backend()
    except Exception:
        backend = 'unknown'
    try:
        import jax
        kind = jax.devices()[0].device_kind.replace(' ', '_')
    except Exception:
        kind = 'unknown'
    try:
        from bifrost_tpu import __version__ as ver
    except Exception:
        ver = '0'
    tag = '%s:%s:v%s' % (backend, kind, ver)
    if backend != 'unknown':        # don't freeze a failed init
        _backend_tag = tag
    return tag


def select(name, key, candidates, make_args, n_reps=3, noise=1.10,
           n_calls=2, persist=True):
    """Measure ``candidates`` and return (winner, ms_per_call, errors).

    name        cache-file name (one JSON per op family)
    key         shape/config signature (backend tag is prepended)
    candidates  {impl_name: fn} — fn(*args) must be jittable-callable;
                compile happens on the first timed-excluded call
    make_args   () -> tuple of device arrays at the ACTUAL shape
    n_calls     calls per timed rep (amortizes per-call dispatch)
    persist     False if the caller already knows this measurement is
                incomplete (e.g. a candidate errored upstream) — the
                winner is used this session but not frozen to disk

    A cached winner (in-process or disk — peek() may have populated
    the in-process cache from disk) is revalidated against the current
    candidate set: a stale name from an older build falls through to a
    fresh measurement instead of crashing the caller.
    """
    full_key = '%s|%s' % (backend_tag(), key)
    fam = _cache.setdefault(name, {})
    reprobe = False
    if full_key in fam and fam[full_key][0] in candidates:
        entry = fam[full_key]
        if not _flip_spent(name, full_key, entry[1], noise):
            return entry
        del fam[full_key]            # coin-flip budget spent: re-race
        reprobe = True
    path = cache_path(name)
    disk = {}
    try:
        with open(path) as f:
            disk = json.load(f)
    except (OSError, ValueError):
        pass
    if full_key in disk and disk[full_key].get('winner') in candidates:
        if reprobe:
            # the spent entry usually ALSO sits on disk (persisted
            # under an older pre-decisive policy): reloading it here
            # would reset the budget and serve the stale winner
            # forever — drop it and fall through to the re-race
            disk.pop(full_key, None)
        else:
            entry = (disk[full_key]['winner'],
                     disk[full_key].get('ms', {}), {})
            # a disk coin flip is budgeted like the in-process case
            if not _flip_spent(name, full_key, entry[1], noise):
                fam[full_key] = entry
                return entry
            disk.pop(full_key, None)

    import jax
    args = make_args()
    ms = {}
    errors = {}
    for cname, fn in candidates.items():
        try:
            jax.block_until_ready(fn(*args))        # compile + drain
            best = float('inf')
            for _ in range(n_reps):
                t0 = time.perf_counter()
                for _ in range(n_calls):
                    y = fn(*args)
                jax.block_until_ready(y)
                best = min(best, (time.perf_counter() - t0) / n_calls)
            ms[cname] = round(best * 1e3, 3)
        except Exception as e:
            errors[cname] = refused(name, cname, e)
    if not ms:
        return (None, {}, errors)
    winner = min(ms, key=ms.get)
    entry = (winner, ms, errors)
    fam[full_key] = entry
    ranked = sorted(ms.values())
    decisive = len(ranked) < 2 or ranked[1] >= ranked[0] * noise
    if persist and not errors and decisive:
        disk[full_key] = {'winner': winner, 'ms': ms}
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + '.tmp%d' % os.getpid()
            with open(tmp, 'w') as f:
                json.dump(disk, f, indent=1)
            os.replace(tmp, path)
        except OSError:
            pass
    return entry
